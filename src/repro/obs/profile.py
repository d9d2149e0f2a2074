"""Roofline attainment profiling for the serving path.

NeCTAr's evaluation judges every unit against its roofline — decode is
weight-bandwidth-bound, prefill compute-bound — and reports efficiency
(GOPs/W), not stopwatch time. This module is the serving-side analogue:
per compiled step shape (W, P) of ``ModelRunner.step`` (the generation
group's width W: 1 for decode, k_max+1 for verify; the prefill group's
rows P, 0 for none) it joins

  * STATIC cost — FLOPs / bytes of the bucket's executable, total and
    per ``jax.named_scope`` (obs.costmodel: unrolled-twin
    ``cost_analysis()`` + HLO-text dot attribution), plus the sampler
    executable as the "sample" scope — with
  * MEASURED time — the tracer's per-tick fenced ``device_wait`` spans
    (``tick_stats`` grouped by ``gen_width`` and ``prefill_bucket``,
    exactly the runner's jit key)

and emits per-bucket achieved GFLOP/s, GB/s, arithmetic intensity, and
roofline ATTAINMENT: ``max(flops/peak, bytes/bw) / measured_s``, i.e.
what fraction of the active hardware spec's best-case step time we
realize (clamped to (0, 1]; ``roofline/hw.active_chip`` looks the spec
up by the device's ``device_kind`` and raises for an unknown kind).

Surfaces: ``metrics.summary()["bucket_attainment"]``, the Prometheus
endpoint (``bucket_attainment_*{bucket="..."}`` labeled gauges),
counter tracks in the Perfetto export, ``launch.serve --profile``
(prints ``attainment_table``), and the ``serving_roofline`` benchmark
suite. Off by default (``ObsConfig.profile``); the static-cost twin
compiles lazily per observed bucket, never on the serving hot path.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional

from repro.obs import costmodel
from repro.roofline import hw

# table columns: (header, width, format) over report() row keys
_COLS = (
    ("bucket", 10, "{:<10}"),
    ("ticks", 6, "{:>6d}"),
    ("dev_ms", 8, "{:>8.3f}"),
    ("GFLOP/s", 9, "{:>9.2f}"),
    ("GB/s", 8, "{:>8.2f}"),
    ("AI", 7, "{:>7.2f}"),
    ("attain", 7, "{:>7.4f}"),
    ("bound", 8, "{:<8}"),
)


def bucket_label(width: int, prefill_bucket: int = 0) -> str:
    """Human name for a (W, P) step shape: "decode", "verify<K+1>", and
    with a prefill group "mixed_p<P>" and "verify<K+1>_p<P>" — stable
    keys for gauges/baselines."""
    label = "decode" if width == 1 else f"verify{width}"
    if not prefill_bucket:
        return label
    return f"mixed_p{prefill_bucket}" if width == 1 \
        else f"{label}_p{prefill_bucket}"


class ServingProfiler:
    """Per-bucket attainment over a live runner. Static costs compile
    lazily (once per observed bucket) and cache for the runner's
    lifetime — reset_metrics() keeps them: the executables don't change
    when the measurement window restarts."""

    def __init__(self, runner, chip: Optional[hw.Chip] = None,
                 n_chips: Optional[int] = None):
        self.runner = runner
        self.chip = chip if chip is not None else hw.active_chip()
        self.n_chips = n_chips if n_chips is not None else (
            runner.mesh.size if runner.mesh is not None else 1)
        self._costs: Dict[tuple, costmodel.StepCost] = {}
        self._sample: Optional[Dict[str, float]] = None

    # --- static side -----------------------------------------------------
    def static_cost(self, width: int,
                    prefill_bucket: int = 0) -> costmodel.StepCost:
        key = (width, prefill_bucket)
        c = self._costs.get(key)
        if c is None:
            c = self._costs[key] = costmodel.step_cost(
                self.runner, width, prefill_bucket)
        return c

    def sample_cost(self) -> Dict[str, float]:
        if self._sample is None:
            scfg, cfg = self.runner.scfg, self.runner.cfg
            self._sample = costmodel.sampler_cost(
                scfg.max_batch, cfg.vocab, cfg.n_codebooks)
        return self._sample

    def _bucket_totals(self, width: int, prefill_bucket: int = 0):
        """(flops, bytes, by_scope) of one tick of this bucket: the step
        executable plus the per-tick sampler call."""
        cost = self.static_cost(width, prefill_bucket)
        samp = self.sample_cost()
        by_scope = {k: dict(v) for k, v in cost.by_scope.items()}
        by_scope["sample"] = {"flops": samp["flops"],
                              "bytes": samp["bytes"]}
        return (cost.flops + samp["flops"],
                cost.hbm_bytes + samp["bytes"], by_scope)

    # --- measured join ---------------------------------------------------
    @staticmethod
    def _grouped(tick_stats: Iterable[dict]) -> Dict[tuple, List[float]]:
        """device_ms samples per (gen_width, prefill_bucket) — the
        runner's jit key, recovered from each tick's recorded attrs.
        Ticks that ran no device step (gen_width absent or zero device
        time) don't belong to any bucket."""
        groups: Dict[tuple, List[float]] = {}
        for t in tick_stats:
            if not t.get("gen_width") or t.get("device_ms", 0.0) <= 0.0:
                continue
            key = _step_key(t)
            groups.setdefault(key, []).append(float(t["device_ms"]))
        return groups

    def report(self, tick_stats: Iterable[dict]) -> List[dict]:
        """One row per observed bucket; see module docstring for the
        attainment formula. Empty when nothing was measured (profiling
        needs tracing's fenced device_wait spans)."""
        rows = []
        for (w, pb), dms in sorted(self._grouped(tick_stats).items()):
            flops, byts, by_scope = self._bucket_totals(w, pb)
            dev_ms = sum(dms) / len(dms)
            dev_s = dev_ms / 1e3
            terms = hw.roofline_terms(flops, byts, 0.0, self.n_chips,
                                      chip=self.chip)
            lb = terms["step_s_lower_bound"]
            attain = min(1.0, lb / dev_s) if dev_s > 0 and lb > 0 \
                else None
            scoped = sum(v["flops"] for k, v in by_scope.items()
                         if k != "other")
            rows.append({
                "bucket": bucket_label(w, pb),
                "width": w,
                "prefill_bucket": pb,
                "ticks": len(dms),
                "dev_ms": dev_ms,
                "flops": flops,
                "hbm_bytes": byts,
                "GFLOP/s": flops / dev_s / 1e9,
                "GB/s": byts / dev_s / 1e9,
                "AI": flops / byts if byts else 0.0,
                "attain": attain,
                "bound": terms["bound"],
                "chip": self.chip.name,
                "n_chips": self.n_chips,
                "scopes": {k: {"flops": v["flops"], "bytes": v["bytes"],
                               "flops_frac": (v["flops"] / flops
                                              if flops else 0.0)}
                           for k, v in sorted(by_scope.items())},
                "scope_attributed_frac": (scoped / flops
                                          if flops else 0.0),
            })
        return rows

    # --- export adapters -------------------------------------------------
    def gauges(self) -> Dict[str, Dict[str, float]]:
        """{bucket label: {metric: value}} for the registry's labeled
        gauge group (``bucket_attainment_<metric>{bucket="..."}``) —
        re-pulled from the live tracer at every scrape."""
        out: Dict[str, Dict[str, float]] = {}
        for r in self.report(self.runner.tracer.tick_stats):
            out[r["bucket"]] = {
                "achieved_gflops": r["GFLOP/s"],
                "achieved_gbs": r["GB/s"],
                "arith_intensity": r["AI"],
                "attainment": r["attain"] if r["attain"] is not None
                else 0.0,
                "device_ms_mean": r["dev_ms"],
                "ticks": r["ticks"],
            }
        return out

    def tick_counters(self, tick_stats: Iterable[dict]):
        """Per-tick Perfetto counter-track samples: (series name, tick
        start seconds, value) for achieved GFLOP/s, GB/s, and attainment
        — the time-resolved twin of the per-bucket means."""
        out = []
        for t in tick_stats:
            w = t.get("width")
            dev_ms = t.get("device_ms", 0.0)
            if not w or dev_ms <= 0.0:
                continue
            flops, byts, _ = self._bucket_totals(*_step_key(t))
            dev_s = dev_ms / 1e3
            terms = hw.roofline_terms(flops, byts, 0.0, self.n_chips,
                                      chip=self.chip)
            t0 = float(t.get("t0_s", 0.0))
            out.append(("achieved_gflops", t0, flops / dev_s / 1e9))
            out.append(("achieved_gbs", t0, byts / dev_s / 1e9))
            out.append(("roofline_attainment", t0,
                        min(1.0, terms["step_s_lower_bound"] / dev_s)))
        return out


def _step_key(t: dict) -> tuple:
    """A tick's (gen_width, prefill_bucket) from its attrs."""
    return int(t["gen_width"]), int(t.get("prefill_bucket", 0))


def attainment_table(rows: List[dict]) -> str:
    """The per-bucket attainment report as a fixed-width table, with a
    per-scope FLOP split line under each bucket row."""
    if not rows:
        return "(no profiled ticks — run with tracing+profiling on)"
    head = " ".join(f"{name:>{w}}" if fmt.startswith("{:>") else
                    f"{name:<{w}}" for name, w, fmt in _COLS)
    lines = [f"roofline attainment vs {rows[0]['chip']} "
             f"(n_chips={rows[0]['n_chips']})", head, "-" * len(head)]
    for r in rows:
        vals = []
        for name, _w, fmt in _COLS:
            v = r[name]
            vals.append(fmt.format(v if v is not None else float("nan")))
        lines.append(" ".join(vals))
        split = "  ".join(
            f"{k}={v['flops_frac'] * 100:.1f}%"
            for k, v in r["scopes"].items() if v["flops"] > 0)
        lines.append(f"           flops: {split}")
    return "\n".join(lines)


__all__ = ["ServingProfiler", "attainment_table", "bucket_label"]
