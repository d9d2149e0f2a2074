"""The benchmark's machinery: load a cell by name, build the served model
and engine, warm the cell's shapes, drive the open-loop window, and read
the results. ``run.py`` is the command line over it; ``sweep.py`` and
``control.py`` reuse it.

Everything that belongs to one configuration, mix, cell or metric is a
file found by name:

  BENCHMARK.json                   the cells and metrics
  bench/configs/<config>.json      sizes as run, source, cuts
  bench/families/<family>.py       config -> program ModelConfig, weights
  bench/reference/<family>.py      the plain float32 reference
  bench/traffic/<traffic>.json     a traffic mix (bench/arrivals.py reads it)
  bench/cells/<workload>.json      the cell's rate, serve settings, limits
  bench/metrics/<metric>.py        one reader per metric
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"

# How long the engine may run past the window's close so that requests
# due in it can finish (open-loop cells only); one still unfinished then
# has failed.
DRAIN_LIMIT_S = 60.0
# Served tokens the correctness sample aims for (the longest finished
# request first, then others drawn from the seed), and its most requests.
SAMPLE_TOKENS = 1024
SAMPLE_MAX_REQUESTS = 16
# Length of the traced slice in the middle of a --trace 1 window.
TRACE_S = 8.0


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with every file it names."""
    name: str
    chips: int
    conf: dict
    mix: dict
    settings: dict
    spec: dict

    def metrics(self, kind: str) -> List[dict]:
        """The cell's ``end_to_end`` or ``per_layer`` metrics."""
        return [m for m in self.spec[kind]
                if "workloads" not in m or self.name in m["workloads"]]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    try:
        w = next(w for w in spec["workloads"] if w["name"] == name)
    except StopIteration:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json") from None
    c = next(c for c in spec["configs"] if c["name"] == w["config"])
    bench = root / "bench"
    return Cell(name=name, chips=int(w["chips"]),
                conf=load_json(root / c["file"]),
                mix=load_json(bench / "traffic" / f"{w['traffic']}.json"),
                settings=load_json(bench / "cells" / f"{name}.json"),
                spec=spec)


def family(conf: dict):
    return load_module(BENCH / "families" / f"{conf['family']}.py",
                       f"bench_family_{conf['family']}")


def reference(conf: dict):
    return load_module(BENCH / "reference" / f"{conf['family']}.py",
                       f"bench_reference_{conf['family']}")


def metric_reader(name: str):
    """``bench/metrics/<name>.py``, else the reader of the quantity before
    the first dot (``decode_step_ms.chat`` -> ``decode_step_ms.py``)."""
    for stem in (name, name.split(".")[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.exists():
            return load_module(path, "bench_metric_" + stem.replace(".", "_"))
    raise FileNotFoundError(f"no reader for metric {name!r}")


class CompileCounter:
    """Compilations (backend compiles and persistent-cache loads) from
    JAX's monitoring events."""

    DURATION = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == self.DURATION:
            self.compiles += 1

    def _event(self, event, **_):
        if event == self.HIT:
            self.cache_hits += 1

    @property
    def total(self) -> int:
        return self.compiles + self.cache_hits


def device_check(chips: int):
    """The devices a run may use, or SystemExit when JAX finds no TPU or
    fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, all of its bits used."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_weights(cell: Cell, seed: int):
    import jax
    fam = family(cell.conf)
    params = jax.jit(lambda k: fam.init_weights(cell.conf, k))(seed_key(seed))
    return jax.block_until_ready(params)


def make_engine(cell: Cell, params, traced: bool):
    from repro.configs.base import ObsConfig, ServeConfig
    from repro.serve.engine import Engine
    obs = ObsConfig(enabled=True, fence_device=False, jax_annotations=True,
                    timeline=False) if traced else ObsConfig()
    scfg = ServeConfig(paged=True, obs=obs, **cell.settings["serve"])
    return Engine(family(cell.conf).model_config(cell.conf), params, scfg)


def warm_up(eng, seed: int) -> None:
    """Serve one request that takes every step shape the cell's traffic
    uses: a full prefill chunk, a one-token last chunk and decode ticks;
    then start a fresh metrics window."""
    from repro.serve.scheduler import Request
    rng = np.random.default_rng([seed, 1])
    n = eng.scfg.prefill_chunk + 1
    req = Request(rid=eng.new_rid(),
                  prompt=rng.integers(0, eng.cfg.vocab, n, dtype=np.int32),
                  max_new=3)
    eng.run([req], max_steps=100)
    if not req.done or len(req.tokens_out) != 3:
        raise RuntimeError("warm-up request did not finish")
    eng.reset_metrics()


@dataclasses.dataclass
class Track:
    """What the benchmark saw of one request: when it was due (window
    clock) and the host time at which each of its tokens came back."""
    off: object
    req: object
    due_abs: float
    token_t: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Tick:
    """One ``Engine.step`` as the benchmark saw it: host clock around the
    call, the rows it ran as ``(n_tokens, ctx_before, sampled)``, how
    many of them were prefill rows, and the scheduler's waiting queue
    after it."""
    step: int
    t0: float
    t1: float
    rows: List[tuple]
    prefill_rows: int
    waiting: int

    @property
    def has_prefill(self) -> bool:
        return self.prefill_rows > 0


@dataclasses.dataclass
class Window:
    """Everything one measured window produced."""
    seconds: float
    t0: float
    t_end: float
    t_stop: float
    tracks: Dict[int, Track]
    ticks: List[Tick]
    refused: list
    counters_end: dict
    compiles_in_window: int


def _snapshot(eng) -> list:
    """(entry, state, pos, ctx_len, n_out) of every entry a step may run:
    the active ones and the head of the waiting queue."""
    s = eng.sched
    ents = list(s.active.values()) + s.waiting[:eng.scfg.max_batch]
    return [(e, e.state, e.pos, e.ctx_len, len(e.req.tokens_out))
            for e in ents]


def _rows(snap) -> List[tuple]:
    """Rows a step ran, from the state before and after it:
    ``(n_tokens, ctx_before, sampled, is_decode)``."""
    from repro.serve.scheduler import State
    rows = []
    for e, state, pos, ctx, n_out in snap:
        if state == State.RUNNING:
            if e.ctx_len == ctx + 1:
                rows.append((1, ctx, True, True))
        elif e.pos > pos and e.state != State.WAITING:
            grew = len(e.req.tokens_out) > n_out
            rows.append((e.pos - pos, pos, grew, False))
    return rows


def serve_window(eng, offered, seconds: float, counter: CompileCounter,
                 trace_dir: Optional[str] = None) -> Window:
    """Drive the open-loop window: hand each request to the engine once
    it is due, step while there is work, sleep to the next due time when
    there is none; then run on until every request due in the window has
    finished (at most DRAIN_LIMIT_S)."""
    import jax
    pending = collections.deque(offered)
    tracks: Dict[int, Track] = {}
    live: Dict[int, Track] = {}
    ticks: List[Tick] = []
    refused = []
    annotate = trace_dir is not None
    trace_at = max(0.0, seconds / 2 - TRACE_S / 2) if annotate else None
    tracing = traced_done = False
    traced_annot = None
    from repro.serve.sampling import SamplingParams
    from repro.serve.scheduler import Request
    GREEDY_LOGPROBS = SamplingParams(temperature=0.0, logprobs=True)
    c0 = c_end = counter.total
    t0 = time.monotonic()
    end = t0 + seconds
    step = 0
    counters_end = None
    draining = False
    while True:
        now = time.monotonic()
        if counters_end is None and now >= end:
            counters_end = _counters(eng)
            c_end = counter.total
            if not live:
                break
            draining = True
        if draining and (not live or now >= end + DRAIN_LIMIT_S):
            break
        if annotate and not tracing and not traced_done \
                and now >= t0 + trace_at:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_profile_options())
            tracing = True
            traced_annot = jax.profiler.TraceAnnotation("bench.traced")
            traced_annot.__enter__()
        if tracing and now >= t0 + trace_at + TRACE_S:
            traced_annot.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing, traced_done = False, True
        while not draining and pending and t0 + pending[0].due <= now:
            off = pending[0]
            req = Request(rid=eng.new_rid(), prompt=off.prompt,
                          max_new=off.max_new, sampling=GREEDY_LOGPROBS)
            if eng.add_request(req):
                tr = Track(off, req, t0 + off.due)
                tracks[req.rid] = live[req.rid] = tr
                pending.popleft()
            else:
                refused.append(pending.popleft())
        if eng.sched.idle:
            nxt = t0 + pending[0].due if pending and not draining else end
            nap = min(nxt, end) - time.monotonic()
            if nap > 0:
                time.sleep(nap)
            continue
        snap = _snapshot(eng)
        ts = time.monotonic()
        if annotate:
            with jax.profiler.StepTraceAnnotation("bench.tick",
                                                  step_num=step):
                eng.step()
        else:
            eng.step()
        te = time.monotonic()
        rows = _rows(snap)
        ticks.append(Tick(step, ts, te, [r[:3] for r in rows],
                          sum(1 for r in rows if not r[3]),
                          len(eng.sched.waiting)))
        step += 1
        for rid in list(live):
            tr = live[rid]
            n = len(tr.req.tokens_out)
            while len(tr.token_t) < n:
                tr.token_t.append(te)
            if tr.req.done:
                del live[rid]
    t_stop = time.monotonic()
    if tracing:
        traced_annot.__exit__(None, None, None)
        jax.profiler.stop_trace()
    return Window(seconds=seconds, t0=t0, t_end=end, t_stop=t_stop,
                  tracks=tracks, ticks=ticks, refused=refused,
                  counters_end=counters_end,
                  compiles_in_window=c_end - c0)


def _profile_options():
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    return o


def _counters(eng) -> dict:
    reg = eng.metrics.registry
    s = eng.metrics.summary()
    return {"prefill_tokens": reg.value("engine_prefill_tokens_total"),
            "generated_tokens": reg.value("request_generated_tokens_total"),
            "preemptions": reg.value("sched_preemptions_total"),
            "gaps_prefill_overlap": s["tpot_overlap_samples"],
            "gaps_steady": s["tpot_steady_samples"]}


def sample_for_check(win: Window, seed: int) -> List[Track]:
    """Finished requests to compare with the reference: the longest, then
    others in an order drawn from the seed, up to SAMPLE_TOKENS served
    tokens or SAMPLE_MAX_REQUESTS requests."""
    done = [t for t in win.tracks.values()
            if t.req.done and t.req.tokens_out]
    if not done:
        return []
    done.sort(key=lambda t: t.off.index)
    longest = max(done, key=lambda t: len(t.off.prompt)
                  + len(t.req.tokens_out))
    rest = [t for t in done if t is not longest]
    order = np.random.default_rng([seed, 2]).permutation(len(rest))
    out, n = [longest], len(longest.req.tokens_out)
    for i in order:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX_REQUESTS:
            break
        out.append(rest[i])
        n += len(rest[i].req.tokens_out)
    return out


def check(cell: Cell, params, sample: List[Track], control: bool = False
          ) -> dict:
    """Score the sample's served tokens with the float32 reference.

    ``max_logit_gap``: the widest gap, over the served tokens, by which
    the served token's reference logit lies below the reference's best.
    ``mean_logprob_err``: the mean, over the served tokens, of the
    difference between the log-probability the program gave the token
    (from the logits it sampled from) and the reference's;
    ``max_logprob_err`` is the widest such difference. With ``control``
    the float8 control stands in for the program: its log-probabilities,
    and the gap of the token it puts first."""
    ref = reference(cell.conf)
    errs, gaps = [], []
    for t in sample:
        toks = np.asarray(t.req.tokens_out)
        gap, lp = ref.served_scores(cell.conf, params, t.off.prompt, toks)
        got = np.asarray(t.req.logprobs_out, np.float64)
        if control:
            gap, got = ref.served_scores(cell.conf, params, t.off.prompt,
                                         toks, control=True)
        errs.append(np.abs(np.asarray(got, np.float64) - lp))
        gaps.append(gap)
    short = sum(1 for t in sample
                if len(t.req.tokens_out) != t.off.max_new)
    err = np.concatenate(errs) if errs else np.zeros(0)
    gap = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"mean_logprob_err": float(err.mean()) if err.size else None,
            "max_logprob_err": float(err.max()) if err.size else None,
            "max_logit_gap": float(gap.max()) if gap.size else None,
            "served_tokens": int(err.size),
            "requests": len(sample),
            "short_requests": short}


def peak_memory(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def free(eng) -> None:
    """Drop the engine and its KV pool before the reference runs."""
    import jax
    jax.block_until_ready(eng.runner.cache)
    eng.runner.cache = None
    del eng
    gc.collect()


def ensure_src() -> None:
    """Put the system under test on the path; refuse to run without it."""
    if not (SRC / "repro" / "__init__.py").exists():
        raise SystemExit(f"bench: the system under test is missing "
                         f"({SRC / 'repro'})")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
