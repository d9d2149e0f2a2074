"""One run of one benchmark cell on the chip this process holds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed as ``setup_s``): weights from the seed, made on the device
in their served type by one jitted call; the paged engine as
``repro.launch.serve.build`` makes it (``Engine(cfg, params,
ServeConfig(paged=True, ...))``, sync tick, default attention backend);
one warm-up request through every step shape the cell uses. Then the
open-loop window of ``--seconds``; then the check of the window's served
tokens against the plain float32 reference.

With ``--trace 0`` the result line carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
the middle of the window. The last line of standard output is the JSON
result; the last lines of standard error give each compared number
beside its limit. Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import arrivals, harness, readings, trace_reduce  # noqa: E402
from bench.peaks import peaks  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _read_trace(trace_dir: str) -> dict:
    paths = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    if not paths:
        raise RuntimeError("the profiler wrote no trace")
    return trace_reduce.from_xplane(sorted(paths)[-1])


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.ensure_src()
    import jax
    devs = harness.device_check(cell.chips)
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = execute(cell, args.seed, args.seconds, bool(args.trace), devs,
                  peaks(devs[0].device_kind))
    print(json.dumps(out))
    return 0


def execute(cell, seed: int, seconds: float, traced: bool, devs, peak
            ) -> dict:
    """Set-up, window, readings and check of one run; returns the result
    line's object and prints the compared numbers to standard error."""
    import jax
    counter = harness.CompileCounter()
    params = harness.make_weights(cell, seed)
    eng = harness.make_engine(cell, params, traced=traced)
    harness.warm_up(eng, seed)
    offered = arrivals.generate(cell.mix, cell.settings.get("rate_per_s", 0),
                                seconds, seed, cell.conf["vocab_size"])
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    try:
        win = harness.serve_window(eng, offered, seconds, counter,
                                   trace_dir)
        setup_s = win.t0 - T_START
        print(f"bench: compiles_in_window {win.compiles_in_window}",
              file=sys.stderr)
        mem = harness.peak_memory(devs)
        trace = _read_trace(trace_dir) if traced else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    run = readings.Run(cell=cell, win=win, setup_s=setup_s, peak=peak,
                       trace=trace)
    metrics = {}
    for m in cell.metrics("per_layer" if traced else "end_to_end"):
        v = harness.metric_reader(m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    breakdown = None
    if trace is not None:
        b, w, merged = readings.busy(run)
        lo, hi = readings.traced_bounds(run)
        device.update({"busy_s": b * 1e-9, "window_s": w * 1e-9})
        inside = [o for o in trace["device_ops"] if lo <= o[1] < hi]
        breakdown = {"device_ops": trace_reduce.top_ops(inside),
                     "idle_gaps": trace_reduce.idle_gaps(
                         merged, trace["host"], lo, hi)}

    harness.free(eng)
    res = harness.check(cell, params, harness.sample_for_check(win, seed))
    compared = {k: {"value": res[k], "limit": lim}
                for k, lim in cell.settings["check"].items()}
    compared["short_requests"] = {"value": res["short_requests"],
                                  "limit": 0}
    compared["served_tokens"] = {"value": res["served_tokens"], "limit": 1}
    correct = (all(v["value"] is not None and v["value"] <= v["limit"]
                   for k, v in compared.items() if k != "served_tokens")
               and res["served_tokens"] >= 1)
    failed = len(win.refused) + sum(
        1 for t in win.tracks.values() if not t.req.done)
    out = {"correct": bool(correct),
           "attempted": len(win.tracks) + len(win.refused),
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = compared
    print(f"bench: sampled {res['requests']} requests, widest logprob "
          f"error {res['max_logprob_err']}", file=sys.stderr)
    for k, v in compared.items():
        bound = "at least" if k == "served_tokens" else "at most"
        print(f"check {k} {v['value']} ({bound} {v['limit']})",
              file=sys.stderr)
    return out


if __name__ == "__main__":
    sys.exit(main())
