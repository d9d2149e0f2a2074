"""Find a cell's knee once, on the chip: serve the cell's mix at several
fixed rates, one window each on each seed, in one process, and print for
each how the scheduler's waiting queue moved through the window.

    python3 bench/sweep.py --workload <name> --rates 0.4,0.5,0.6 \
        --seconds 51 --seeds 7,8

The knee is the highest rate at which, on every seed, the waiting queue
does not grow through the window: its mean depth over the ticks of the
last quarter is no more than over the ticks of the second quarter plus
one request. A cell's ``rate_per_s`` is then fixed at about 0.8 of it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import arrivals, harness, readings  # noqa: E402


def queue_by_quarter(win) -> list:
    """Mean depth of the waiting queue over the ticks of each quarter of
    the window."""
    out = []
    for q in range(4):
        lo = win.t0 + win.seconds * q / 4
        hi = win.t0 + win.seconds * (q + 1) / 4
        d = [t.waiting for t in win.ticks if lo <= t.t1 < hi]
        out.append(sum(d) / len(d) if d else 0.0)
    return out


def grows(queue: list) -> bool:
    return queue[3] > queue[1] + 1.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seeds", default="7,8")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.ensure_src()
    import jax
    harness.device_check(cell.chips)
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = harness.CompileCounter()
    seeds = [int(s) for s in args.seeds.split(",")]
    params = harness.make_weights(cell, seeds[0])
    eng = harness.make_engine(cell, params, traced=False)
    harness.warm_up(eng, seeds[0])
    for rate in (float(r) for r in args.rates.split(",")):
        for seed in seeds:
            offered = arrivals.generate(cell.mix, rate, args.seconds, seed,
                                        cell.conf["vocab_size"])
            win = harness.serve_window(eng, offered, args.seconds, counter)
            t_drain = time.monotonic()
            while not eng.sched.idle and time.monotonic() - t_drain < 180:
                eng.step()
            eng.reset_metrics()
            run = readings.Run(cell=cell, win=win, setup_s=0.0, peak={})
            itl = readings.itl_s(run)
            ttft = readings.ttfts_s(run)
            queue = queue_by_quarter(win)
            print(json.dumps({
                "rate_per_s": rate, "seed": seed, "due": len(offered),
                "refused": len(win.refused),
                "unfinished_after_drain": sum(
                    1 for tr in win.tracks.values() if not tr.req.done),
                "queue_by_quarter": queue, "queue_grows": grows(queue),
                "preemptions": win.counters_end["preemptions"],
                "ttft_p50_ms": readings.percentile(ttft, 50) * 1e3,
                "ttft_p90_ms": readings.percentile(ttft, 90) * 1e3,
                "itl_mean_ms": sum(itl) / len(itl) * 1e3 if itl else None,
                "itl_p95_ms": (readings.percentile(itl, 95) or 0) * 1e3,
                "tok_s": (win.counters_end["prefill_tokens"]
                          + win.counters_end["generated_tokens"])
                / args.seconds,
                "compiles_in_window": win.compiles_in_window,
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
