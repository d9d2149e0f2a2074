"""Needed work's least time over device time, every traced tick (decode
rows and prefill rows alike), in %, for the latent-attention MoE family
(bench/work_mla_moe.py counts the work; the binding term is printed to
standard error)."""

import sys

from bench import readings, work, work_mla_moe


def read(run):
    comp = mem = least = dev = 0.0
    for t, d, _ in readings.traced(run):
        if d == 0 or not t.rows:
            continue
        c, m = work.least_seconds(work_mla_moe.tick_work(run.cell.conf,
                                                         t.rows), run.peak)
        comp, mem, least, dev = comp + c, mem + m, least + max(c, m), \
            dev + d * 1e-9
    if dev == 0:
        return None
    print(f"bench: moe ticks: compute-bound term {comp:.6f} s, "
          f"memory-bound term {mem:.6f} s, device {dev:.6f} s "
          f"({'compute' if comp >= mem else 'memory'} binds)",
          file=sys.stderr)
    return 100.0 * least / dev
