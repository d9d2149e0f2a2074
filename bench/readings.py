"""Arithmetic the metric readers share: latencies from the benchmark's
own host clock, counters from the program, and the traced ticks joined
with the device time the profiler recorded for each."""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from bench import trace_reduce as tr
from bench import work


@dataclasses.dataclass
class Run:
    """What a metric reader may read: the cell, the window, the set-up
    time, the chip's peaks and (traced runs) the reduced trace."""
    cell: object
    win: object
    setup_s: float
    peak: dict
    trace: Optional[dict] = None


def percentile(values, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(values), q)) if len(values) \
        else None


def ttfts_s(run: Run) -> List[float]:
    """Due to first token for every request due in the window. A request
    refused, or without a first token when the run stopped, counts with
    the time it had waited by then (a lower bound of what it would take)."""
    w = run.win
    out = [(t.token_t[0] if t.token_t else w.t_stop) - t.due_abs
           for t in w.tracks.values()]
    out += [w.t_stop - (w.t0 + o.due) for o in w.refused]
    return out


def itl_s(run: Run) -> List[float]:
    """Every inter-token gap that closed inside the window."""
    gaps = []
    for t in run.win.tracks.values():
        ts = [x for x in t.token_t if x <= run.win.t_end]
        gaps.extend(np.diff(ts).tolist())
    return gaps


def traced(run: Run):
    """The traced ticks: ``(tick, device_ns, span_ns)`` for every tick
    whose host span lies inside the traced slice."""
    if run.trace is None or not run.trace["device_ops"]:
        return []
    spans = tr.ticks(run.trace["host"])
    dev = tr.device_ns_per_tick(run.trace["device_ops"], spans)
    by_step = {t.step: t for t in run.win.ticks}
    return [(by_step[s], dev[s], e - b) for s, b, e in spans if s in by_step]


def traced_bounds(run: Run):
    """[lo, hi) of the traced slice in trace nanoseconds."""
    for name, s, dur, _ in run.trace["host"]:
        if name == "bench.traced":
            return s, s + dur
    spans = tr.ticks(run.trace["host"])
    return spans[0][1], spans[-1][2]


def decode_ticks(run: Run):
    """``(tick, device_ns)`` of the traced ticks with no prefill row."""
    return [(t, d) for t, d, _ in traced(run)
            if not t.has_prefill and t.rows]


def decode_step_ms(run: Run) -> Optional[float]:
    ts = decode_ticks(run)
    return sum(d for _, d in ts) / len(ts) * 1e-6 if ts else None


def decode_roofline_pct(run: Run) -> Optional[float]:
    """Least time the chip could take for the needed work of the traced
    decode ticks, over their device time, in %. The binding term is
    printed to standard error."""
    import sys
    comp = mem = dev = least = 0.0
    for t, d in decode_ticks(run):
        if d == 0:
            continue
        c, m = work.least_seconds(work.tick_work(run.cell.conf, t.rows),
                                  run.peak)
        comp, mem, least, dev = comp + c, mem + m, least + max(c, m), \
            dev + d * 1e-9
    if dev == 0:
        return None
    print(f"bench: decode ticks: compute-bound term {comp:.6f} s, "
          f"memory-bound term {mem:.6f} s, device {dev:.6f} s "
          f"({'compute' if comp >= mem else 'memory'} binds)",
          file=sys.stderr)
    return 100.0 * least / dev


def step_mfu_pct(run: Run) -> Optional[float]:
    ts = traced(run)
    span = sum(s for _, _, s in ts) * 1e-9
    if not span:
        return None
    flops = sum(work.tick_work(run.cell.conf, t.rows).flops
                for t, _, _ in ts)
    return 100.0 * flops / (span * run.peak["bf16_flops_per_s"])


def busy(run: Run):
    """(device busy ns, traced window ns, merged busy intervals)."""
    lo, hi = traced_bounds(run)
    merged = tr.merge(tr.op_intervals(run.trace["device_ops"]))
    return tr.covered(merged, lo, hi), hi - lo, merged
