"""From a profiler trace to device busy time, time per tick, top device
operations and named idle gaps.

``from_xplane`` reads the ``.xplane.pb`` the JAX profiler writes and keeps
two lists, which is all the rest needs (and what ``bench/testdata`` holds):

  * ``device_ops``: ``[name, start_ns, dur_ns]`` of every operation run on
    the first TPU device (its "XLA Ops" line);
  * ``host``: ``[name, start_ns, dur_ns, step]`` of the host spans whose
    name is the benchmark's (``bench.*``) or an engine phase, with the
    ``step_num`` a ``StepTraceAnnotation`` carries (else ``None``).

Everything else here is plain Python over those lists, so it runs without
JAX and without a chip.
"""

from __future__ import annotations

import bisect
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

TICK = "bench.tick"
ENGINE_PHASES = ("schedule", "admit", "draft", "batch_assemble",
                 "device_dispatch", "device_wait", "sample_sync",
                 "postprocess")


def from_xplane(path: str) -> dict:
    from jax._src.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tpus = sorted((p for p in pd.planes
                   if p.name.startswith("/device:TPU:")
                   and any(ln.name == "XLA Ops" for ln in p.lines)),
                  key=lambda p: p.name)
    ops: List[list] = []
    if tpus:
        for line in tpus[0].lines:
            if line.name == "XLA Ops":
                ops = [[e.name, int(e.start_ns), int(e.duration_ns)]
                       for e in line.events]
    host: List[list] = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                n = e.name
                if n.startswith("bench.") or n in ENGINE_PHASES:
                    stats = dict(e.stats)
                    step = stats.get("step_num")
                    host.append([n, int(e.start_ns), int(e.duration_ns),
                                 None if step is None else int(step)])
    ops.sort(key=lambda o: o[1])
    host.sort(key=lambda h: h[1])
    return {"device_ops": ops, "host": host}


def merge(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Union of [start, end) intervals, as sorted disjoint intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_intervals(ops: Sequence[list]) -> List[Tuple[int, int]]:
    return [(o[1], o[1] + o[2]) for o in ops]


def covered(merged: Sequence[Tuple[int, int]], lo: int, hi: int) -> int:
    """Nanoseconds of [lo, hi) that the merged intervals cover."""
    tot = 0
    i = max(bisect.bisect_right([s for s, _ in merged], lo) - 1, 0)
    for s, e in merged[i:]:
        if s >= hi:
            break
        tot += max(0, min(e, hi) - max(s, lo))
    return tot


def ticks(host: Sequence[list]) -> List[Tuple[int, int, int]]:
    """(step, start_ns, end_ns) of each benchmark tick span."""
    return [(h[3], h[1], h[1] + h[2]) for h in host
            if h[0] == TICK and h[3] is not None]


def device_ns_per_tick(ops: Sequence[list],
                       tick_spans: Sequence[Tuple[int, int, int]]
                       ) -> Dict[int, int]:
    """Device busy nanoseconds of each tick: the union of the operations
    that start inside the tick's host span."""
    starts = [o[1] for o in ops]
    out: Dict[int, int] = {}
    for step, s, e in tick_spans:
        a, b = bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)
        m = merge(op_intervals(ops[a:b]))
        out[step] = sum(y - x for x, y in m)
    return out


def top_ops(ops: Sequence[list], k: int = 10) -> List[list]:
    """The ``k`` operation names with the most device time, in seconds."""
    acc: Dict[str, int] = {}
    for name, _, dur in ops:
        acc[name] = acc.get(name, 0) + dur
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns * 1e-9] for n, ns in best]


def _innermost(host: Sequence[list], t: int) -> Optional[str]:
    best = None
    for name, s, dur, _ in host:
        if s > t:
            break
        if s <= t < s + dur and (best is None or dur < best[1]):
            best = (name, dur)
    return best[0] if best else None


def idle_gaps(merged: Sequence[Tuple[int, int]], host: Sequence[list],
              lo: int, hi: int, k: int = 10) -> List[list]:
    """The ``k`` longest device-idle gaps inside [lo, hi), each named by
    the innermost host span open at its middle ("untraced host" where
    none is), in seconds."""
    gaps = []
    prev = lo
    for s, e in merged:
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if prev < hi:
        gaps.append((prev, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_innermost(host, (a + b) // 2) or "untraced host",
             (b - a) * 1e-9] for a, b in gaps[:k]]
