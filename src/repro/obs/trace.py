"""Tracer: per-tick phase spans + per-request lifecycle timelines.

The engine is instrumented unconditionally — every tick runs under
``with tracer.tick():`` and every phase under ``with tracer.span(...):``
— but the DEFAULT tracer is ``NULL_TRACER``, whose ``span``/``event``
return a shared no-op singleton: no allocation, no clock reads, no
device fences. ``ObsConfig(enabled=True)`` swaps in the recording
``Tracer`` (obs.make_tracer), which is where all cost lives.

Attribution model (mirrors the paper's near-core vs near-memory
accounting at the software level): within one tick,

  device_ms = time inside the ``device_wait`` span — the runner fences
              with ``jax.block_until_ready`` after dispatch, so this is
              actual device execution not hidden by async dispatch;
  host_ms   = tick wall time - device_ms — scheduling, drafting, batch
              assembly, sampling sync, host-side commit.

Each tick also records per-phase durations (``phases`` dict), per-row-
kind row/token counts, and the padding-waste fraction of the device
batch (1 - valid token slots / B*S — the mixed-tick padding artifact
the disaggregated-prefill ROADMAP item wants to kill).

Spans are recorded AT EXIT with (t0, t1, depth, tick); request events
record (rid, name, t, tick, attrs). The clock is ``time.monotonic``,
the one ``MetricsCollector`` stamps requests with; span and event
times are relative to the tracer epoch, and each tick's stats entry
also keeps its absolute start (``t0_mono_s``).

With ``ObsConfig.jax_annotations`` every span opens a
``jax.profiler.TraceAnnotation`` carrying its attributes, and every
tick runs inside an ``engine.step`` annotation: ``step_num`` (the
tick), ``mono_ns`` (``time.monotonic_ns()`` at entry — the profiler's
start_ns minus it maps any engine stamp onto the profiler timeline),
and on exit the tick's attributes, ``compiles`` among them (compiles
during the tick, obs.compiles). ``slowest_tick`` is the stats entry
of the window's longest tick. Storage is bounded by
ObsConfig.max_events: past it, new entries are dropped and counted
(``dropped``) rather than silently wrapping — a truncated trace must be
detectable (tools/check_trace.py warns on it).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

from repro.configs.base import ObsConfig
from repro.obs import compiles


@dataclasses.dataclass
class Span:
    """One closed phase span. Times are seconds on the tracer's clock
    (``time.monotonic``), relative to the tracer epoch."""
    name: str
    t0: float
    t1: float
    depth: int
    tick: int
    attrs: Optional[dict] = None

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Event:
    """One request-lifecycle instant (arrival, first_token, ...)."""
    rid: int
    name: str
    t: float
    tick: int
    attrs: Optional[dict] = None


class _NullSpan:
    """The shared no-op context manager the disabled path returns.
    One module-level instance, ``__slots__ = ()``: entering a span on a
    disabled tracer allocates nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class NullTracer:
    """Disabled-mode tracer: every hook is a no-op returning shared
    singletons. The engine/runner never branch on ``if tracer`` — they
    always call through, and this class is what makes that free."""

    __slots__ = ()
    enabled = False
    spans: tuple = ()
    events: tuple = ()
    tick_stats: tuple = ()
    slowest_tick = None
    dropped = 0

    def span(self, name, **attrs):
        return NULL_SPAN

    def tick(self):
        return NULL_SPAN

    def tick_attrs(self, **attrs):
        pass

    def event(self, rid, name, **attrs):
        pass

    def reset(self):
        pass


NULL_TRACER = NullTracer()


class _SpanCM:
    """Context manager recording one span on exit (enabled mode); with
    profiler annotations on, it holds its own, attributes attached."""

    __slots__ = ("tr", "name", "t0", "attrs", "annot")

    def __init__(self, tr: "Tracer", name: str, attrs: Optional[dict]):
        self.tr = tr
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        tr = self.tr
        tr._depth += 1
        if tr._annot is not None:
            self.annot = tr._annot(self.name, **(self.attrs or {}))
            self.annot.__enter__()
        self.t0 = tr._now() - tr.epoch
        return self

    def __exit__(self, *exc):
        tr = self.tr
        t1 = tr._now() - tr.epoch
        tr._depth -= 1
        if tr._annot is not None:
            self.annot.__exit__(*exc)
        tr._phase_s[self.name] = tr._phase_s.get(self.name, 0.0) \
            + (t1 - self.t0)
        tr._record(Span(self.name, self.t0, t1, tr._depth, tr.n_ticks,
                        self.attrs))
        return False


class _TickCM:
    """Context manager for one engine tick: opens the ``tick`` span (and
    the ``engine.step`` profiler annotation), resets per-phase
    accumulators, and folds a tick_stats entry (host vs device
    attribution + the engine's tick_attrs + compiles) on exit."""

    __slots__ = ("tr", "t0", "c0", "annot")

    def __init__(self, tr: "Tracer"):
        self.tr = tr

    def __enter__(self):
        tr = self.tr
        tr._phase_s = {}
        tr._tick_attrs = {}
        tr._depth += 1
        self.c0 = compiles.total
        if tr._annot is not None:
            self.annot = tr._annot("engine.step", step_num=tr.n_ticks,
                                   mono_ns=time.monotonic_ns())
            self.annot.__enter__()
        self.t0 = tr._now()
        return self

    def __exit__(self, *exc):
        tr = self.tr
        t1 = tr._now()
        tr._depth -= 1
        tick = tr.n_ticks
        attrs = tr._tick_attrs
        attrs["compiles"] = compiles.total - self.c0
        if tr._annot is not None:
            self.annot.set_metadata(**attrs)
            self.annot.__exit__(*exc)
        t0 = self.t0 - tr.epoch
        tr._record(Span("tick", t0, t1 - tr.epoch, tr._depth, tick, None))
        dur = t1 - self.t0
        device = tr._phase_s.get("device_wait", 0.0)
        entry = {
            "tick": tick,
            "t0_s": t0,
            "t0_mono_s": self.t0,
            "dur_ms": dur * 1e3,
            "device_ms": device * 1e3,
            "host_ms": max(dur - device, 0.0) * 1e3,
            "phases_ms": {k: v * 1e3 for k, v in tr._phase_s.items()},
        }
        entry.update(attrs)
        tr.tick_stats.append(entry)
        if tr.slowest_tick is None \
                or entry["dur_ms"] > tr.slowest_tick["dur_ms"]:
            tr.slowest_tick = entry
        tr.n_ticks = tick + 1
        return False


class Tracer:
    """The recording tracer (ObsConfig(enabled=True)).

    One per engine; not thread-safe (the engine tick loop is single-
    threaded host code). ``spans`` and ``events`` hold the raw record;
    ``tick_stats`` is the per-tick aggregate (host_ms/device_ms/pad
    waste/per-kind row counts/rows/compiles), ``slowest_tick``
    the entry of the window's longest tick; exporters
    (repro.obs.export) turn the raw record into Perfetto/JSONL files.
    """

    _now = staticmethod(time.monotonic)

    def __init__(self, cfg: Optional[ObsConfig] = None):
        self.cfg = cfg if cfg is not None else ObsConfig(enabled=True)
        self.enabled = True
        self._annot = None
        if self.cfg.jax_annotations:
            import jax.profiler
            self._annot = jax.profiler.TraceAnnotation
        self.reset()

    # --- lifecycle --------------------------------------------------------
    def reset(self) -> None:
        """Fresh measurement window (Engine.reset_metrics, e.g. after
        warm-up). The epoch restarts so exported timestamps are relative
        to the window."""
        self.epoch = self._now()
        self.spans: List[Span] = []
        self.events: List[Event] = []
        self.tick_stats: List[dict] = []
        self.slowest_tick: Optional[dict] = None
        self.n_ticks = 0
        self.dropped = 0
        self._depth = 0
        self._phase_s: Dict[str, float] = {}
        self._tick_attrs: dict = {}

    def _record(self, item) -> None:
        store = self.spans if type(item) is Span else self.events
        if len(self.spans) + len(self.events) >= self.cfg.max_events:
            self.dropped += 1
            return
        store.append(item)

    # --- spans ------------------------------------------------------------
    def span(self, name: str, **attrs) -> _SpanCM:
        """Open a phase span; nests (depth tracked) and records on exit."""
        if not self.cfg.tick_spans:
            return NULL_SPAN
        return _SpanCM(self, name, attrs or None)

    def tick(self) -> _TickCM:
        """Open one engine-tick span; on exit a tick_stats entry with
        host/device attribution is folded."""
        if not self.cfg.tick_spans:
            return NULL_SPAN
        return _TickCM(self)

    def tick_attrs(self, **attrs) -> None:
        """Attach per-tick engine facts (row-kind counts, batch width,
        pad_waste_frac, ...) to the current tick's stats entry."""
        self._tick_attrs.update(attrs)

    # --- request timeline -------------------------------------------------
    def event(self, rid: int, name: str, **attrs) -> None:
        """One request-lifecycle instant on request ``rid``'s timeline."""
        if not self.cfg.timeline:
            return
        self._record(Event(rid, name, self._now() - self.epoch,
                           self.n_ticks, attrs or None))

    def timeline(self, rid: int) -> List[Event]:
        """Request ``rid``'s lifecycle events in time order."""
        return sorted((e for e in self.events if e.rid == rid),
                      key=lambda e: e.t)

    # --- aggregates -------------------------------------------------------
    def tick_summary(self) -> dict:
        """Means over tick_stats — the benchmark columns. Ticks that ran
        no device step (empty scheduler polls) still count: their device
        time is genuinely zero host-side overhead.

        Per-tick costs are normalized by DEVICE ticks, not engine ticks:
        an async K-tick device burst (docs/async.md) records one
        tick_stats entry with ``device_ticks=K`` (the engine set it via
        tick_attrs), so host_ms_per_tick measures host overhead per
        emitted decode step either way. Synchronous ticks default to
        device_ticks=1, which reduces to the old per-entry mean."""
        ts = self.tick_stats
        if not ts:
            return {"n_ticks": 0, "host_ms_per_tick": None,
                    "device_ms_per_tick": None, "pad_waste_frac": None}
        n = len(ts)
        ndev = sum(int(t.get("device_ticks", 1)) or 1 for t in ts)
        padded = [t["pad_waste_frac"] for t in ts
                  if t.get("pad_waste_frac") is not None]
        return {
            "n_ticks": n,
            "n_device_ticks": ndev,
            "host_ms_per_tick": sum(t["host_ms"] for t in ts) / ndev,
            "device_ms_per_tick": sum(t["device_ms"] for t in ts) / ndev,
            "pad_waste_frac": (sum(padded) / len(padded)) if padded
            else None,
        }

    def phase_ms_per_tick(self) -> Dict[str, float]:
        """Mean per-tick duration of each phase span (draft, schedule,
        device_wait, ...) — where a regression's time actually went."""
        if not self.tick_stats:
            return {}
        acc: Dict[str, float] = {}
        for t in self.tick_stats:
            for k, v in t["phases_ms"].items():
                acc[k] = acc.get(k, 0.0) + v
        return {k: v / len(self.tick_stats) for k, v in acc.items()}


def make_tracer(cfg: Optional[ObsConfig]):
    """ObsConfig -> NULL_TRACER (disabled; the shared no-op singleton)
    or a fresh recording Tracer. ``profile`` implies tracing: attainment
    joins static cost with the fenced device_wait spans, so a profiling
    run without the spans would have nothing to measure."""
    if cfg is None or not (cfg.enabled or cfg.profile):
        return NULL_TRACER
    return Tracer(cfg)


__all__ = ["Event", "NULL_SPAN", "NULL_TRACER", "NullTracer", "Span",
           "Tracer", "make_tracer"]
