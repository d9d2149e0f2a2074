"""Compile events: one process-wide ``jax.monitoring`` listener.

Every compile of a jitted function, and every load of one from the
persistent compile cache, fires JAX's backend-compile event with the
function's name (``fun_name``, e.g. ``jit(serve_step_w1_prefill_p1x32)``).
The listener counts it in ``total`` — the process-wide count the
tracer diffs across each tick (the tick's ``compiles`` stat) — and,
while an engine tick runs, in that engine's ``engine_compiles_total``
counter, labelled by ``fun_name``. Compiles outside a tick (engine
construction) reach ``total`` only.

The engine is single-threaded host code: ``attach`` routes events to
the ticking engine's counter and hands back the previous sink, which
the engine re-attaches when its tick ends.
"""

from __future__ import annotations

EVENT = "/jax/core/compile/backend_compile_duration"

total = 0
_sink = None
_installed = False


def _on_duration(event: str, secs: float, fun_name: str = "", **_) -> None:
    global total
    if event != EVENT:
        return
    total += 1
    if _sink is not None:
        _sink.inc(fun_name)


def install() -> None:
    """Register the listener (once per process)."""
    global _installed
    if not _installed:
        import jax
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True


def attach(counter):
    """Send compile events to ``counter`` (an obs.registry.LabeledCounter,
    or None for nowhere); returns the sink it replaces."""
    global _sink
    prev, _sink = _sink, counter
    return prev


__all__ = ["EVENT", "attach", "install"]
