"""Needed FLOPs of every traced tick over their summed ``Engine.step``
wall time (the host span in the trace) times the chip's bf16 peak, in %."""

from bench import readings


def read(run):
    return readings.step_mfu_pct(run)
