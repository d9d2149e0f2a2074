"""Share of the window's inter-token gaps that had a prefill in flight,
from the engine's split gap counters (serve/metrics.py), in %."""


def read(run):
    c = run.win.counters_end
    n = c["gaps_prefill_overlap"] + c["gaps_steady"]
    return 100.0 * c["gaps_prefill_overlap"] / n if n else None
