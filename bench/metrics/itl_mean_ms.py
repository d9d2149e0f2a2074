"""Mean of every inter-token gap that closed inside the window: the
decode ticks and the ticks a prefill chunk stretched, each gap counted
once."""

from bench import readings


def read(run):
    gaps = readings.itl_s(run)
    return sum(gaps) / len(gaps) * 1e3 if gaps else None
