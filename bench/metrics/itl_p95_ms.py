"""95th percentile of every inter-token gap that closed inside the
window; gaps stretched by a prefill chunk in the same tick sit here."""

from bench import readings


def read(run):
    v = readings.percentile(readings.itl_s(run), 95)
    return None if v is None else v * 1e3
