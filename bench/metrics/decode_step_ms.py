"""Device time per traced tick with no prefill row (profiler trace)."""

from bench import readings


def read(run):
    return readings.decode_step_ms(run)
