"""90th percentile, over every request due in the window, of due time to
first token (host clock, after the token reached the host)."""

from bench import readings


def read(run):
    v = readings.percentile(readings.ttfts_s(run), 90)
    return None if v is None else v * 1e3
