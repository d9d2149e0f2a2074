"""Needed work's least time over device time, traced ticks with no
prefill row, in % (bench/work.py counts the work)."""

from bench import readings


def read(run):
    return readings.decode_roofline_pct(run)
