"""Share of the time inside ``Engine.step`` (traced ticks) in which no
operation ran on the device, in %; waiting for arrivals is left out."""

from bench import trace_reduce


def read(run):
    ts = trace_reduce.ticks(run.trace["host"]) if run.trace else []
    if not ts:
        return None
    merged = trace_reduce.merge(
        trace_reduce.op_intervals(run.trace["device_ops"]))
    span = sum(e - s for _, s, e in ts)
    dev = sum(trace_reduce.covered(merged, s, e) for _, s, e in ts)
    return 100.0 * (1.0 - dev / span)
