"""Preemptions in the window (``sched_preemptions_total``) per request
due in it."""


def read(run):
    n = len(run.win.tracks) + len(run.win.refused)
    return run.win.counters_end["preemptions"] / n if n else None
