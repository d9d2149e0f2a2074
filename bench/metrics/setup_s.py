"""Set-up: process start to the window's first due request (loading,
weights, engine, compile-cache loads or compiles, warm-up)."""


def read(run):
    return run.setup_s
