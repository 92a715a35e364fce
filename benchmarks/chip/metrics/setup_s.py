"""Process start to the first timed request: JAX start-up, the compile
(from the cache after a cell's first run), key rows, ring prefill, warm-up
(host clock)."""


def read(run):
    return run.setup_s
