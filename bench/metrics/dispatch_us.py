"""dispatch_us.<cell>: median host time, in us, from the end of a
request's lookup until its inputs' transfer and its jitted call have
been enqueued (the ``h2d`` and ``dispatch`` phases)."""


def read(run):
    m = run.phase_median("h2d", "dispatch")
    return None if m is None else 1e6 * m
