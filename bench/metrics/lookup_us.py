"""lookup_us.<cell>: median host time, in us, of the serving store's
``request`` in the timed path (the ``lookup`` phase)."""


def read(run):
    m = run.phase_median("lookup")
    return None if m is None else 1e6 * m
