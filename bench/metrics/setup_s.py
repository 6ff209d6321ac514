"""setup_s: seconds from process start until the first measured request
could be sent (imports, device start, weights, input pool, lookups,
compilation or the compile cache, warm-up)."""


def read(run):
    return run.setup_s
