"""device_ms.<cell>: device busy time per request, in ms: the union of
the trace's op intervals over the window, divided by the requests whose
answers reached the host inside it."""


def read(run):
    if run.trace is None or not run.done:
        return None
    return 1e3 * run.trace["busy_s"] / len(run.done)
