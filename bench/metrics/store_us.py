"""store_us.<cell>: median duration, in us, of the program's own
``serve.request`` span (``ServeStore.request``) on the profiler's host
plane, in a short profiled window (``layer_profile.py``); missing where
the program writes no such span."""
import layer_profile


def read(run):
    return layer_profile.span_median_us(run)
