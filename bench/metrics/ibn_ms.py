"""ibn_ms.<cell>: device ms per request in the inverted-bottleneck
layers, those the scheduler gives an ``ibn_role`` (each block's ``pw1``,
``act`` and ``pw2``): the layer fusion's target.  Read from the program's
layer scopes in a short profiled window (``layer_profile.py``); missing
where no op of such a layer ran."""
import layer_profile


def read(run):
    return layer_profile.class_ms(run, lambda name, op, role: role is not None)
