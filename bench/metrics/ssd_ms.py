"""ssd_ms.<cell>: device ms per request (a prompt) in the Mamba-2
layers' chunked SSD, the layer scan's ``mamba.ssd`` scope, apart from
the projections and the conv that feed it (``layer_profile.py``);
missing where no op of it ran (a program without that scope)."""
import layer_profile


def read(run):
    return layer_profile.class_ms(run, lambda name, op, role:
                                  name == "mamba.ssd")
