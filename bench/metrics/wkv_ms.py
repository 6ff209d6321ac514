"""wkv_ms.<cell>: device ms per request (a prompt) in the WKV scan,
the layer scan's ``tmix.wkv`` scope, apart from the projections that
feed it (``layer_profile.py``); missing where no op of it ran."""
import layer_profile


def read(run):
    return layer_profile.class_ms(run, lambda name, op, role:
                                  name == "tmix.wkv")
