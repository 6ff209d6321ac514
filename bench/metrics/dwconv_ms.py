"""dwconv_ms.<cell>: device ms per request in the depthwise-convolution
layers (scheduler op ``dwconv``: each block's ``dw``, each SDTA's
``dw0``...), from the program's layer scopes (``layer_profile.py``);
missing where no op of such a layer ran."""
import layer_profile


def read(run):
    return layer_profile.class_ms(run, lambda name, op, role: op == "dwconv")
