"""norm_ms.<cell>: device ms per request in the LayerNorm and softmax
layers (scheduler ops ``norm`` and ``softmax``), which temporal
reordering serves, from the program's layer scopes
(``layer_profile.py``); missing where no op of such a layer ran."""
import layer_profile


def read(run):
    return layer_profile.class_ms(
        run, lambda name, op, role: op in ("norm", "softmax"))
