"""pwconv_ms.<cell>: device ms per request in the projection layers
(scheduler op ``pwconv``: ``tmix.rkvg``, ``tmix.out``, ``cmix.key``,
``cmix.value``), their weights' casts to bf16 included, which XLA hoists
out of the layer scan; from the program's layer scopes
(``layer_profile.py``); missing where no op of such a layer ran."""
import layer_profile


def read(run):
    return layer_profile.class_ms(run, lambda name, op, role: op == "pwconv")
