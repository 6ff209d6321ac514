"""The control, the plain reference one precision step below the
configuration's, put in the program's place, comes out not correct.

Sizes a test run can hold: EdgeNeXt-S at its published widths on 64 px
images, RWKV-6 at its published depth on a 256-wide model.  On a TPU
"high" is the hardware's three-pass mode; here it is written out.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from test_faults import EDGE, RWKV, go, root, sizes  # noqa: F401

RWKV_DEEP = dict(RWKV, num_layers=24)


def control_in_place(model):
    mode = model.sizes["correct"]["control"]
    model.launch = lambda xd: jnp.asarray(
        model.reference(np.asarray(xd), mode=mode))


@pytest.mark.parametrize("cell,config,small", [
    ("edgenext-s.b64", "edgenext-s", EDGE),
    ("rwkv6.prefill512", "rwkv6-1.6b", RWKV_DEEP)])
def test_control_is_not_correct(root, cell, config, small):  # noqa: F811
    res = go(root, cell, sizes(config, **small), patch=control_in_place)
    (check,) = res["checks"].values()
    assert check["value"] > check["limit"]
    assert res["correct"] is False
