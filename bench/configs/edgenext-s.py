"""EdgeNeXt-S (arXiv:2206.10589) served through the serving store.

Sizes: ``edgenext-s.json``, the published widths at 256 px and 1000
classes, checked against ``repro.configs.edgenext_s.CONFIG``.  It runs in
float32 under ``"highest"`` matmul precision, as the configuration states.
Deployment: one TPU v5e chip classifying 256x256 uint8 RGB frames.

The timed path of a request: ``ServeStore(<checkout>/.search-cache)
.request("edgenext-s", batch)``, the frames' bytes put on the device as
they are, ``[batch, -1, 128]`` uint8 (lane-dense, so the transfer moves
the frames' own bytes and no padding), one jitted call that views them
as ``[batch, img, img, 3]`` and runs ``repro.models.edgenext.forward``
with the program's default arguments, and the logits copied back.  (The
served schedule is looked up but not executed: no executor exists yet.
When one does, it belongs behind ``launch``, and these cells measure it
unchanged.)

Cells: ``edgenext-s.b64`` (batch classification, 64 frames a request: the
4x inverted-bottleneck intermediates are largest) and
``edgenext-s.b1-poisson`` (single frames from independent clients: host
dispatch, transfers and small device ops dominate).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import numpy as np

import registry
import weights as W
from traffic import rng

WORKLOAD = "edgenext-s"           # the serving registry's name
REF_BLOCK = 64                    # images per reference call
LANES = 128                       # a frame's bytes travel as [-1, LANES]


def program_config(sizes: dict):
    from repro.configs.edgenext_s import EdgeNeXtConfig
    fields = {f.name for f in dataclasses.fields(EdgeNeXtConfig)}
    return EdgeNeXtConfig(**{k: tuple(v) if isinstance(v, list) else v
                             for k, v in sizes.items() if k in fields})


def check_sizes(sizes: dict) -> None:
    """The program's configuration is the one this file states."""
    from repro.configs.edgenext_s import CONFIG
    if program_config(sizes) != CONFIG:
        raise SystemExit(f"bench: {sizes['name']}.json differs from "
                         f"the program's configuration {CONFIG}")


def split_widths(c: int, scales: int):
    width = -(-c // scales)
    return [width] * (scales - 1) + [c - width * (scales - 1)]


def weight_spec(s: dict) -> dict:
    """The weight tree, in the program's layout.  Layer scales and XCA
    temperatures are drawn at sizes where every block moves the logits
    (the program's init has layer scales of 1e-6)."""
    e = s["expan_ratio"]

    def ln(c):
        return {"scale": W.normal((c,), 0.1, 1.0),
                "bias": W.normal((c,), 0.1)}

    def mlp(c):
        return {"pw1_w": W.fan_in((c, e * c)),
                "pw1_b": W.normal((e * c,), 0.1),
                "pw2_w": W.fan_in((e * c, c)),
                "pw2_b": W.normal((c,), 0.1)}

    def conv_block(c, k):
        return {"dw_w": W.fan_in((k, k, c), k * k),
                "dw_b": W.normal((c,), 0.1), "ln": ln(c), **mlp(c),
                "gamma": W.uniform((c,), 0.5, 1.0)}

    def sdta(c, scales):
        h = s["heads"]
        return {"dw": [{"w": W.fan_in((3, 3, w), 9),
                        "b": W.normal((w,), 0.1)}
                       for w in split_widths(c, scales)[1:]],
                "ln_x": ln(c), "qkv_w": W.fan_in((c, 3 * c)),
                "qkv_b": W.normal((3 * c,), 0.1),
                "temp": W.uniform((h, 1, 1), 1.0, 4.0),
                "proj_w": W.fan_in((c, c)), "proj_b": W.normal((c,), 0.1),
                "gamma_x": W.uniform((c,), 0.5, 1.0), "ln_m": ln(c),
                **mlp(c), "gamma_m": W.uniform((c,), 0.5, 1.0)}

    stages = []
    for si, c in enumerate(s["dims"]):
        n_sdta = s["sdta_blocks"][si]
        st = {"conv_blocks": [conv_block(c, s["kernel_sizes"][si])
                              for _ in range(s["depths"][si] - n_sdta)],
              "sdta_blocks": [sdta(c, s["sdta_scales"][si])
                              for _ in range(n_sdta)]}
        if si == 0:
            # uint8 pixels (std ~74): scale the stem so its output is O(1)
            st["down_w"] = W.fan_in((4, 4, s["in_channels"], c),
                                    gain=1 / 128)
        else:
            cp = s["dims"][si - 1]
            st["down_ln"] = ln(cp)
            st["down_w"] = W.fan_in((2, 2, cp, c))
        st["down_b"] = W.normal((c,), 0.1)
        stages.append(st)
    c = s["dims"][-1]
    return {"stages": stages, "head_ln": ln(c),
            "head_w": W.fan_in((c, s["num_classes"])),
            "head_b": W.normal((s["num_classes"],), 0.1)}


def flops_per_image(s: dict) -> float:
    """2 x the multiply-adds of the convolutions and matmuls (XCA's two
    products included; norms, activations and softmax left out)."""
    side = s["img_size"] // 4
    e = s["expan_ratio"]
    macs = side * side * 16 * s["in_channels"] * s["dims"][0]
    for si, c in enumerate(s["dims"]):
        n = side * side                                  # pixels
        if si > 0:
            macs += n * 4 * s["dims"][si - 1] * c
        n_sdta = s["sdta_blocks"][si]
        k = s["kernel_sizes"][si]
        macs += (s["depths"][si] - n_sdta) * n * (k * k * c + 2 * e * c * c)
        widths = split_widths(c, s["sdta_scales"][si])[1:]
        macs += n_sdta * n * (9 * sum(widths) + 3 * c * c
                              + 2 * c * c // s["heads"] + c * c
                              + 2 * e * c * c)
        side //= 2
    macs += s["dims"][-1] * s["num_classes"]
    return 2.0 * macs


def served(cfg, params, frames):
    """Logits of frames sent as their bytes, ``[batch, -1, LANES]``."""
    from repro.models import edgenext
    images = frames.reshape(frames.shape[0], cfg.img_size, cfg.img_size,
                            cfg.in_channels)
    return edgenext.forward(cfg, params, images)


class Model:
    ITEM = "images"

    def __init__(self, sizes: dict, seed: int, root):
        from repro.models import edgenext
        from repro.models.params import ParamDef
        from repro.serve.store import ServeStore
        jax.config.update("jax_default_matmul_precision",
                          sizes["matmul_precision"])
        self.sizes, self.root = sizes, root
        cfg = program_config(sizes)
        spec = weight_spec(sizes)
        want = jax.tree.map(lambda d: d.shape, edgenext.param_defs(cfg),
                            is_leaf=lambda x: isinstance(x, ParamDef))
        if W.shapes(spec) != want:
            raise SystemExit("bench: the weight tree differs from the "
                             "program's parameter layout")
        self.weights = W.make(spec, seed)
        self.device = jax.devices()[0]
        self.store = ServeStore(root / ".search-cache")
        self.fwd = jax.jit(functools.partial(served, cfg))

    def inputs(self, pool: int, batch: int, length: int, seed: int
               ) -> np.ndarray:
        """The pool: ``pool`` requests' worth of uint8 images."""
        s = self.sizes
        shape = (pool, batch, s["img_size"], s["img_size"], s["in_channels"])
        return rng(seed, 5).integers(0, 256, shape, dtype=np.uint8)

    def lookup(self, batch: int):
        return self.store.request(WORKLOAD, batch)

    def put(self, x):
        return jax.device_put(x.reshape(len(x), -1, LANES), self.device)

    def launch(self, xd):
        return self.fwd(self.weights, xd)

    def fetch(self, yd) -> np.ndarray:
        return np.asarray(yd)

    def items(self, req) -> int:
        return req.batch

    def flops(self, req) -> float:
        return req.batch * flops_per_image(self.sizes)

    def free(self) -> None:
        """Drop the program's state; the weights stay for the reference."""
        del self.fwd, self.store

    def reference(self, images: np.ndarray, mode: str = "exact"
                  ) -> np.ndarray:
        """Reference logits of ``images`` [N, img, img, 3] (or their bytes,
        as sent), in blocks."""
        ref = registry.reference_module(self.sizes["name"], self.root)
        fn = jax.jit(functools.partial(ref.forward, self.sizes, mode=mode))
        s, n = self.sizes, len(images)
        images = np.asarray(images).reshape(n, s["img_size"], s["img_size"],
                                            s["in_channels"])
        blocks = []
        for i in range(0, n, REF_BLOCK):
            x = images[i:i + REF_BLOCK]
            pad = REF_BLOCK - len(x)
            if pad:
                x = np.concatenate([x, np.zeros((pad,) + x.shape[1:],
                                                x.dtype)])
            blocks.append(np.asarray(fn(self.weights, x))[:REF_BLOCK - pad])
        return np.concatenate(blocks)[:n]

