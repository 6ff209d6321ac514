"""IBM Granite 4.0-H Micro (3B, dense; the Hugging Face config named in
``granite-4.0-h-micro.json``) served through the serving store.

Sizes: ``granite-4.0-h-micro.json``, the published config whole: 40
layers, d_model 2048, attention at layers 5, 15, 25 and 35 (32 query
heads of 64 over 8 KV heads, no positional encoding), Mamba-2 in the
other 36 (64 SSD heads of 64, d_state 128, one B/C group, conv width 4,
chunk 256), SwiGLU MLP of 8192 in every layer, vocabulary 100352 tied,
and the muP multipliers; checked against
``repro.configs.granite_4_0_h_micro``.  Weights are bfloat16 (6.38 GB),
as the released checkpoint, and the forward computes in bfloat16.
Deployment: one TPU v5e chip prefilling 8k-token documents.

The timed path of a request: ``ServeStore(<checkout>/.search-cache)
.request("granite-h-micro", 1)``, the token ids put on the device, one
jitted call of ``repro.models.mamba_hybrid.forward`` and ``logits_fn``
on the last position, and the logits over the whole vocabulary copied
back.  (The schedule is looked up, not executed: no executor exists
yet.)

Cell: ``granite-h-micro.prefill8k``: at 8192 tokens the matmuls are
compute-bound and the mixers' memory-bound work shows at its share: the
chunked SSD (32 chunks a layer), the causal conv, the gated norm, and
four layers of full causal attention.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import numpy as np

import registry
import weights as W
from traffic import rng

WORKLOAD = "granite-h-micro"      # the serving registry's name


def program_config(sizes: dict):
    from repro.configs.base import ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in sizes.items() if k in fields}
    return ModelConfig(block_pattern=tuple(sizes["layer_types"]), **kw)


def check_sizes(sizes: dict) -> None:
    """The program's configuration is the one this file states, and the
    catalog's keys agree with the program's."""
    from repro.configs.granite_4_0_h_micro import CONFIG
    same = {"num_layers": "num_hidden_layers", "d_model": "hidden_size",
            "num_heads": "num_attention_heads",
            "num_kv_heads": "num_key_value_heads",
            "d_ff": "intermediate_size", "conv1d_width": "mamba_d_conv",
            "tie_embeddings": "tie_word_embeddings"}
    catalog = all(sizes[a] == sizes[b] for a, b in same.items()) and (
        sizes["mamba_n_heads"] * sizes["mamba_d_head"]
        == sizes["mamba_expand"] * sizes["d_model"])
    if program_config(sizes) != CONFIG or not catalog:
        raise SystemExit(f"bench: {sizes['name']}.json differs from "
                         f"the program's configuration {CONFIG}")


def weight_spec(s: dict) -> dict:
    """The weight tree, in the program's layout (stacked per kind)."""
    d, f, v = s["d_model"], s["d_ff"], s["vocab_size"]
    H, P, N = s["mamba_n_heads"], s["mamba_d_head"], s["mamba_d_state"]
    di, cw = H * P, s["conv1d_width"]
    h, hk, hd = s["num_heads"], s["num_kv_heads"], s["head_dim"]
    nm = s["layer_types"].count("mamba")
    na = s["layer_types"].count("attention")
    nl = s["num_layers"]

    def ln(n, width=d):
        return {"scale": W.normal((n, width), 0.1, 1.0)}

    def mat(n, rows, cols):
        return W.fan_in((n, rows, cols), rows)

    mamba = {"ln": ln(nm),
             "w_zx": mat(nm, d, 2 * di), "w_bc": mat(nm, d, 2 * N),
             "w_dt": mat(nm, d, H),
             "conv_w": W.normal((nm, cw, di + 2 * N), 1.0 / math.sqrt(cw)),
             "conv_b": W.normal((nm, di + 2 * N), 0.1),
             "dt_bias": W.uniform((nm, H), -6.9, -2.25),
             "A_log": W.uniform((nm, H), 0.0, math.log(16.0)),
             "D": W.uniform((nm, H), 0.5, 1.5),
             "gnorm": ln(nm, di),
             "w_out": mat(nm, di, d)}
    attn = {"ln": ln(na), "wq": mat(na, d, h * hd),
            "wk": mat(na, d, hk * hd), "wv": mat(na, d, hk * hd),
            "wo": mat(na, h * hd, d)}
    mlp = {"ln": ln(nl), "w_in": mat(nl, d, 2 * f), "w_out": mat(nl, f, d)}
    return {"embed": {"embedding": W.normal((v, d), 0.1)},
            "mamba": mamba, "attn": attn, "mlp": mlp,
            "ln_f": {"scale": W.normal((d,), 0.1, 1.0)}}


def flops_prompt(s: dict, length: int) -> float:
    """2 x the multiply-adds of a prompt of ``length`` tokens: every
    layer's projections and MLP; the chunked SSD's C.B^T, scores.(dt x),
    chunk states and carried-state read-out; causal attention's q.k and
    p.v over the keys each query sees; not the logits (``flops_head``)."""
    d, f, T = s["d_model"], s["d_ff"], length
    H, P, N = s["mamba_n_heads"], s["mamba_d_head"], s["mamba_d_state"]
    Q, di = s["mamba_chunk_size"], H * P
    h, hk, hd = s["num_heads"], s["num_kv_heads"], s["head_dim"]
    mlp = 3 * d * f
    mamba = d * (2 * di + 2 * N + H) + di * d + Q * N + H * Q * P \
        + 2 * H * P * N
    attn = T * (d * (h + 2 * hk) * hd + h * hd * d) \
        + 2 * h * hd * T * (T + 1) // 2
    nm = s["layer_types"].count("mamba")
    na = s["layer_types"].count("attention")
    return 2.0 * (s["num_layers"] * T * mlp + nm * T * mamba + na * attn)


def flops_head(s: dict) -> float:
    """2 x the multiply-adds of the logits of one position."""
    return 2.0 * s["d_model"] * s["vocab_size"]


def served(cfg, params, tokens):
    from repro.models import mamba_hybrid
    hidden, _ = mamba_hybrid.forward(cfg, params, {"tokens": tokens})
    return mamba_hybrid.logits_fn(cfg, params, hidden[:, -1:, :])[:, 0, :]


class Model:
    ITEM = "tokens"

    def __init__(self, sizes: dict, seed: int, root):
        from repro.models import mamba_hybrid
        from repro.models.params import ParamDef
        from repro.serve.store import ServeStore
        self.sizes, self.root = sizes, root
        cfg = program_config(sizes)
        spec = weight_spec(sizes)
        want = jax.tree.map(lambda p: p.shape, mamba_hybrid.param_defs(cfg),
                            is_leaf=lambda x: isinstance(x, ParamDef))
        if W.shapes(spec) != want:
            raise SystemExit("bench: the weight tree differs from the "
                             "program's parameter layout")
        self.weights = W.make(spec, seed, sizes["param_dtype"])
        self.device = jax.devices()[0]
        self.store = ServeStore(root / ".search-cache")
        self.fwd = jax.jit(functools.partial(served, cfg))

    def inputs(self, pool: int, batch: int, length: int, seed: int
               ) -> np.ndarray:
        """The pool: ``pool`` requests' worth of int32 token ids."""
        shape = (pool, batch, length)
        return rng(seed, 5).integers(0, self.sizes["vocab_size"], shape,
                                     dtype=np.int32)

    def lookup(self, batch: int):
        return self.store.request(WORKLOAD, batch)

    def put(self, x):
        return jax.device_put(x, self.device)

    def launch(self, xd):
        return self.fwd(self.weights, xd)

    def fetch(self, yd) -> np.ndarray:
        return np.asarray(yd)

    def items(self, req) -> int:
        return req.batch * req.length

    def flops(self, req) -> float:
        s = self.sizes
        return req.batch * (flops_prompt(s, req.length) + flops_head(s))

    def free(self) -> None:
        """Drop the program's state; the weights stay for the reference."""
        del self.fwd, self.store

    def reference(self, tokens: np.ndarray, mode: str = "exact"
                  ) -> np.ndarray:
        """Reference next-token logits of ``tokens`` [N, T]."""
        ref = registry.reference_module(self.sizes["name"], self.root)
        return np.asarray(ref.logits(self.sizes, self.weights, tokens,
                                     mode))
