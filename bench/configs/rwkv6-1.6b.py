"""RWKV-6 "Finch" 1.6B (arXiv:2404.05892) served through the serving
store.

Sizes: ``rwkv6-1.6b.json``, the published 1.6B: 24 layers, d_model 2048,
32 WKV heads of 64, d_ff 7168, vocabulary 65536, token-shift LoRA rank 32
and decay LoRA rank 64, checked against ``repro.configs.rwkv6_1_6b`` and
``repro.models.rwkv6``.  Weights are float32 (6.4 GB) and the forward
computes in bfloat16, as the configuration states.  Deployment: one TPU
v5e chip prefilling 512-token prompts for an LM service.

The timed path of a request: ``ServeStore(<checkout>/.search-cache)
.request("rwkv6", 1)``, the token ids put on the device, one jitted call
of ``repro.models.rwkv6.forward`` and ``logits_fn`` on the last position,
and the logits over the whole vocabulary copied back.  (The schedule is
looked up, not executed: no executor exists yet.)

Cell: ``rwkv6.prefill512``: the WKV scan (XLA's chunked form today,
``rwkv_chunk`` later) and large bf16 matmuls, on the serving path and the
block and lowering rules that EdgeNeXt-S shares.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import numpy as np

import registry
import weights as W
from traffic import rng

WORKLOAD = "rwkv6"                # the serving registry's name


def program_config(sizes: dict):
    from repro.configs.base import ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    return ModelConfig(**{k: v for k, v in sizes.items() if k in fields})


def check_sizes(sizes: dict) -> None:
    """The program's configuration is the one this file states."""
    from repro.configs.rwkv6_1_6b import CONFIG
    from repro.models import rwkv6
    if program_config(sizes) != CONFIG or (
            sizes["lora_mix"], sizes["lora_decay"]) != (
            rwkv6.LORA_MIX, rwkv6.LORA_DECAY):
        raise SystemExit(f"bench: {sizes['name']}.json differs from "
                         f"the program's configuration {CONFIG}")


def padded_vocab(s: dict) -> int:
    return -(-s["vocab_size"] // 128) * 128


def weight_spec(s: dict) -> dict:
    """The weight tree, in the program's layout (layers stacked)."""
    L, d, f = s["num_layers"], s["d_model"], s["d_ff"]
    h, k = d // s["wkv_head_dim"], s["wkv_head_dim"]
    rm, rd, v = s["lora_mix"], s["lora_decay"], padded_vocab(s)

    def ln(*lead):
        return {"scale": W.normal(lead + (d,), 0.1, 1.0),
                "bias": W.normal(lead + (d,), 0.1)}

    def mat(rows, cols, gain=1.0):
        return W.fan_in((L, rows, cols), rows, gain)

    def mix():
        return W.uniform((L, d), 0.0, 1.0)

    tm = {"maa_x": mix(), "maa_w": mix(), "maa_k": mix(), "maa_v": mix(),
          "maa_r": mix(), "maa_g": mix(),
          "maa_w1": mat(d, 5 * rm),
          "maa_w2": W.fan_in((L, 5, rm, d), rm, 0.5),
          "decay": W.uniform((L, d), -6.0, -1.0),
          "td_w1": mat(d, rd), "td_w2": mat(rd, d, 0.5),
          "faaaa": W.normal((L, h, k), 0.5),
          "wr": mat(d, d), "wk": mat(d, d), "wv": mat(d, d),
          "wg": mat(d, d), "wo": mat(d, d),
          "lnx_scale": W.normal((L, d), 0.1, 1.0),
          "lnx_bias": W.normal((L, d), 0.1)}
    cm = {"maa_k": mix(), "maa_r": mix(), "wk": mat(d, f), "wv": mat(f, d),
          "wr": mat(d, d)}
    return {"embed": {"embedding": W.normal((v, d), 1.0),
                      "unembed": W.fan_in((d, v))},
            "ln0": ln(),
            "blocks": {"ln1": ln(L), "tm": tm, "ln2": ln(L), "cm": cm},
            "ln_f": ln()}


def flops_per_token(s: dict) -> float:
    """2 x the multiply-adds a prompt token needs in every layer: the
    projections, the LoRAs, the channel mix, and the WKV recurrence's two
    (K x V) products per head (r.S and the state's decay)."""
    d, f = s["d_model"], s["d_ff"]
    lora = 2 * d * 5 * s["lora_mix"] + 2 * d * s["lora_decay"]
    per_layer = 6 * d * d + 2 * d * f + lora + 2 * d * s["wkv_head_dim"]
    return 2.0 * s["num_layers"] * per_layer


def flops_head(s: dict) -> float:
    """2 x the multiply-adds of the logits of one position."""
    return 2.0 * s["d_model"] * padded_vocab(s)


def served(cfg, params, tokens):
    from repro.models import rwkv6
    hidden, _ = rwkv6.forward(cfg, params, {"tokens": tokens})
    return rwkv6.logits_fn(cfg, params, hidden[:, -1:, :])[:, 0, :]


class Model:
    ITEM = "tokens"

    def __init__(self, sizes: dict, seed: int, root):
        from repro.models import rwkv6
        from repro.models.params import ParamDef
        from repro.serve.store import ServeStore
        self.sizes, self.root = sizes, root
        cfg = program_config(sizes)
        spec = weight_spec(sizes)
        want = jax.tree.map(lambda p: p.shape, rwkv6.param_defs(cfg),
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
        return req.batch * (req.length * flops_per_token(s) + flops_head(s))

    def free(self) -> None:
        """Drop the program's state; the weights stay for the reference."""
        del self.fwd, self.store

    def reference(self, tokens: np.ndarray, mode: str = "exact"
                  ) -> np.ndarray:
        """Reference next-token logits of ``tokens`` [N, T]."""
        ref = registry.reference_module(self.sizes["name"], self.root)
        return np.asarray(ref.logits(self.sizes, self.weights, tokens,
                                     mode))
