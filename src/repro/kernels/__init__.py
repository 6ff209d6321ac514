"""Pallas TPU kernels for the paper's compute hot-spots.

  fused_ibn       C3: pw-expand -> act -> pw-project with the expanded
                  intermediate resident only in VMEM (depth-first tiles)
  matmul_ln       C2: LayerNorm statistics computed in the accumulator
                  before writeback (pixelwise ordering)
  flash_attention C2: online-softmax attention (m/l/acc scratch = the
                  streaming writeback buffer), causal + sliding window
  depthwise_conv  C1: C|FX dataflow — channels on VPU lanes, kernel taps
                  as an unrolled temporal accumulation (no MXU)
  rwkv_chunk      beyond-paper: chunked WKV6 recurrence, state + decay
                  tensors VMEM-resident
  stacked_proj    x @ w[layer] read from a whole [L, K, N] weight stack,
                  each tile cast to the compute dtype in VMEM (RWKV-6's
                  projections)

``ops`` exposes jit'd wrappers (auto-padding, interpret=True off-TPU);
``ref`` holds the pure-jnp oracles every kernel is tested against.
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
