"""IBM Granite 4.0-H Micro, 3B dense
(https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json).

40L, d_model=2048.  Layer i is GQA attention (32 query heads of 64 over 8
KV heads, no positional encoding) when i % 10 == 5 and Mamba-2 otherwise
(64 SSD heads of 64, d_state 128, one B/C group, expand 2, causal conv of
width 4, chunk 256).  Every layer ends in a SwiGLU MLP of 8192.  Vocab
100352, tied embeddings, RMSNorm eps 1e-5.  muP: embeddings x12, each
block's output x0.22 onto the residual, softmax scale 1/64, logits / 8.
"""
from repro.configs.base import ModelConfig

_PATTERN = tuple("attention" if i % 10 == 5 else "mamba" for i in range(40))

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="mamba_hybrid",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab_size=100_352,
    norm="rmsnorm",
    mlp="swiglu",
    rope="none",
    causal=True,
    block_pattern=_PATTERN,
    conv1d_width=4,
    mamba_n_heads=64,
    mamba_d_head=64,
    mamba_d_state=128,
    mamba_n_groups=1,
    mamba_chunk_size=256,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.015625,
    logits_scaling=8.0,
    rms_norm_eps=1e-5,
    tie_embeddings=True,
    param_dtype="bfloat16",      # the released checkpoint's
)
