"""rwkv6-7b — RWKV-6 "Finch" 7B, attention-free, data-dependent decay.

[arXiv:2404.05892; huggingface.co/RWKV/v6-Finch-7B-HF]  32L d_model=4096,
64 heads of 64, d_ff=14336, vocab=65536, LayerNorm (eps 1e-5), untied head.

As published for this size:

- the output gate is a full-rank ``d × d`` projection,
  ``g = silu(x_g · W_g)`` (``models/rwkv6.py``);
- ``ln0``, a LayerNorm with scale and bias, normalises the embedding before
  the first block (``models/model.py``);
- the token-shift LoRA (``TIME_MIX_EXTRA_DIM``) has rank 64 and the decay
  LoRA (``TIME_DECAY_EXTRA_DIM``) rank 128: RWKV-LM v6 doubles both ranks of
  the smaller Finch sizes (32 and 64) at hidden size 4096.

Attention-free: O(1) decode state per layer → long_500k RUNS (max_context=None).
"""
from repro.configs.base import ArchConfig, RWKVConfig, register

CONFIG = register(ArchConfig(
    name="rwkv6-7b",
    family="rwkv",
    num_layers=32,
    d_model=4096,
    num_heads=64,                    # 4096 / head_dim 64
    num_kv_heads=64,
    head_dim=64,
    d_ff=14336,
    vocab_size=65536,
    block_pattern=("rwkv",),
    ffn_activation="relu_sq_rwkv",   # RWKV channel-mix: relu(x)^2 gated by receptance
    norm="layernorm",
    max_context=None,                # attention-free: unbounded context
    microbatches=4,
    rwkv=RWKVConfig(head_dim=64, decay_lora=128, mix_lora=64),
    source="[arXiv:2404.05892; huggingface.co/RWKV/v6-Finch-7B-HF]",
))
