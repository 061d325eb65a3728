"""Architecture configuration system.

Every assigned architecture is expressed as an :class:`ArchConfig` — a single
frozen dataclass that the model builder (``repro.models.model``) consumes.
Configs register themselves into :data:`ARCH_REGISTRY` at import time via
:func:`register`; ``repro.configs`` imports every ``<arch>.py`` so that
``get_config("<id>")`` works everywhere (launcher, tests, benchmarks).

Layer kinds
-----------
The decoder stack is described by a repeating *block pattern* of layer kinds:

- ``"attn"``        — global causal self-attention (GQA)
- ``"local_attn"``  — sliding-window causal self-attention
- ``"recurrent"``   — RG-LRU gated linear recurrence block
- ``"rwkv"``        — RWKV-6 time-mix block (data-dependent decay)

Cross-attention (vision) and encoder-decoder (whisper) wiring is expressed
with dedicated fields rather than layer kinds, since they change the input
signature of the model.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts settings (GShard/DeepSeek style routed experts)."""

    num_experts: int
    top_k: int
    expert_d_ff: int
    num_shared_experts: int = 0
    shared_d_ff: Optional[int] = None          # defaults to expert_d_ff * shared
    capacity_factor: float = 1.25
    router_dtype: str = "float32"
    # index of first MoE layer; earlier layers use the dense FFN
    first_moe_layer: int = 1

    @property
    def shared_ff(self) -> int:
        if self.shared_d_ff is not None:
            return self.shared_d_ff
        return self.expert_d_ff * max(self.num_shared_experts, 1)


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V2 multi-head latent attention settings."""

    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class RecurrentConfig:
    """RG-LRU (Griffin/RecurrentGemma) recurrent-block settings."""

    lru_width: int = 4096
    conv_width: int = 4
    # c constant in a_t = exp(-c * softplus(Lambda) * sigmoid(W_a x))
    c: float = 8.0


@dataclasses.dataclass(frozen=True)
class RWKVConfig:
    """RWKV-6 (Finch) time-mix settings."""

    head_dim: int = 64
    decay_lora: int = 64
    mix_lora: int = 32


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                      # dense | moe | rwkv | hybrid | encdec | vision
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None   # default d_model // num_heads
    # block pattern, tiled over num_layers (e.g. ("recurrent","recurrent","local_attn"))
    block_pattern: Tuple[str, ...] = ("attn",)
    # FFN activation: "swiglu" | "squared_relu" | "gelu" | "relu_sq_rwkv"
    ffn_activation: str = "swiglu"
    qkv_bias: bool = False
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    rope_theta: float = 10000.0
    tie_embeddings: bool = False
    local_window: int = 4096         # for "local_attn" layers
    # sub-quadratic context support: None = quadratic attention (long_500k skips)
    max_context: Optional[int] = 131072
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    recurrent: Optional[RecurrentConfig] = None
    rwkv: Optional[RWKVConfig] = None
    # --- encoder-decoder (whisper) ---
    encoder_layers: int = 0
    encoder_frames: int = 1500       # precomputed frame embeddings (frontend stub)
    # --- vision cross-attention (llama-3.2-vision) ---
    cross_attn_every: int = 0        # every Nth layer is a gated cross-attn layer
    num_image_tokens: int = 1600     # precomputed patch embeddings (frontend stub)
    # --- numerics ---
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    # optimizer moment dtype ("float32" default, "bfloat16" for the 236B/340B
    # archs so the single-pod 256 x 16GB HBM budget holds — see DESIGN.md §5.4)
    moment_dtype: str = "float32"
    remat_policy: str = "full"       # nothing | dots | full | moe (hillclimb)
    grad_accum_dtype: str = "float32"  # bf16 halves the accumulator for giants
    microbatches: int = 1            # gradient-accumulation steps per train step
    attn_chunk: int = 512            # online-softmax query-block size
    xent_chunk: int = 256            # chunked cross-entropy sequence block
    use_pallas: bool = False         # TPU target path; CPU dry-run uses pure JAX
    source: str = ""                 # provenance note [citation; tier]

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.num_heads

    def layer_kinds(self) -> Tuple[str, ...]:
        """The per-layer kind list, tiling ``block_pattern`` to num_layers."""
        pat = self.block_pattern
        reps = (self.num_layers + len(pat) - 1) // len(pat)
        return tuple((pat * reps)[: self.num_layers])

    def kind_counts(self) -> dict:
        kinds = self.layer_kinds()
        return {k: kinds.count(k) for k in sorted(set(kinds))}

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype)

    def num_params(self) -> int:
        """Analytic parameter count (used for MODEL_FLOPS = 6·N·D)."""
        from repro.models.model import count_params  # local import to avoid cycle
        return count_params(self)

    def num_active_params(self) -> int:
        from repro.models.model import count_params
        return count_params(self, active_only=True)


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    """One assigned input-shape cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: str                        # "train" | "prefill" | "decode"


SHAPES: dict = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


ARCH_REGISTRY: dict = {}


def register(cfg: ArchConfig) -> ArchConfig:
    ARCH_REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ArchConfig:
    import repro.configs  # noqa: F401  — triggers per-arch module imports

    if name not in ARCH_REGISTRY:
        raise KeyError(f"unknown architecture {name!r}; known: {sorted(ARCH_REGISTRY)}")
    return ARCH_REGISTRY[name]


def list_archs() -> Sequence[str]:
    import repro.configs  # noqa: F401

    return sorted(ARCH_REGISTRY)


def supports_shape(cfg: ArchConfig, shape: ShapeSpec) -> Tuple[bool, str]:
    """Admission check for an (arch × shape) cell.

    This is the control-plane capability check: quadratic-attention archs do
    not advertise 500k contexts, so the long_500k cell is rejected by the
    descriptor rather than silently attempted (DESIGN.md §4).
    """
    if shape.kind == "decode" and cfg.family == "encdec" and shape.seq_len > 65536:
        return False, "enc-dec decoder context bound"
    if cfg.max_context is not None and shape.seq_len > cfg.max_context:
        return False, (
            f"{cfg.name} is quadratic-attention (max_context={cfg.max_context}); "
            f"{shape.name} ({shape.seq_len}) requires sub-quadratic decode state"
        )
    return True, "ok"


def reduced(cfg: ArchConfig, **overrides) -> ArchConfig:
    """A tiny same-family config for CPU smoke tests.

    Keeps the layer-kind *pattern* (so at least one full pattern repetition
    runs), shrinks widths/experts/vocab.
    """
    small = dict(
        num_layers=max(len(cfg.block_pattern) * 2, 2),
        d_model=64,
        num_heads=4,
        num_kv_heads=min(cfg.num_kv_heads, 2),
        head_dim=16,
        d_ff=128,
        vocab_size=256,
        encoder_layers=2 if cfg.encoder_layers else 0,
        encoder_frames=16 if cfg.encoder_layers else 1500,
        cross_attn_every=cfg.cross_attn_every and 2,
        num_image_tokens=8 if cfg.cross_attn_every else 1600,
        local_window=16,
        attn_chunk=16,
        xent_chunk=32,
        microbatches=1,
        moment_dtype="float32",
        param_dtype="float32",
        compute_dtype="float32",
    )
    if cfg.moe is not None:
        small["moe"] = dataclasses.replace(
            cfg.moe, num_experts=4, top_k=2, expert_d_ff=64,
            num_shared_experts=min(cfg.moe.num_shared_experts, 1),
            shared_d_ff=64 if cfg.moe.num_shared_experts else None,
            first_moe_layer=min(cfg.moe.first_moe_layer, 1),
            # drop-free on CPU so decode/forward parity is exact: capacity
            # drops legitimately differ with sequence length otherwise
            capacity_factor=8.0,
        )
    if cfg.mla is not None:
        small["mla"] = MLAConfig(kv_lora_rank=32, q_lora_rank=48,
                                 qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
        small["head_dim"] = None
    if cfg.recurrent is not None:
        small["recurrent"] = RecurrentConfig(lru_width=64, conv_width=4, c=8.0)
    if cfg.rwkv is not None:
        small["rwkv"] = RWKVConfig(head_dim=16, decay_lora=8, mix_lora=8)
        small["num_heads"] = 4
        small["head_dim"] = 16
    small.update(overrides)
    return dataclasses.replace(cfg, **small)
