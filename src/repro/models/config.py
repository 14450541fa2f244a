"""Model configuration — one dataclass covering all assigned arch families.

Families: dense decoder LMs (GQA), MoE (shared+routed top-k), MLA+MoE
(DeepSeek-V3), hybrid recurrent (RG-LRU + local attention), xLSTM
(sLSTM/mLSTM), encoder-decoder (Seamless), and VLM/audio-frontend stubs.

Layer stacking: each layer has a *kind* (``attn``, ``moe``, ``rglru``,
``mlstm``, ``slstm``).  The stack is compiled as
``prefix (unrolled) + scan over repeated pattern super-blocks + tail
(unrolled)`` so the HLO stays small for 60–96-layer models while still
supporting mixed patterns like RecurrentGemma's 2:1 recurrent:attention.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax.numpy as jnp

__all__ = ["MoEConfig", "MLAConfig", "ModelConfig", "LayerPlan"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_routed: int
    top_k: int
    d_expert: int                 # intermediate size of each routed expert
    n_shared: int = 0
    d_shared: int | None = None   # intermediate size of each shared expert
    aux_loss_coef: float = 0.001
    # "ep"  -> experts sharded over the model axis (one expert group/chip)
    # "tp"  -> every expert's ffn dim sharded over the model axis
    shard_mode: str = "ep"
    # router: scores s = softmax|sigmoid(x W_r) over all ``n_routed``; the
    # top_k of s (of s + b, b the learned ``router_bias``, with
    # ``selection_bias``) are chosen and weighted by s, normalised over the
    # k, times ``routed_scale``
    scoring: str = "softmax"
    selection_bias: bool = False
    routed_scale: float = 1.0
    # experts held here: ``n_held`` of them from ``first_held`` (None: all
    # ``n_routed``); tokens routed elsewhere add nothing on this chip
    first_held: int = 0
    n_held: int | None = None
    # None -> dropless.  Else each expert takes at most
    # ceil(S * top_k * capacity_factor / n_routed) tokens of a batch row;
    # overflow tokens fall through the residual (dropped-token MoE)
    capacity_factor: float | None = 1.25

    @property
    def d_shared_total(self) -> int:
        return (self.d_shared or self.d_expert) * self.n_shared

    @property
    def held(self) -> int:
        return self.n_routed if self.n_held is None else self.n_held


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V3 multi-head latent attention dims; ``q_lora_rank`` None
    projects the query directly (Moonlight)."""

    q_lora_rank: int | None = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_dim(self) -> int:
        """Per-token decode cache: compressed kv + shared rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # audio|dense|hybrid|vlm|moe|ssm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int | None = None
    # layer stack: kinds cycled from ``pattern``; ``dense_prefix`` forces the
    # first k layers to plain attn+dense-mlp (DeepSeek-V3's first 3 layers).
    pattern: tuple[str, ...] = ("attn",)
    dense_prefix: int = 0
    # attention
    attn_bias: bool = False
    rope_theta: float = 10_000.0
    rope_pct: float = 1.0         # stablelm-2 uses 25% partial rotary
    local_window: int = 0         # >0: sliding-window for ``attn`` layers
    mla: MLAConfig | None = None
    # mlp
    mlp_kind: str = "swiglu"      # swiglu|relu2|geglu|none
    moe: MoEConfig | None = None
    # recurrent families
    lru_width: int | None = None  # RG-LRU state width (default d_model)
    conv1d_width: int = 4
    # encoder-decoder
    encoder_layers: int = 0
    # frontends (stubs: input_specs provide precomputed embeddings)
    frontend: str | None = None   # audio|vision|None
    frontend_tokens: int = 0      # e.g. 576 vision patches
    # numerics
    dtype: str = "bfloat16"       # activations
    param_dtype: str = "float32"  # parameters (bf16 + Adafactor for >=30B)
    norm_eps: float = 1.0e-6
    tie_embeddings: bool = False
    logit_chunks: int = 8         # chunked CE to bound the logits peak
    vocab_pad_multiple: int = 2048  # pad tables so "model"-axis sharding divides

    def __post_init__(self) -> None:
        # a configuration read from JSON brings its blocks as dicts and
        # its pattern as a list
        if isinstance(self.moe, dict):
            object.__setattr__(self, "moe", MoEConfig(**self.moe))
        if isinstance(self.mla, dict):
            object.__setattr__(self, "mla", MLAConfig(**self.mla))
        object.__setattr__(self, "pattern", tuple(self.pattern))

    # ----- derived -------------------------------------------------------------
    @property
    def head_dim_(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_multiple
        return ((self.vocab_size + m - 1) // m) * m

    @property
    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    def layer_kinds(self) -> tuple[str, ...]:
        kinds = ["attn"] * self.dense_prefix
        i = 0
        while len(kinds) < self.n_layers:
            kinds.append(self.pattern[i % len(self.pattern)])
            i += 1
        return tuple(kinds[: self.n_layers])

    def layer_plan(self) -> "LayerPlan":
        return LayerPlan.build(self.layer_kinds(), self.pattern, self.dense_prefix)

    @property
    def n_moe_layers(self) -> int:
        """Layers whose MLP is the expert layer: the attention blocks past
        the dense prefix."""
        if self.moe is None:
            return 0
        return self.layer_kinds()[self.dense_prefix:].count("attn")

    # Parameter counts are computed from the actual template tree — see
    # ``repro.models.params.param_counts`` — so they can never drift from
    # the implementation.


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """How the layer list compiles to prefix + scanned super-blocks + tail."""

    prefix: tuple[str, ...]        # unrolled leading layer kinds
    super_block: tuple[str, ...]   # one scanned repetition
    n_super: int                   # scan length
    tail: tuple[str, ...]          # unrolled trailing layer kinds

    @staticmethod
    def build(kinds: Sequence[str], pattern: Sequence[str],
              dense_prefix: int) -> "LayerPlan":
        prefix = tuple(kinds[:dense_prefix])
        body = tuple(kinds[dense_prefix:])
        plen = len(pattern)
        n_super = len(body) // plen
        tail = body[n_super * plen:]
        return LayerPlan(prefix=prefix, super_block=tuple(pattern),
                         n_super=n_super, tail=tail)

    @property
    def n_layers(self) -> int:
        return len(self.prefix) + self.n_super * len(self.super_block) + len(self.tail)
