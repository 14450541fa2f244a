"""Parameter templates: shapes, sharding, and init — one source of truth.

Every block kind declares its parameters once as ``ParamSpec``s; from the
same template tree we derive (a) real initialized arrays for smoke tests and
examples, (b) ``ShapeDtypeStruct`` stand-ins for the no-allocation dry-run,
(c) ``PartitionSpec`` trees for pjit, and (d) exact parameter counts for the
roofline's MODEL_FLOPS = 6·N·D term.

Sharding convention (mesh axes ``pod``/``data``/``model``):
  * vocab tables shard the (padded) vocab dim over ``model``;
  * attention/MLP follow Megatron TP: column-parallel in, row-parallel out;
  * MoE experts shard the expert dim over ``model`` (EP) or each expert's
    ffn dim (TP) per ``MoEConfig.shard_mode``;
  * recurrent-family inner widths shard over ``model`` when divisible,
    else replicate (xlstm-125m deliberately replicates — DP-only is the
    right call at 125M; see DESIGN.md).
Parameters are always replicated over ``pod`` and ``data`` (ZeRO-1 shards
*optimizer state*, not params — see repro.optim).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig

__all__ = ["ParamSpec", "model_templates", "init_params", "param_shape_structs",
           "param_pspecs", "param_counts"]


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    pspec: tuple[Any, ...]
    init: str = "fan_in"     # fan_in | normal02 | zeros | ones | lru_lambda
    dtype: str | None = None  # override config.param_dtype


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


# --- per-kind templates --------------------------------------------------------


def _norm(d: int) -> ParamSpec:
    return ParamSpec((d,), (None,), "ones")


def _mlp_templates(cfg: ModelConfig, dense: bool) -> dict[str, ParamSpec]:
    d = cfg.d_model
    if cfg.moe is not None and not dense:
        m = cfg.moe
        e, fe = m.held, m.d_expert
        fs = (m.d_shared or m.d_expert) * max(m.n_shared, 1)
        if m.shard_mode == "ep":
            ep = lambda *s: ("model",) + (None,) * (len(s) - 1)
        else:  # tp: shard each expert's ffn dim
            ep = lambda *s: (None, None, "model") if len(s) == 3 else (None,)
        t = {
            "router": ParamSpec((d, m.n_routed), (None, None), "normal02"),
            "we_in": ParamSpec((e, d, fe), ep(e, d, fe)),
            "we_gate": ParamSpec((e, d, fe), ep(e, d, fe)),
            "we_out": ParamSpec(
                (e, fe, d),
                ("model", None, None) if m.shard_mode == "ep" else (None, "model", None)),
        }
        if m.selection_bias:
            # the router's selection bias, kept in float32 as published
            # (DeepSeek-V3's e_score_correction_bias); drawn small
            t["router_bias"] = ParamSpec((m.n_routed,), (None,), "normal02",
                                         "float32")
        if m.n_shared:
            t.update({
                "ws_in": ParamSpec((d, fs), (None, "model")),
                "ws_gate": ParamSpec((d, fs), (None, "model")),
                "ws_out": ParamSpec((fs, d), ("model", None)),
            })
        return t
    f = cfg.d_ff
    t = {"w_in": ParamSpec((d, f), (None, "model")),
         "w_out": ParamSpec((f, d), ("model", None))}
    if cfg.mlp_kind in ("swiglu", "geglu"):
        t["w_gate"] = ParamSpec((d, f), (None, "model"))
    return t


def _attn_templates(cfg: ModelConfig, cross: bool = False) -> dict[str, ParamSpec]:
    d, h, hk, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    if cfg.mla is not None and not cross:
        m = cfg.mla
        if m.q_lora_rank is None:
            q = {"w_q": ParamSpec((d, h * m.qk_head_dim), (None, "model"))}
        else:
            q = {"w_dq": ParamSpec((d, m.q_lora_rank), (None, None)),
                 "q_norm": _norm(m.q_lora_rank),
                 "w_uq": ParamSpec((m.q_lora_rank, h * m.qk_head_dim),
                                   (None, "model"))}
        return {
            **q,
            "w_dkv": ParamSpec((d, m.kv_lora_rank + m.qk_rope_head_dim), (None, None)),
            "kv_norm": _norm(m.kv_lora_rank),
            "w_uk": ParamSpec((m.kv_lora_rank, h * m.qk_nope_head_dim), (None, "model")),
            "w_uv": ParamSpec((m.kv_lora_rank, h * m.v_head_dim), (None, "model")),
            "w_o": ParamSpec((h * m.v_head_dim, d), ("model", None)),
        }
    t = {
        "w_q": ParamSpec((d, h * hd), (None, "model")),
        "w_k": ParamSpec((d, hk * hd), (None, "model")),
        "w_v": ParamSpec((d, hk * hd), (None, "model")),
        "w_o": ParamSpec((h * hd, d), ("model", None)),
    }
    if cfg.attn_bias:
        t.update({
            "b_q": ParamSpec((h * hd,), ("model",), "zeros"),
            "b_k": ParamSpec((hk * hd,), ("model",), "zeros"),
            "b_v": ParamSpec((hk * hd,), ("model",), "zeros"),
        })
    return t


def _rglru_templates(cfg: ModelConfig) -> dict[str, ParamSpec]:
    d = cfg.d_model
    w = cfg.lru_width or d
    shard = "model" if w % 128 == 0 else None
    return {
        "w_y": ParamSpec((d, w), (None, shard)),
        "w_x": ParamSpec((d, w), (None, shard)),
        "conv_w": ParamSpec((cfg.conv1d_width, w), (None, shard), "normal02"),
        "conv_b": ParamSpec((w,), (shard,), "zeros"),
        "w_a": ParamSpec((w, w), (None, shard)),
        "b_a": ParamSpec((w,), (shard,), "zeros"),
        "w_i": ParamSpec((w, w), (None, shard)),
        "b_i": ParamSpec((w,), (shard,), "zeros"),
        "lam": ParamSpec((w,), (shard,), "lru_lambda"),
        "w_ro": ParamSpec((w, d), (shard, None)),
    }


def _mlstm_templates(cfg: ModelConfig) -> dict[str, ParamSpec]:
    """xLSTM mLSTM block: pf=2 up-projection, conv, matrix-memory cell."""
    d = cfg.d_model
    di = 2 * d
    h = cfg.n_heads
    rep = None  # 125M-class: replicate inner mats, DP-only (DESIGN.md)
    return {
        "w_up": ParamSpec((d, di), (None, rep)),
        "w_gate_up": ParamSpec((d, di), (None, rep)),
        "conv_w": ParamSpec((cfg.conv1d_width, di), (None, rep), "normal02"),
        "conv_b": ParamSpec((di,), (rep,), "zeros"),
        "w_q": ParamSpec((di, di), (None, rep)),
        "w_k": ParamSpec((di, di), (None, rep)),
        "w_v": ParamSpec((di, di), (None, rep)),
        "w_if": ParamSpec((di, h), (None, None), "normal02"),
        "b_if": ParamSpec((h,), (None,), "zeros"),
        "w_ff": ParamSpec((di, h), (None, None), "normal02"),
        "b_ff": ParamSpec((h,), (None,), "zeros"),
        "skip_scale": ParamSpec((di,), (rep,), "ones"),
        "w_down": ParamSpec((di, d), (rep, None)),
    }


def _slstm_templates(cfg: ModelConfig) -> dict[str, ParamSpec]:
    """xLSTM sLSTM block: scalar memory, block-diagonal recurrence, pf-4/3 FFN."""
    d = cfg.d_model
    h = cfg.n_heads
    hd = d // h
    f = ((4 * d // 3) + 127) // 128 * 128
    t: dict[str, ParamSpec] = {}
    for g in ("i", "f", "z", "o"):
        t[f"w_{g}"] = ParamSpec((d, d), (None, None))
        t[f"r_{g}"] = ParamSpec((h, hd, hd), (None, None, None))
        t[f"b_{g}"] = ParamSpec((d,), (None,), "zeros")
    t["ffn_in"] = ParamSpec((d, f), (None, "model" if f % 128 == 0 else None))
    t["ffn_gate"] = ParamSpec((d, f), (None, "model" if f % 128 == 0 else None))
    t["ffn_out"] = ParamSpec((f, d), ("model" if f % 128 == 0 else None, None))
    return t


def block_templates(cfg: ModelConfig, kind: str, dense: bool,
                    cross_attn: bool = False) -> dict[str, Any]:
    d = cfg.d_model
    if kind == "attn":
        t = {"ln1": _norm(d), "attn": _attn_templates(cfg),
             "ln2": _norm(d), "mlp": _mlp_templates(cfg, dense)}
        if cross_attn:
            t["ln_x"] = _norm(d)
            t["xattn"] = _attn_templates(cfg, cross=True)
        return t
    if kind == "rglru":
        return {"ln1": _norm(d), "rglru": _rglru_templates(cfg),
                "ln2": _norm(d), "mlp": _mlp_templates(cfg, True)}
    if kind == "mlstm":
        return {"ln1": _norm(d), "mlstm": _mlstm_templates(cfg)}
    if kind == "slstm":
        return {"ln1": _norm(d), "slstm": _slstm_templates(cfg), "ln2": _norm(d)}
    raise ValueError(f"unknown block kind {kind!r}")


# --- whole-model templates -------------------------------------------------------


def _super_block_templates(cfg: ModelConfig, cross_attn: bool = False) -> dict:
    """One scanned repetition of the pattern: keys '<i>_<kind>'."""
    return {f"{i}_{kind}": block_templates(cfg, kind, dense=False,
                                           cross_attn=cross_attn)
            for i, kind in enumerate(cfg.layer_plan().super_block)}


def _stack(tree: dict, n: int) -> dict:
    """Prepend a scan dim of length n to every leaf spec."""
    def add(spec: ParamSpec) -> ParamSpec:
        return ParamSpec((n,) + spec.shape, (None,) + spec.pspec, spec.init,
                         spec.dtype)
    return jax.tree_util.tree_map(add, tree, is_leaf=_is_spec)


def model_templates(cfg: ModelConfig) -> dict:
    plan = cfg.layer_plan()
    d, vp = cfg.d_model, cfg.padded_vocab
    t: dict[str, Any] = {
        "embed": ParamSpec((vp, d), ("model", None), "normal02"),
        "final_norm": _norm(d),
    }
    if not cfg.tie_embeddings:
        t["head"] = ParamSpec((vp, d), ("model", None), "normal02")
    cross = cfg.is_encdec
    if plan.prefix:
        t["prefix"] = {f"{i}_{k}": block_templates(cfg, k, dense=True, cross_attn=cross)
                       for i, k in enumerate(plan.prefix)}
    if plan.n_super:
        t["stack"] = _stack(_super_block_templates(cfg, cross), plan.n_super)
    if plan.tail:
        t["tail"] = {f"{i}_{k}": block_templates(cfg, k, dense=False, cross_attn=cross)
                     for i, k in enumerate(plan.tail)}
    if cfg.is_encdec:
        enc = {f"0_attn": block_templates(cfg, "attn", dense=True)}
        t["encoder"] = {"stack": _stack(enc, cfg.encoder_layers),
                        "final_norm": _norm(d)}
    if cfg.frontend is not None:
        t["frontend"] = {"adapter": ParamSpec((d, d), (None, None))}
    return t


# --- materialization ---------------------------------------------------------------


def _init_leaf(spec: ParamSpec, key: jax.Array, dtype) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "lru_lambda":
        # a = exp(-8 * softplus(lam)) in [0.9, 0.999] at init (Griffin A.2)
        u = jax.random.uniform(key, spec.shape, jnp.float32,
                               minval=0.9 ** 2, maxval=0.999 ** 2)
        lam = jnp.log(jnp.expm1(-jnp.log(u) / (2.0 * 8.0)))
        return lam.astype(dtype)
    if spec.init == "normal02":
        return (0.02 * jax.random.normal(key, spec.shape, jnp.float32)).astype(dtype)
    # fan_in: std = 1/sqrt(fan_in); fan_in = second-to-last dim (or last for 1-D)
    fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return (std * jax.random.normal(key, spec.shape, jnp.float32)).astype(dtype)


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    tree = model_templates(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    dtype = jnp.dtype(cfg.param_dtype)
    out = [_init_leaf(spec, k, jnp.dtype(spec.dtype) if spec.dtype else dtype)
           for spec, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, out)


def param_shape_structs(cfg: ModelConfig) -> dict:
    dtype = jnp.dtype(cfg.param_dtype)
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype) if s.dtype else dtype),
        model_templates(cfg), is_leaf=_is_spec)


def param_pspecs(cfg: ModelConfig, *, fsdp_size: int = 0,
                 tp_size: int = 16) -> dict:
    """PartitionSpec tree; optionally adds FSDP sharding over ``data``.

    ``fsdp_size`` > 0 (set for >=30B configs) shards each parameter's
    largest still-unsharded, divisible dimension over the ``data`` axis —
    ZeRO-3-style: XLA SPMD then all-gathers each layer's weights just
    before use inside the scan (persistent footprint /= fsdp_size).
    The scan-stack dim (dim 0 of stacked params) is never FSDP-sharded.
    FSDP never spans ``pod`` so per-chip shards are pod-count invariant
    (elastic scaling across pods, DESIGN.md §5).
    """
    def to_pspec(spec: ParamSpec, stacked_hint: bool) -> P:
        axes = list(spec.pspec)
        # drop TP axes the mesh can't divide (e.g. tiny test meshes)
        for i, ax in enumerate(axes):
            if ax == "model" and spec.shape[i] % tp_size != 0:
                axes[i] = None
        if fsdp_size:
            start = 1 if stacked_hint else 0
            cands = [i for i in range(start, len(axes))
                     if axes[i] is None and spec.shape[i] % fsdp_size == 0
                     and spec.shape[i] >= 4 * fsdp_size]
            if cands:
                best = max(cands, key=lambda i: spec.shape[i])
                axes[best] = "data"
        return P(*axes)

    def walk(node, under_stack: bool):
        if _is_spec(node):
            return to_pspec(node, under_stack)
        return {k: walk(v, under_stack or k == "stack") for k, v in node.items()}

    return walk(model_templates(cfg), False)


def param_counts(cfg: ModelConfig) -> tuple[int, int]:
    """(total, active-per-token) parameter counts from the template tree."""
    total = 0
    inactive = 0
    for path, spec in jax.tree_util.tree_flatten_with_path(
            model_templates(cfg), is_leaf=_is_spec)[0]:
        n = int(math.prod(spec.shape))
        total += n
        if cfg.moe is not None:
            keys = [getattr(p, "key", None) for p in path]
            if any(k in ("we_in", "we_gate", "we_out") for k in keys):
                inactive += n * (cfg.moe.n_routed - cfg.moe.top_k) // cfg.moe.n_routed
    return total, total - inactive
