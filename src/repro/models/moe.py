"""Mixture-of-Experts layer: shared experts + routed top-k over the experts
held here, dropless by default.

Router (``MoEConfig`` fields, computed in float32): scores
``s = softmax|sigmoid(x W_r)`` over all ``n_routed`` experts; the top-k of
``s`` (of ``s + b`` with ``selection_bias``, DeepSeek's ``noaux_tc``) are
chosen, weighted by ``s`` normalised over the k, times ``routed_scale``.

Dispatch: a chip holds experts ``first_held .. first_held + held - 1``.
The batch rows split into dispatch groups, one per batch shard of the
context mesh (one off-mesh), so the sort stays along unsharded axes.
Within a group every (token, expert) pair is sorted by expert, pairs of
absent experts last, and the held experts' MLPs run as grouped matmuls
(``jax.lax.ragged_dot``) over the sorted rows: every pair routed to a held
expert is computed, at static shapes (top_k rows a token), and the absent
experts add nothing.  With a ``capacity_factor`` the pairs past each
expert's capacity in a batch row are marked absent first (the
dropped-token MoE the training configurations use).

The layer returns its per-held-expert pair counts beside its output.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

__all__ = ["moe_apply", "route"]


def route(cfg: ModelConfig, p: dict, x: jax.Array):
    """x: (..., D) -> (weights (..., k) f32, experts (..., k), aux_loss)."""
    m = cfg.moe
    logits = jnp.matmul(x.astype(jnp.float32), p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if m.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choice = scores + p["router_bias"].astype(jnp.float32) \
        if m.selection_bias else scores
    _, idx = jax.lax.top_k(choice, m.top_k)
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9) * m.routed_scale
    # Switch-style load-balance auxiliary loss.
    e = m.n_routed
    assign = jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(-2)
    frac = assign.reshape(-1, e).mean(0) / m.top_k
    prob = jax.nn.softmax(logits, axis=-1).reshape(-1, e).mean(0)
    aux = m.aux_loss_coef * e * jnp.sum(frac * prob)
    return w, idx, aux


def _within_capacity(cfg: ModelConfig, idx: jax.Array) -> jax.Array:
    """idx: (B, S, k) -> (B, S*k) bool: the first ``capacity`` pairs of each
    expert in each batch row, in (token, slot) order."""
    m = cfg.moe
    b, s, k = idx.shape
    e = m.n_routed
    cap = max(-(-s * k * int(4 * m.capacity_factor) // (4 * e)), 1)
    flat = idx.reshape(b, s * k)
    order = jnp.argsort(flat, axis=-1, stable=True)
    se = jnp.take_along_axis(flat, order, -1)
    starts = jax.vmap(lambda row: jnp.searchsorted(row, jnp.arange(e)))(se)
    rank = jnp.arange(s * k)[None, :] - jnp.take_along_axis(starts, se, -1)
    rows = jnp.arange(b)[:, None]
    return jnp.zeros((b, s * k), bool).at[rows, order].set(rank < cap)


def _shared(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    if "ws_in" not in p:
        return jnp.zeros_like(x)
    h = jax.nn.silu(x @ p["ws_in"].astype(x.dtype)) * (x @ p["ws_gate"].astype(x.dtype))
    return h @ p["ws_out"].astype(x.dtype)


def _dispatch_groups(b: int) -> int:
    """Batch shards of the context mesh (its ``pod`` x ``data`` extent)
    when they divide the ``b`` rows, else 1."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return 1
    g = math.prod(mesh.shape[a] for a in ("pod", "data")
                  if a in mesh.axis_names)
    return g if b % g == 0 else 1


def _experts(xs: jax.Array, sizes: jax.Array, w_in: jax.Array,
             w_gate: jax.Array, w_out: jax.Array) -> jax.Array:
    """Held experts' MLPs over rows sorted by expert, ``sizes`` rows each."""
    h = jax.nn.silu(jax.lax.ragged_dot(xs, w_in, sizes)) \
        * jax.lax.ragged_dot(xs, w_gate, sizes)
    return jax.lax.ragged_dot(h, w_out, sizes)


def moe_apply(cfg: ModelConfig, p: dict, x: jax.Array):
    """x: (B, S, D) -> (out (B, S, D), aux_loss, pairs per held expert
    (held,) int32)."""
    m = cfg.moe
    b, s, d = x.shape
    k, held = m.top_k, m.held
    g = _dispatch_groups(b)
    n = b * s * k // g                             # pairs of a group
    w, idx, aux = route(cfg, p, x)

    local = (idx - m.first_held).reshape(g, n)
    here = (local >= 0) & (local < held)
    if m.capacity_factor is not None:
        here &= _within_capacity(cfg, idx).reshape(g, n)
    key = jnp.where(here, local, held)             # absent pairs sort last
    order = jnp.argsort(key, axis=-1, stable=True)
    sizes = jax.vmap(lambda r: jnp.bincount(r, length=held + 1))(key)
    sizes = sizes[:, :held].astype(jnp.int32)

    xs = jnp.take_along_axis(x.reshape(g, n // k, d),
                             (order // k)[..., None], 1)   # by expert
    ws = [p[name].astype(x.dtype) for name in ("we_in", "we_gate", "we_out")]
    if g == 1:
        y = _experts(xs[0], sizes[0], *ws)[None]
    else:   # ragged_dot batches only with its weights batched too
        y = jax.vmap(_experts)(xs, sizes,
                               *[jnp.broadcast_to(w_, (g,) + w_.shape)
                                 for w_ in ws])
    # rows past the held groups belong to no expert: zero, whatever the
    # grouped matmul leaves there
    y = jnp.where((jnp.take_along_axis(key, order, -1) < held)[..., None],
                  y, 0)

    # back to (token, slot) order; combine in float32
    inv = jax.vmap(lambda o: jnp.zeros_like(o).at[o].set(jnp.arange(n)))(order)
    wk = jnp.where(here, w.reshape(g, n), 0.0)
    y = jnp.take_along_axis(y, inv[..., None], 1).astype(jnp.float32) \
        * wk[..., None]
    out = y.reshape(b, s, k, d).sum(axis=2).astype(x.dtype)
    return out + _shared(cfg, p, x), aux, sizes.sum(0)
