"""Attention: GQA (optional sliding window / bias / partial rotary) and
DeepSeek-V3 MLA, each with full-sequence and single-token-decode paths.

Full-sequence attention is computed in query chunks (``lax.scan``) so the
score tensor peak is (B, H, q_chunk, S) instead of (B, H, S, S) — the
difference between fitting and not fitting 32k prefill in HBM.  On real TPU
the Pallas flash kernel (``repro.kernels.local_attention``) replaces the
chunked-jnp path; the jnp path is what the dry-run lowers (DESIGN.md §3).

Decode caches:
  GQA:  k/v (B, Hkv, S_max, hd), written at ``pos`` per step.  Windowed
        layers use a ring buffer of size ``window`` plus a slot->absolute
        position buffer, so a 500k-token stream needs O(window) memory.
  MLA:  the compressed (B, S_max, kv_rank + rope_dim) latent cache; decode
        uses the *absorbed* form (score via W_uk-absorbed queries against
        the latent cache) so neither K nor V is ever materialized.
The decode functions also take the cache of a whole scanned layer stack
(every leaf with a leading layer axis) and a ``layer`` index: they then
write the step's one position (a K and a V row and its ``slot_pos``, or
one latent row) into that layer of the stacked buffer, which the scan
carries and updates in place, and attend over the layer's view of it.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import rms_norm, rope

__all__ = ["gqa_full", "gqa_decode", "mla_full", "mla_decode",
           "init_gqa_cache", "init_mla_cache"]

_NEG = -1.0e30


def _sdpa_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool, window: int, q_pos0: int, k_pos0: int,
                  q_chunk: int = 256, softmax_scale: float | None = None) -> jax.Array:
    """q: (B,S,H,hd); k/v: (B,Sk,Hkv,hd).  Returns (B,S,H,hd)."""
    b, s, h, hd = q.shape
    _, sk, hkv, _ = k.shape
    g = h // hkv
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    nc = s // q_chunk if (s % q_chunk == 0 and s > q_chunk) else 1
    qc = s // nc
    qr = q.reshape(b, nc, qc, hkv, g, hd).transpose(1, 0, 3, 4, 2, 5)
    kt = k.transpose(0, 2, 1, 3)  # (B,Hkv,Sk,hd)
    vt = v.transpose(0, 2, 1, 3)
    kpos = k_pos0 + jnp.arange(sk)

    def chunk(ci, qb):
        # qb: (B,Hkv,G,qc,hd)
        s_ = jnp.einsum("bkgqd,bksd->bkgqs", qb.astype(jnp.float32),
                        kt.astype(jnp.float32)) * scale
        qpos = q_pos0 + ci * qc + jnp.arange(qc)
        m = jnp.ones((qc, sk), bool)
        if causal:
            m &= qpos[:, None] >= kpos[None, :]
        if window > 0:
            m &= (qpos[:, None] - kpos[None, :]) < window
        s_ = jnp.where(m, s_, _NEG)
        p = jax.nn.softmax(s_, axis=-1)
        return jnp.einsum("bkgqs,bksd->bkgqd", p, vt.astype(jnp.float32))

    # checkpoint: backward re-forms each chunk's (bq x Sk) score block from
    # q/k instead of saving softmax residuals for every chunk — the chunked
    # equivalent of flash attention's recompute (O(S) not O(S^2) memory).
    out = jax.lax.scan(
        jax.checkpoint(lambda _, xs: (None, chunk(xs[0], xs[1]))), None,
        (jnp.arange(nc), qr))[1]
    hdv = v.shape[-1]  # v head dim can differ from q/k head dim (MLA)
    # (nc,B,Hkv,G,qc,hdv) -> (B,S,H,hdv)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, s, h, hdv)
    return out.astype(q.dtype)


# --- GQA -----------------------------------------------------------------------


def _qkv(cfg: ModelConfig, p: dict, x: jax.Array,
         kv_x: jax.Array | None = None):
    h, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    kv_in = x if kv_x is None else kv_x
    q = x @ p["w_q"].astype(x.dtype)
    k = kv_in @ p["w_k"].astype(x.dtype)
    v = kv_in @ p["w_v"].astype(x.dtype)
    if "b_q" in p:
        q = q + p["b_q"].astype(x.dtype)
        k = k + p["b_k"].astype(x.dtype)
        v = v + p["b_v"].astype(x.dtype)
    q = q.reshape(*x.shape[:-1], h, hd)
    k = k.reshape(*kv_in.shape[:-1], hk, hd)
    v = v.reshape(*kv_in.shape[:-1], hk, hd)
    return q, k, v


def gqa_full(cfg: ModelConfig, p: dict, x: jax.Array, *, pos0: int = 0,
             window: int = 0, causal: bool = True,
             cross_kv: jax.Array | None = None, use_rope: bool = True,
             return_cache: bool = False):
    """Full-sequence attention. cross_kv: encoder memory for cross-attention."""
    q, k, v = _qkv(cfg, p, x, cross_kv)
    if use_rope and cross_kv is None:
        s = x.shape[1]
        qpos = pos0 + jnp.arange(s)
        q = rope(q, qpos, theta=cfg.rope_theta, pct=cfg.rope_pct)
        k = rope(k, qpos, theta=cfg.rope_theta, pct=cfg.rope_pct)
    out = _sdpa_chunked(q, k, v, causal=causal and cross_kv is None,
                        window=window if cross_kv is None else 0,
                        q_pos0=pos0, k_pos0=pos0 if cross_kv is None else 0)
    y = out.reshape(*x.shape[:-1], -1) @ p["w_o"].astype(x.dtype)
    if return_cache:
        return y, (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
    return y


def init_gqa_cache(cfg: ModelConfig, batch: int, max_len: int, window: int = 0):
    hk, hd = cfg.n_kv_heads, cfg.head_dim_
    size = min(window, max_len) if window > 0 else max_len
    dt = cfg.activation_dtype
    return {
        "k": jnp.zeros((batch, hk, size, hd), dt),
        "v": jnp.zeros((batch, hk, size, hd), dt),
        # per-lane ring map: slot -> absolute position (continuous batching:
        # every batch lane decodes at its own position)
        "slot_pos": jnp.full((batch, size), -1, jnp.int32),
    }


def _write(buf: jax.Array, index: tuple, rows: jax.Array,
           layer: jax.Array | None) -> jax.Array:
    """``buf[index] = rows``, at ``buf[layer][index]`` in a stacked cache."""
    if layer is not None:
        index = (layer,) + index
    return buf.at[index].set(rows.astype(buf.dtype))


def _view(buf: jax.Array, layer: jax.Array | None) -> jax.Array:
    """One layer's buffer: ``buf[layer]`` of a stacked cache."""
    return buf if layer is None else buf[layer]


def gqa_decode(cfg: ModelConfig, p: dict, x: jax.Array, cache: dict,
               pos: jax.Array, *, window: int = 0,
               cross_kv: jax.Array | None = None,
               layer: jax.Array | None = None):
    """One-token decode. x: (B, 1, D); pos: (B,) int32 per-lane positions.
    With ``layer``, ``cache`` is a stacked cache (see the module doc)."""
    b = x.shape[0]
    h, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    q, k, v = _qkv(cfg, p, x)
    q = rope(q, pos[:, None], theta=cfg.rope_theta, pct=cfg.rope_pct)
    k = rope(k, pos[:, None], theta=cfg.rope_theta, pct=cfg.rope_pct)
    size = cache["k"].shape[-2]
    slot = pos % size if window > 0 else jnp.minimum(pos, size - 1)
    bi = jnp.arange(b)[:, None]
    hi = jnp.arange(hk)[None, :]
    row = (bi, hi, slot[:, None], slice(None))
    new_cache = {"k": _write(cache["k"], row, k[:, 0], layer),
                 "v": _write(cache["v"], row, v[:, 0], layer),
                 "slot_pos": _write(cache["slot_pos"], (jnp.arange(b), slot),
                                    pos, layer)}
    ck, cv, spos = (_view(new_cache[n], layer) for n in ("k", "v", "slot_pos"))

    if cross_kv is not None:
        raise NotImplementedError("use gqa_decode_cross for cross attention")

    qh = q.reshape(b, 1, hk, h // hk, hd).transpose(0, 2, 3, 1, 4)
    s_ = jnp.einsum("bkgqd,bksd->bkgqs", qh.astype(jnp.float32),
                    ck.astype(jnp.float32)) * hd ** -0.5
    valid = spos >= 0                               # (B, size)
    if window > 0:
        valid &= (pos[:, None] - spos) < window
    else:
        valid &= spos <= pos[:, None]
    s_ = jnp.where(valid[:, None, None, None, :], s_, _NEG)
    pw = jax.nn.softmax(s_, axis=-1)
    out = jnp.einsum("bkgqs,bksd->bkgqd", pw, cv.astype(jnp.float32))
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, 1, h * hd).astype(x.dtype)
    return out @ p["w_o"].astype(x.dtype), new_cache


def gqa_decode_cross(cfg: ModelConfig, p: dict, x: jax.Array,
                     enc_out: jax.Array):
    """Cross-attention during decode: static encoder memory, no cache update."""
    y = gqa_full(cfg, p, x, cross_kv=enc_out, causal=False, use_rope=False)
    return y


# --- MLA (DeepSeek-V3) ------------------------------------------------------------


def _mla_q(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array):
    m = cfg.mla
    h = cfg.n_heads
    if m.q_lora_rank is None:
        q = x @ p["w_q"].astype(x.dtype)
    else:
        cq = rms_norm(x @ p["w_dq"].astype(x.dtype), p["q_norm"], cfg.norm_eps)
        q = cq @ p["w_uq"].astype(x.dtype)
    q = q.reshape(*x.shape[:-1], h, m.qk_head_dim)
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = rope(q_rope, positions, theta=cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array):
    m = cfg.mla
    dkv = x @ p["w_dkv"].astype(x.dtype)           # (B,S,Rkv+rope)
    c_kv = rms_norm(dkv[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = dkv[..., m.kv_lora_rank:][:, :, None, :]  # (B,S,1,rope)
    k_rope = rope(k_rope, positions, theta=cfg.rope_theta)[:, :, 0, :]
    return c_kv, k_rope


def mla_full(cfg: ModelConfig, p: dict, x: jax.Array, *, pos0: int = 0,
             return_cache: bool = False):
    m = cfg.mla
    h = cfg.n_heads
    b, s, _ = x.shape
    positions = pos0 + jnp.arange(s)
    q_nope, q_rope = _mla_q(cfg, p, x, positions)
    c_kv, k_rope = _mla_latent(cfg, p, x, positions)
    k_nope = (c_kv @ p["w_uk"].astype(x.dtype)).reshape(b, s, h, m.qk_nope_head_dim)
    v = (c_kv @ p["w_uv"].astype(x.dtype)).reshape(b, s, h, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, :, None, :], (b, s, h, m.qk_rope_head_dim))], axis=-1)
    out = _sdpa_chunked(q, k, v, causal=True, window=0, q_pos0=pos0, k_pos0=pos0,
                        softmax_scale=m.qk_head_dim ** -0.5)
    y = out.reshape(b, s, -1) @ p["w_o"].astype(x.dtype)
    if return_cache:
        return y, jnp.concatenate([c_kv, k_rope], axis=-1)  # (B,S,Rkv+rope)
    return y


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int):
    return {"latent": jnp.zeros((batch, max_len, cfg.mla.cache_dim),
                                cfg.activation_dtype)}


def mla_decode(cfg: ModelConfig, p: dict, x: jax.Array, cache: dict,
               pos: jax.Array, *, layer: jax.Array | None = None):
    """Absorbed-matrix MLA decode: attention runs entirely in latent space.
    pos: (B,) int32 per-lane positions.  With ``layer``, ``cache`` is a
    stacked cache (see the module doc)."""
    m = cfg.mla
    h = cfg.n_heads
    b = x.shape[0]
    q_nope, q_rope = _mla_q(cfg, p, x, pos[:, None])       # (B,1,H,*)
    c_kv, k_rope = _mla_latent(cfg, p, x, pos[:, None])
    new_lat = jnp.concatenate([c_kv, k_rope], axis=-1)     # (B,1,D_lat)
    new_cache = {"latent": _write(cache["latent"],
                                  (jnp.arange(b), pos, slice(None)),
                                  new_lat[:, 0], layer)}
    lat = _view(new_cache["latent"], layer)
    c_all, r_all = lat[..., :m.kv_lora_rank], lat[..., m.kv_lora_rank:]

    # absorb W_uk into the query: q_eff[b,h,r] = sum_d q_nope[b,h,d] W_uk[r, h*d]
    wuk = p["w_uk"].astype(x.dtype).reshape(m.kv_lora_rank, h, m.qk_nope_head_dim)
    q_eff = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], wuk)
    scores = (jnp.einsum("bhr,bsr->bhs", q_eff.astype(jnp.float32),
                         c_all.astype(jnp.float32))
              + jnp.einsum("bhr,bsr->bhs", q_rope[:, 0].astype(jnp.float32),
                           r_all.astype(jnp.float32))) * m.qk_head_dim ** -0.5
    valid = jnp.arange(lat.shape[1])[None, :] <= pos[:, None]
    scores = jnp.where(valid[:, None, :], scores, _NEG)
    pw = jax.nn.softmax(scores, axis=-1)
    out_lat = jnp.einsum("bhs,bsr->bhr", pw, c_all.astype(jnp.float32))
    wuv = p["w_uv"].astype(x.dtype).reshape(m.kv_lora_rank, h, m.v_head_dim)
    out = jnp.einsum("bhr,rhd->bhd", out_lat.astype(x.dtype), wuv)
    y = out.reshape(b, 1, h * m.v_head_dim) @ p["w_o"].astype(x.dtype)
    return y, new_cache
