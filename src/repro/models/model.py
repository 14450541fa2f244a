"""Model assembly: block dispatch, layer-stack scan, train/prefill/decode.

The stack compiles as ``prefix (unrolled) + lax.scan over super-blocks +
tail (unrolled)`` with per-super-block rematerialization, so a 96-layer
340B model lowers to a compact HLO whose memory profile is
(1 super-block of activations) x (scan carry), not 96 layers of residuals.

Three entry points per architecture (what the dry-run lowers per shape):
  ``loss``         — training forward (train_* shapes)
  ``prefill``      — full-sequence forward that also builds the decode cache
                     (prefill_* shapes)
  ``decode_step``  — one new token against the cache (decode_* / long_*)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain, constrain_residual
from repro.models import attention as attn
from repro.models import recurrent as rec
from repro.models.config import ModelConfig
from repro.models.layers import chunked_ce_loss, embed_tokens, mlp_apply, rms_norm
from repro.models.moe import moe_apply

__all__ = ["LM"]


# --------------------------------------------------------------------------- #
# single-block apply                                                           #
# --------------------------------------------------------------------------- #


def _block_full(cfg: ModelConfig, kind: str, p: dict, x: jax.Array, *,
                pos0: int, dense: bool, enc_out: jax.Array | None,
                causal: bool, build_cache: bool):
    """Returns (x, aux_loss, cache_or_None, expert_load_or_None)."""
    aux = jnp.zeros((), jnp.float32)
    cache = None
    x = constrain_residual(x)
    if kind == "attn":
        h_in = rms_norm(x, p["ln1"], cfg.norm_eps)
        window = cfg.local_window
        if cfg.mla is not None:
            if build_cache:
                y, lat = attn.mla_full(cfg, p["attn"], h_in, pos0=pos0,
                                       return_cache=True)
                cache = {"latent": lat}
            else:
                y = attn.mla_full(cfg, p["attn"], h_in, pos0=pos0)
        else:
            if build_cache:
                y, (k, v) = attn.gqa_full(cfg, p["attn"], h_in, pos0=pos0,
                                          window=window, causal=causal,
                                          return_cache=True)
                cache = {"k": k, "v": v}
            else:
                y = attn.gqa_full(cfg, p["attn"], h_in, pos0=pos0,
                                  window=window, causal=causal)
        x = x + y
        if enc_out is not None and "xattn" in p:
            xh = rms_norm(x, p["ln_x"], cfg.norm_eps)
            x = x + attn.gqa_full(cfg, p["xattn"], xh, cross_kv=enc_out,
                                  causal=False, use_rope=False)
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.moe is not None and not dense:
            y2, aux, load = moe_apply(cfg, p["mlp"], h2)
        else:
            y2, load = mlp_apply(cfg, p["mlp"], h2), None
        return x + y2, aux, cache, load

    if kind == "rglru":
        h_in = rms_norm(x, p["ln1"], cfg.norm_eps)
        if build_cache:
            y, st = rec.rglru_full(cfg, p["rglru"], h_in, return_state=True)
            cache = st
        else:
            y = rec.rglru_full(cfg, p["rglru"], h_in)
        x = x + y
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        return x + mlp_apply(cfg, p["mlp"], h2), aux, cache, None

    if kind == "mlstm":
        h_in = rms_norm(x, p["ln1"], cfg.norm_eps)
        if build_cache:
            y, st = rec.mlstm_full(cfg, p["mlstm"], h_in, return_state=True)
            cache = st
        else:
            y = rec.mlstm_full(cfg, p["mlstm"], h_in)
        return x + y, aux, cache, None

    if kind == "slstm":
        h_in = rms_norm(x, p["ln1"], cfg.norm_eps)
        if build_cache:
            y, st = rec.slstm_full(cfg, p["slstm"], h_in, return_state=True)
            cache = st
        else:
            y = rec.slstm_full(cfg, p["slstm"], h_in)
        x = x + y
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        return x + rec.slstm_ffn(p["slstm"], h2), aux, cache, None

    raise ValueError(f"unknown block kind {kind!r}")


def _block_decode(cfg: ModelConfig, kind: str, p: dict, x: jax.Array,
                  cache: dict, pos: jax.Array, *, dense: bool,
                  enc_out: jax.Array | None):
    """One-token step.  Returns (x, new_cache, expert_load_or_None)."""
    if kind == "attn":
        h_in = rms_norm(x, p["ln1"], cfg.norm_eps)
        if cfg.mla is not None:
            y, cache = attn.mla_decode(cfg, p["attn"], h_in, cache, pos)
        else:
            y, cache = attn.gqa_decode(cfg, p["attn"], h_in, cache, pos,
                                       window=cfg.local_window)
        x = x + y
        if enc_out is not None and "xattn" in p:
            xh = rms_norm(x, p["ln_x"], cfg.norm_eps)
            x = x + attn.gqa_decode_cross(cfg, p["xattn"], xh, enc_out)
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.moe is not None and not dense:
            y2, _, load = moe_apply(cfg, p["mlp"], h2)
        else:
            y2, load = mlp_apply(cfg, p["mlp"], h2), None
        return x + y2, cache, load

    if kind == "rglru":
        h_in = rms_norm(x, p["ln1"], cfg.norm_eps)
        y, cache = rec.rglru_decode(cfg, p["rglru"], h_in, cache)
        x = x + y
        return x + mlp_apply(cfg, p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps)), cache, None

    if kind == "mlstm":
        h_in = rms_norm(x, p["ln1"], cfg.norm_eps)
        y, cache = rec.mlstm_decode(cfg, p["mlstm"], h_in, cache)
        return x + y, cache, None

    if kind == "slstm":
        h_in = rms_norm(x, p["ln1"], cfg.norm_eps)
        y, cache = rec.slstm_decode(cfg, p["slstm"], h_in, cache)
        x = x + y
        return x + rec.slstm_ffn(p["slstm"], rms_norm(x, p["ln2"], cfg.norm_eps)), cache, None

    raise ValueError(kind)


def _init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int):
    if kind == "attn":
        if cfg.mla is not None:
            return attn.init_mla_cache(cfg, batch, max_len)
        return attn.init_gqa_cache(cfg, batch, max_len, cfg.local_window)
    if kind == "rglru":
        return rec.init_rglru_state(cfg, batch)
    if kind == "mlstm":
        return rec.init_mlstm_state(cfg, batch)
    if kind == "slstm":
        return rec.init_slstm_state(cfg, batch)
    raise ValueError(kind)


def _cache_from_prefill(cfg: ModelConfig, kind: str, built: dict | None,
                        batch: int, seq: int, max_len: int):
    """Convert prefill-built per-layer state into a decode cache of max_len."""
    if built is None:
        return _init_block_cache(cfg, kind, batch, max_len)
    if kind == "attn" and cfg.mla is not None:
        cache = attn.init_mla_cache(cfg, batch, max_len)
        lat = jax.lax.dynamic_update_slice(
            cache["latent"], built["latent"].astype(cache["latent"].dtype),
            (0, 0, 0))
        return {"latent": lat}
    if kind == "attn":
        cache = attn.init_gqa_cache(cfg, batch, max_len, cfg.local_window)
        size = cache["k"].shape[2]
        k, v = built["k"].astype(cache["k"].dtype), built["v"].astype(cache["v"].dtype)
        if cfg.local_window > 0 and seq > size:
            # keep the last `size` positions, ring-aligned: slot = pos % size
            positions = jnp.arange(seq - size, seq)
            slots = positions % size
            ck = cache["k"].at[:, :, slots, :].set(k[:, :, -size:, :])
            cv = cache["v"].at[:, :, slots, :].set(v[:, :, -size:, :])
            sp = cache["slot_pos"].at[:, slots].set(
                positions.astype(jnp.int32)[None, :])
            return {"k": ck, "v": cv, "slot_pos": sp}
        ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0))
        sp = cache["slot_pos"].at[:, :seq].set(
            jnp.arange(seq, dtype=jnp.int32)[None, :])
        return {"k": ck, "v": cv, "slot_pos": sp}
    return built  # recurrent states carry over unchanged


def _stack_loads(loads: list) -> jax.Array | None:
    """Per-held-expert pair counts of the expert layers, in layer order:
    (n_moe_layers, held) int32, or None for a model without experts."""
    loads = [l.reshape(-1, l.shape[-1]) for l in loads if l is not None]
    return jnp.concatenate(loads) if loads else None


# --------------------------------------------------------------------------- #
# whole model                                                                  #
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class LM:
    cfg: ModelConfig

    # ----- input embedding / frontends ---------------------------------------
    def _inputs(self, params: dict, batch: dict):
        """Returns (x, labels|None, enc_out|None)."""
        cfg = self.cfg
        labels = batch.get("labels")
        enc_out = None
        if cfg.is_encdec:
            frames = batch["frames"].astype(cfg.activation_dtype)
            frames = frames @ params["frontend"]["adapter"].astype(frames.dtype)
            enc_out = self._encode(params, frames)
        x = embed_tokens(cfg, params["embed"], batch["tokens"])
        if cfg.frontend == "vision":
            patches = batch["patches"].astype(cfg.activation_dtype)
            patches = patches @ params["frontend"]["adapter"].astype(patches.dtype)
            x = jnp.concatenate([patches, x], axis=1)
            if labels is not None:
                pad = jnp.full(patches.shape[:2], -1, labels.dtype)
                labels = jnp.concatenate([pad, labels], axis=1)
        x = constrain(x, ("pod", "data"), None, None)
        return x, labels, enc_out

    def _encode(self, params: dict, frames: jax.Array) -> jax.Array:
        cfg = self.cfg
        enc = params["encoder"]

        def body(x, lp):
            x, _, _, _ = _block_full(cfg, "attn", lp["0_attn"], x, pos0=0,
                                     dense=True, enc_out=None, causal=False,
                                     build_cache=False)
            return x, None

        x, _ = jax.lax.scan(jax.checkpoint(body), frames, enc["stack"])
        return rms_norm(x, enc["final_norm"], cfg.norm_eps)

    # ----- layer-stack traversal ----------------------------------------------
    def _super_full(self, sp: dict, x: jax.Array, *, pos0: int,
                    enc_out, build_cache: bool):
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        caches = {}
        loads = []
        for key in sorted(sp.keys(), key=lambda s: int(s.split("_")[0])):
            kind = key.split("_", 1)[1]
            x, a, c, l = _block_full(cfg, kind, sp[key], x, pos0=pos0,
                                     dense=False, enc_out=enc_out, causal=True,
                                     build_cache=build_cache)
            aux = aux + a
            loads.append(l)
            if build_cache:
                caches[key] = c
        return x, aux, caches, _stack_loads(loads)

    def _forward(self, params: dict, x: jax.Array, *, enc_out=None,
                 build_cache: bool = False, remat: bool = True):
        """Shared full-sequence traversal.  Returns (x, aux, caches, expert
        loads); the caches and loads only with ``build_cache``."""
        cfg = self.cfg
        aux_total = jnp.zeros((), jnp.float32)
        caches: dict[str, Any] = {}
        loads: list = []

        for section, dense in (("prefix", True), ):
            if section in params:
                caches[section] = {}
                for key in sorted(params[section],
                                  key=lambda s: int(s.split("_")[0])):
                    kind = key.split("_", 1)[1]
                    x, a, c, l = _block_full(cfg, kind, params[section][key], x,
                                             pos0=0, dense=dense, enc_out=enc_out,
                                             causal=True, build_cache=build_cache)
                    aux_total = aux_total + a
                    if build_cache:
                        caches[section][key] = c
                        loads.append(l)

        if "stack" in params:
            def body(carry, lp):
                xx, aux = carry
                xx, a, c, l = self._super_full(lp, xx, pos0=0, enc_out=enc_out,
                                               build_cache=build_cache)
                return (xx, aux + a), ((c, l) if build_cache else None)

            # remat policy (REPRO_REMAT_POLICY): 'full' recomputes everything
            # in backward (min residency, max recompute); 'dots' saves matmul
            # outputs (the §Perf compute<->memory trade lever).
            import os as _os
            if _os.environ.get("REPRO_REMAT_POLICY", "full") == "dots":
                policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                body_fn = jax.checkpoint(body, policy=policy) if remat else body
            else:
                body_fn = jax.checkpoint(body) if remat else body

            # 2-level (recursive) checkpointing: REPRO_REMAT_GROUP=g saves
            # only every g-th residual during the forward scan (n/g group
            # boundaries + g per-layer saves inside the one group being
            # differentiated) — O(n/g + g) residency instead of O(n).
            # The enabler for 96-layer d=18432 training at 16 GB/chip.
            g = int(_os.environ.get("REPRO_REMAT_GROUP", "1"))
            n_super = jax.tree_util.tree_leaves(params["stack"])[0].shape[0]
            if remat and not build_cache and g > 1 and n_super % g == 0:
                grouped = jax.tree_util.tree_map(
                    lambda a: a.reshape((n_super // g, g) + a.shape[1:]),
                    params["stack"])

                def group_body(carry, glp):
                    cc, _ = jax.lax.scan(body_fn, carry, glp)
                    return cc, None

                (x, aux_total), _ = jax.lax.scan(
                    jax.checkpoint(group_body), (x, aux_total), grouped)
                stack_out = None
            else:
                (x, aux_total), stack_out = jax.lax.scan(
                    body_fn, (x, aux_total), params["stack"])
            if build_cache:
                caches["stack"], stack_loads = stack_out
                loads.append(stack_loads)

        if "tail" in params:
            caches["tail"] = {}
            for key in sorted(params["tail"], key=lambda s: int(s.split("_")[0])):
                kind = key.split("_", 1)[1]
                x, a, c, l = _block_full(cfg, kind, params["tail"][key], x,
                                         pos0=0, dense=False, enc_out=enc_out,
                                         causal=True, build_cache=build_cache)
                aux_total = aux_total + a
                if build_cache:
                    caches["tail"][key] = c
                    loads.append(l)

        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, aux_total, caches, _stack_loads(loads)

    # ----- public entry points ---------------------------------------------------
    def loss(self, params: dict, batch: dict, *, remat: bool = True):
        cfg = self.cfg
        x, labels, enc_out = self._inputs(params, batch)
        x, aux, _, _ = self._forward(params, x, enc_out=enc_out, remat=remat)
        head = params["embed"] if cfg.tie_embeddings else params["head"]
        ce, metrics = chunked_ce_loss(cfg, head, x, labels)
        metrics["aux_loss"] = aux
        return ce + aux, metrics

    def prefill(self, params: dict, batch: dict, *, max_len: int,
                remat: bool = True):
        """Forward + cache build.  Returns (cache, last-position logits).
        With experts, ``cache["expert_tokens"]`` holds the prefill's
        (token, expert) pairs per expert layer and held expert."""
        cfg = self.cfg
        x, _, enc_out = self._inputs(params, batch)
        b, s, _ = x.shape
        x, _, built, loads = self._forward(params, x, enc_out=enc_out,
                                           build_cache=True, remat=remat)
        cache = self._caches_to_decode(built, b, s, max_len)
        cache["pos"] = jnp.full((b,), s, jnp.int32)  # per-lane positions
        if loads is not None:
            cache["expert_tokens"] = loads
        if enc_out is not None:
            cache["enc_out"] = enc_out
        head = params["embed"] if cfg.tie_embeddings else params["head"]
        logits = (x[:, -1, :] @ head.astype(x.dtype).T).astype(jnp.float32)
        return cache, logits[:, : cfg.vocab_size]

    def _caches_to_decode(self, built: dict, b: int, s: int, max_len: int):
        cfg = self.cfg
        out: dict[str, Any] = {}
        for section in ("prefix", "tail"):
            if section in built:
                out[section] = {
                    key: _cache_from_prefill(cfg, key.split("_", 1)[1],
                                             built[section][key], b, s, max_len)
                    for key in built[section]}
        if "stack" in built:
            # vmap the conversion over the scan (leading) axis of every leaf
            def per_layer(subtree, key):
                kind = key.split("_", 1)[1]
                return jax.vmap(lambda bt: _cache_from_prefill(
                    cfg, kind, bt, b, s, max_len))(subtree)
            out["stack"] = {k: per_layer(built["stack"][k], k)
                            for k in built["stack"]}
        return out

    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        plan = cfg.layer_plan()
        out: dict[str, Any] = {"pos": jnp.zeros((batch,), jnp.int32)}
        if cfg.moe is not None:
            out["expert_tokens"] = jnp.zeros((cfg.n_moe_layers, cfg.moe.held),
                                             jnp.int32)
        if plan.prefix:
            out["prefix"] = {f"{i}_{k}": _init_block_cache(cfg, k, batch, max_len)
                             for i, k in enumerate(plan.prefix)}
        if plan.n_super:
            def one(kind):
                return _init_block_cache(cfg, kind, batch, max_len)
            stack = {f"{i}_{k}": jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (plan.n_super,) + a.shape),
                one(k)) for i, k in enumerate(plan.super_block)}
            out["stack"] = stack
        if plan.tail:
            out["tail"] = {f"{i}_{k}": _init_block_cache(cfg, k, batch, max_len)
                           for i, k in enumerate(plan.tail)}
        return out

    def decode_step(self, params: dict, cache: dict, tokens: jax.Array):
        """tokens: (B, 1) int32.  Returns (logits (B, V), new cache); with
        experts, the new cache's ``expert_tokens`` holds this step's
        (token, expert) pairs per expert layer and held expert."""
        cfg = self.cfg
        pos = cache["pos"]
        enc_out = cache.get("enc_out")
        x = embed_tokens(cfg, params["embed"], tokens)
        new_cache: dict[str, Any] = {"pos": pos + 1}
        loads: list = []
        if enc_out is not None:
            new_cache["enc_out"] = enc_out

        for section in ("prefix",):
            if section in params:
                new_cache[section] = {}
                for key in sorted(params[section],
                                  key=lambda s: int(s.split("_")[0])):
                    kind = key.split("_", 1)[1]
                    x, c, l = _block_decode(cfg, kind, params[section][key], x,
                                            cache[section][key], pos, dense=True,
                                            enc_out=enc_out)
                    new_cache[section][key] = c
                    loads.append(l)

        if "stack" in params:
            def body(xx, inp):
                lp, lc = inp
                ncs, ls = {}, []
                for key in sorted(lp.keys(), key=lambda s: int(s.split("_")[0])):
                    kind = key.split("_", 1)[1]
                    xx, nc, l = _block_decode(cfg, kind, lp[key], xx, lc[key],
                                              pos, dense=False, enc_out=enc_out)
                    ncs[key] = nc
                    ls.append(l)
                return xx, (ncs, _stack_loads(ls))

            x, (stack_cache, stack_loads) = jax.lax.scan(
                body, x, (params["stack"], cache["stack"]))
            new_cache["stack"] = stack_cache
            loads.append(stack_loads)

        if "tail" in params:
            new_cache["tail"] = {}
            for key in sorted(params["tail"], key=lambda s: int(s.split("_")[0])):
                kind = key.split("_", 1)[1]
                x, c, l = _block_decode(cfg, kind, params["tail"][key], x,
                                        cache["tail"][key], pos, dense=False,
                                        enc_out=enc_out)
                new_cache["tail"][key] = c
                loads.append(l)
        loads = _stack_loads(loads)
        if loads is not None:
            new_cache["expert_tokens"] = loads

        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"] if cfg.tie_embeddings else params["head"]
        logits = (x[:, 0, :] @ head.astype(x.dtype).T).astype(jnp.float32)
        return logits[:, : cfg.vocab_size], new_cache
