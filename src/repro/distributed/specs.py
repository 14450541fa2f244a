"""PartitionSpec trees for everything that isn't a parameter:
batches, decode caches, and optimizer states.

These are the dry-run's in/out shardings; without them the 2.5 TB
Nemotron decode cache would be lowered replicated per chip.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.params import ParamSpec  # noqa: F401  (doc reference)

__all__ = ["batch_pspecs", "cache_pspecs", "opt_pspecs", "DP"]

DP = ("pod", "data")  # logical data-parallel axes (filtered per mesh)


def _dp(mesh_axes: tuple[str, ...]):
    got = tuple(a for a in DP if a in mesh_axes)
    return got if got else None


def batch_pspecs(batch_like: Any, mesh_axes: tuple[str, ...],
                 dp_total: int = 32) -> Any:
    """Shard dim0 (global batch) over the data axes; rest replicated.
    Leaves whose batch dim doesn't divide the dp extent (long_500k: B=1)
    stay replicated."""
    dp = _dp(mesh_axes)

    def one(x):
        lead = dp if (dp is not None and x.shape
                      and x.shape[0] % dp_total == 0) else None
        return P(lead, *([None] * (len(x.shape) - 1)))
    return jax.tree_util.tree_map(one, batch_like)


def _shard_last(dim: int, tp: int):
    return "model" if dim % tp == 0 else None


def cache_pspecs(cfg: ModelConfig, cache_like: Any,
                 mesh_axes: tuple[str, ...], tp: int,
                 batch: int) -> Any:
    """Decode-cache shardings, keyed on leaf shapes.

    GQA k/v (B, Hkv, S, hd): batch over data axes; heads over ``model``
    when divisible, else head_dim (128/192/256 all divide 16).  MLA latent
    (B, S, D_lat): D_lat over model.  Recurrent states: width over model
    when divisible.  Scan-stacked leaves get a leading None.
    When the global batch doesn't cover the dp axes (long_500k B=1), batch
    stays replicated.
    """
    dp_axes = _dp(mesh_axes)
    # conservative: shard batch only when it divides the largest dp extent
    # we deploy (2 pods x 16 = 32); long_500k (B=1) stays replicated.
    dp = dp_axes if (dp_axes is not None and batch % 32 == 0) else None

    def leaf_spec(path, x) -> P:
        keys = [getattr(pp, "key", "") for pp in path]
        stacked = "stack" in keys
        shape = x.shape[1:] if stacked else x.shape
        name = keys[-1] if keys else ""
        if name in ("k", "v") and len(shape) == 4:
            b, hk, s, hd = shape
            if hk % tp == 0:
                spec = (dp, "model", None, None)
            elif hd % tp == 0:
                spec = (dp, None, None, "model")
            else:
                spec = (dp, None, None, None)
        elif name == "latent" and len(shape) == 3:
            spec = (dp, None, _shard_last(shape[-1], tp))
        elif name in ("slot_pos", "pos", "expert_tokens"):
            spec = tuple([None] * len(shape))
        elif name == "enc_out":
            spec = (dp,) + (None,) * (len(shape) - 1)
        elif name in ("c",) and len(shape) == 4:   # mLSTM matrix memory
            spec = (dp, None, None, None)
        elif len(shape) >= 2:
            spec = (dp,) + (None,) * (len(shape) - 2) + (
                _shard_last(shape[-1], tp),)
        elif len(shape) == 1:
            spec = (dp,) if dp is not None and shape[0] % 32 == 0 else (None,)
        else:
            spec = ()
        if stacked:
            spec = (None,) + spec
        return P(*spec)

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_like)
    return jax.tree_util.tree_unflatten(
        treedef, [leaf_spec(p, x) for p, x in flat])


def opt_pspecs(opt_like: Any, params_pspecs: Any) -> Any:
    """Optimizer-state shardings derived from parameter shardings.

    adamw m/v mirror the param spec exactly; adafactor vr/vc take the spec
    minus the reduced dim.  Works structurally: opt leaves live under the
    same param path with an extra {'m'|'v'|'vr'|'vc'} level.
    """
    def build(opt_node, pspec_node):
        if isinstance(opt_node, dict):
            out = {}
            for k, v in opt_node.items():
                if k == "vr" and not isinstance(v, dict):
                    out[k] = P(*pspec_node[:-1])
                elif k == "vc" and not isinstance(v, dict):
                    out[k] = P(*(tuple(pspec_node[:-2]) + (pspec_node[-1],)))
                elif k in ("m", "v") and not isinstance(v, dict):
                    out[k] = pspec_node
                else:
                    out[k] = build(v, pspec_node[k] if isinstance(pspec_node, dict)
                                   and k in pspec_node else pspec_node)
            return out
        return pspec_node

    return build(opt_like, params_pspecs)
