"""The decode step writes the stacked cache in place: one position a layer
into the buffer the layer scan carries.  It must serve exactly what the
per-layer step served, where each layer's cache was sliced out of the
stack, stepped and restacked; and with the cache donated, the compiled
step must update it where it lies, copying none of it."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import LM, init_params
from repro.models.layers import embed_tokens, rms_norm
from repro.models.model import _block_decode, _stack_loads

LANES, STEPS = 4, 4


def _order(section: dict) -> list:
    return sorted(section, key=lambda s: int(s.split("_")[0]))


def _reference_step(model: LM, params: dict, cache: dict, tokens):
    """The per-layer decode step: every section's layers stepped by
    ``_block_decode`` on their own cache, the scanned stack's per-layer
    caches sliced out by the scan and restacked from its outputs."""
    cfg = model.cfg
    pos, enc_out = cache["pos"], cache.get("enc_out")
    x = embed_tokens(cfg, params["embed"], tokens)
    new: dict = {"pos": pos + 1}
    loads: list = []
    if enc_out is not None:
        new["enc_out"] = enc_out

    def unrolled(section, dense):
        new[section] = {}
        nonlocal x
        for key in _order(params[section]):
            x, new[section][key], l = _block_decode(
                cfg, key.split("_", 1)[1], params[section][key], x,
                cache[section][key], pos, dense=dense, enc_out=enc_out)
            loads.append(l)

    if "prefix" in params:
        unrolled("prefix", True)
    if "stack" in params:
        def body(xx, inp):
            lp, lc = inp
            ncs, ls = {}, []
            for key in _order(lp):
                xx, ncs[key], l = _block_decode(
                    cfg, key.split("_", 1)[1], lp[key], xx, lc[key], pos,
                    dense=False, enc_out=enc_out)
                ls.append(l)
            return xx, (ncs, _stack_loads(ls))

        x, (new["stack"], stack_loads) = jax.lax.scan(
            body, x, (params["stack"], cache["stack"]))
        loads.append(stack_loads)
    if "tail" in params:
        unrolled("tail", False)
    loads = _stack_loads(loads)
    if loads is not None:
        new["expert_tokens"] = loads
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = (x[:, 0, :] @ head.astype(x.dtype).T).astype(jnp.float32)
    return logits[:, : cfg.vocab_size], new


def _lanes_at_different_depths(model: LM, params: dict, max_len: int,
                               lengths: list[int]):
    """A ``LANES``-lane cache whose lane ``i`` holds a prefilled prompt of
    ``lengths[i]`` tokens."""
    cache = model.init_cache(LANES, max_len)
    prefill = jax.jit(lambda p, t: model.prefill(
        p, {"tokens": t}, max_len=max_len))
    for lane, n in enumerate(lengths):
        toks = jax.random.randint(jax.random.PRNGKey(lane), (1, n), 0,
                                  model.cfg.vocab_size)
        one, _ = prefill(params, toks)

        def put(dst, src):
            for d in range(dst.ndim):
                if dst.shape[d] == LANES and src.shape[d] == 1:
                    idx = [slice(None)] * dst.ndim
                    idx[d] = slice(lane, lane + 1)
                    return dst.at[tuple(idx)].set(src.astype(dst.dtype))
            return dst
        cache = jax.tree_util.tree_map(put, cache, one)
    return cache


CASES = {
    # arch: (prompt lengths of the four lanes, cache length)
    "stablelm-1.6b": ([3, 9, 14, 20], 32),
    "moonlight-16b-a3b": ([2, 7, 11, 16], 24),
    # window 8: lanes past it wrap the ring buffer during the steps
    "recurrentgemma-9b": ([4, 6, 9, 15], 32),
    "xlstm-125m": ([3, 5, 8, 12], 24),
    "deepseek-v3-671b": ([2, 6, 10, 13], 24),
}


@pytest.mark.parametrize("arch", sorted(CASES))
def test_decode_step_matches_the_per_layer_step_bit_for_bit(arch):
    cfg = get_smoke_config(arch)
    lengths, max_len = CASES[arch]
    model = LM(cfg)
    params = init_params(cfg, jax.random.PRNGKey(0))
    if arch == "recurrentgemma-9b":
        assert cfg.local_window and max(lengths) + STEPS > cfg.local_window
    cache = _lanes_at_different_depths(model, params, max_len, lengths)
    got_step = jax.jit(model.decode_step)
    want_step = jax.jit(lambda p, c, t: _reference_step(model, p, c, t))
    got, want = cache, cache
    for k in range(STEPS):
        toks = jax.random.randint(jax.random.PRNGKey(100 + k), (LANES, 1), 0,
                                  cfg.vocab_size)
        lg, got = got_step(params, got, toks)
        lw, want = want_step(params, want, toks)
        np.testing.assert_array_equal(np.asarray(lg), np.asarray(lw))
        assert jax.tree_util.tree_structure(got) == \
            jax.tree_util.tree_structure(want)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert list(np.asarray(got["pos"])) == [n + STEPS for n in lengths]


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "moonlight-16b-a3b"])
def test_donated_decode_step_updates_the_stacked_cache_in_place(
        arch, copy_bytes):
    cfg = get_smoke_config(arch)
    model = LM(cfg)
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = model.init_cache(LANES, 256)
    tokens = jnp.zeros((LANES, 1), jnp.int32)
    step = jax.jit(model.decode_step, donate_argnums=(1,), keep_unused=True)
    hlo = step.lower(params, cache, tokens).compile().as_text()

    # every stacked leaf is an input that an output aliases
    args = jax.tree_util.tree_flatten_with_path((params, cache, tokens))[0]
    stacked = {n for n, (path, _) in enumerate(args)
               if path[0].idx == 1 and path[1].key == "stack"}
    aliased = {int(n) for n in re.findall(r"\((\d+), \{\}, may-alias\)",
                                          hlo.split("\n", 1)[0])}
    assert stacked and stacked <= aliased, (stacked, aliased)
    stack = cache["stack"]
    leaves = jax.tree_util.tree_leaves(stack)
    # copies of the stack, or of one layer of it
    shapes = [a.shape for a in leaves] + [a.shape[1:] for a in leaves]
    assert copy_bytes(hlo, shapes) < 0.1 * sum(a.nbytes for a in leaves)

    step(params, cache, tokens)
    assert all(a.is_deleted() for a in jax.tree_util.tree_leaves(stack))
    with pytest.raises((RuntimeError, ValueError), match="deleted|donated"):
        step(params, cache, tokens)
