"""The one traffic generator.  A mix is a JSON file of parameters under
``traffic/``; this module turns it and a seed into what the drivers send.

Two shapes of traffic:

``closed_bursts``  one client sends ``burst`` fresh frames, flushes, waits
                   for all of them, then sends the next burst.
``closed_chat``    ``clients`` closed-loop clients; each sends its next
                   request when its last one completes.  Prompt and
                   output lengths are log-normal quantiles (clipped,
                   prompts rounded to a multiple).  Every ``block``
                   consecutive requests hold the same ``block`` sizes, in
                   an order drawn from the seed: every seed sends the same
                   work, in another order.  The seed draws the token ids.

Frames are made on the device from the seed and an index, never
repeated within a run: ``gratings``, a few sinusoidal gratings (whole
cycles across the aperture, so each lights a pair of Fourier-plane spots)
over a mean level with uniform pixel noise.
"""

from __future__ import annotations

import functools
import math
import statistics

import numpy as np

from benchmarks.chip.common import BenchError, np_rng, seed_words


def _lengths(spec: dict, n: int) -> np.ndarray:
    """The log-normal's quantiles at (i + 0.5) / n, clipped and rounded to
    the nearest ``multiple``."""
    inv = statistics.NormalDist().inv_cdf
    z = np.array([inv((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    x = np.clip(x, spec["min"], spec["max"])
    m = spec.get("multiple", 1)
    return (np.round(x / m) * m).astype(np.int64)


def chat_requests(mix: dict, seed: int) -> list[tuple[int, int]]:
    """``(prompt_len, max_new_tokens)`` of the first ``pool`` requests a
    ``closed_chat`` mix sends, in the order the clients take them."""
    block = mix["block"]
    prompts = _lengths(mix["prompt"], block)
    outs = _lengths(mix["output"], block)
    rng = np_rng(seed, 2)
    out: list[tuple[int, int]] = []
    for _ in range(-(-mix["pool"] // block)):
        out += zip(rng.permutation(prompts).tolist(),
                   rng.permutation(outs).tolist())
    return out[:mix["pool"]]


def prompt_tokens(seed: int, index: int, length: int, vocab: int) -> list[int]:
    """Token ids of request ``index``'s prompt."""
    return np_rng(seed, 3, index).integers(0, vocab, length).tolist()


# --- frames --------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _frame_fn(kind: str, n: int, h: int, w: int, params: tuple):
    import jax
    import jax.numpy as jnp

    if kind != "gratings":
        raise BenchError(f"unknown frame kind {kind!r}")
    p = dict(params)
    k_n = int(p["count"])
    fmax = int(p["max_cycles"])

    def bench_frames(lo, hi, index):
        key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        key = jax.random.fold_in(jax.random.fold_in(key, 4), index)
        kf, ka, kp, kn = jax.random.split(key, 4)
        fy, fx = jax.random.randint(kf, (2, n, k_n, 1, 1), -fmax, fmax + 1)
        amp = jax.random.uniform(ka, (n, k_n, 1, 1), jnp.float32,
                                 p["amp_min"], p["amp_max"])
        phase = jax.random.uniform(kp, (n, k_n, 1, 1), jnp.float32,
                                   0.0, 2.0 * math.pi)
        y = jnp.arange(h, dtype=jnp.float32)[:, None] / h
        x = jnp.arange(w, dtype=jnp.float32)[None, :] / w
        ang = 2.0 * math.pi * (fy * y + fx * x) + phase
        waves = jnp.sum(amp * jnp.cos(ang), axis=1)
        noise = jax.random.uniform(kn, (n, h, w), jnp.float32, -0.5, 0.5)
        stack = jnp.clip(p["mean"] + waves + p["noise"] * noise, 0.0, 1.0)
        return tuple(stack[i] for i in range(n))
    return jax.jit(bench_frames)


def make_frames(spec: dict, seed: int, index: int, n: int,
                shape: tuple[int, int]):
    """``n`` frames of ``shape`` from ``seed`` and ``index``, made on the
    device in one call: a tuple of ``n`` arrays."""
    params = tuple(sorted((k, v) for k, v in spec.items() if k != "kind"))
    fn = _frame_fn(spec["kind"], n, int(shape[0]), int(shape[1]), params)
    lo, hi = seed_words(seed)
    return fn(np.uint32(lo), np.uint32(hi), np.uint32(index))
