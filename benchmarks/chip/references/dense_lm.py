"""Plain reference of a dense decoder LM in ``jax.numpy``, for the serving
cells.  It imports nothing of the program and takes none of its arrays:
it draws its own weights from the seed's key.

The equations are those the configuration file states for the program's
model: token embedding; per block a pre-norm RMSNorm (eps, a scale, no
bias), multi-head attention with no biases, rotary embedding on the
first ``rope_pct`` of each head's dims (the two halves of that slice
rotated against each other), causal softmax attention, a second RMSNorm
and a SwiGLU MLP, each added to the residual; a final RMSNorm and an
untied output head.  The weights follow the program's documented
initialisation: one key per parameter, split from the seed's key in the
order of the sorted parameter names, N(0, 0.02^2) for the embedding and
the head, N(0, 1/fan_in) for the matrices, ones for the norm scales.

``precision`` is ``"highest"`` for the reference (float32 products at
full precision), or a lower type the control casts every product's
operands to (``float8_e4m3fn`` below the configuration's bfloat16).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _dims(cfg: dict):
    d, h, hk = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd = cfg.get("head_dim") or d // h
    return d, h, hk, hd


def init_weights(cfg: dict, key) -> dict:
    """The weights, drawn from ``key`` (call under ``jax.jit``)."""
    d, h, hk, hd = _dims(cfg)
    n, f = cfg["n_layers"], cfg["d_ff"]
    vp = -(-cfg["vocab_size"] // cfg["vocab_pad_multiple"]) \
        * cfg["vocab_pad_multiple"]
    # (name, shape, init) in the order of the sorted parameter names
    leaves = [("embed", (vp, d), "normal02"), ("final_norm", (d,), "ones"),
              ("head", (vp, d), "normal02"),
              ("w_k", (n, d, hk * hd), "fan_in"),
              ("w_o", (n, h * hd, d), "fan_in"),
              ("w_q", (n, d, h * hd), "fan_in"),
              ("w_v", (n, d, hk * hd), "fan_in"),
              ("ln1", (n, d), "ones"), ("ln2", (n, d), "ones"),
              ("w_gate", (n, d, f), "fan_in"), ("w_in", (n, d, f), "fan_in"),
              ("w_out", (n, f, d), "fan_in")]
    keys = jax.random.split(key, len(leaves))
    out = {}
    for (name, shape, init), k in zip(leaves, keys):
        if init == "ones":
            out[name] = jnp.ones(shape, jnp.float32)
        elif init == "normal02":
            out[name] = 0.02 * jax.random.normal(k, shape, jnp.float32)
        else:
            std = 1.0 / math.sqrt(shape[-2])
            out[name] = std * jax.random.normal(k, shape, jnp.float32)
    return out


def _mm(a, b, precision):
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    lo = jnp.dtype(precision)
    return jnp.matmul(a.astype(lo), b.astype(lo),
                      preferred_element_type=jnp.float32)


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pct, theta):
    s, hd = x.shape[0], x.shape[-1]
    rot = int(hd * pct)
    rot -= rot % 2
    half = rot // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., rot:]], axis=-1)


def hidden(cfg: dict, w: dict, tokens, precision="highest"):
    """Final-norm hidden states of every position of ``tokens`` (S,)."""
    d, h, hk, hd = _dims(cfg)
    eps, pct, theta = cfg["norm_eps"], cfg["rope_pct"], cfg["rope_theta"]
    s = tokens.shape[0]
    x = w["embed"][tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def block(x, p):
        a = _norm(x, p["ln1"], eps)
        q = _rope(_mm(a, p["w_q"], precision).reshape(s, h, hd), pct, theta)
        k = _rope(_mm(a, p["w_k"], precision).reshape(s, hk, hd), pct, theta)
        v = _mm(a, p["w_v"], precision).reshape(s, hk, hd)
        k = jnp.repeat(k, h // hk, axis=1)
        v = jnp.repeat(v, h // hk, axis=1)
        sc = _mm(q.transpose(1, 0, 2), k.transpose(1, 2, 0), precision)
        sc = jnp.where(causal, sc / math.sqrt(hd), -jnp.inf)
        o = _mm(jax.nn.softmax(sc, axis=-1), v.transpose(1, 0, 2), precision)
        x = x + _mm(o.transpose(1, 0, 2).reshape(s, h * hd), p["w_o"],
                    precision)
        m = _norm(x, p["ln2"], eps)
        g = jax.nn.silu(_mm(m, p["w_in"], precision)) \
            * _mm(m, p["w_gate"], precision)
        return x + _mm(g, p["w_out"], precision), None

    layers = {k: w[k] for k in ("w_q", "w_k", "w_v", "w_o", "ln1", "ln2",
                                "w_in", "w_gate", "w_out")}
    x, _ = jax.lax.scan(block, x, layers)
    return _norm(x, w["final_norm"], eps)


def logits_at(cfg: dict, w: dict, tokens, positions, precision="highest"):
    """Logits (len(positions), vocab) predicting the token after each of
    ``positions``."""
    x = hidden(cfg, w, tokens, precision)[positions]
    return _mm(x, w["head"].T, precision)[:, :cfg["vocab_size"]]
