"""Plain reference of a latent-attention, sparse-expert decoder LM (the
DeepSeek-V3 block as Moonlight-16B-A3B configures it) in ``jax.numpy``,
for the serving cells.  It imports nothing of the program and takes none
of its arrays: it draws its own weights from the seed's key.

The equations are those the configuration file's ``model`` block states:
token embedding; per block a pre-norm RMSNorm, multi-head latent
attention and a second RMSNorm before the MLP, each added to the
residual; a final RMSNorm and an untied output head.

- Attention (``mla``, no q-LoRA): q = x W_q, split per head into a
  ``qk_nope_head_dim`` part and a ``qk_rope_head_dim`` part; the latent
  [c, k_r] = x W_dkv, c RMS-normed (``kv_norm``), k_r one rotary key
  shared by every head; K and V up-projected from c (W_uk, W_uv) for every
  position: the full, non-absorbed form.  Rotary on the rope parts, causal
  softmax at scale 1/sqrt(nope + rope), output W_o.
- The first ``dense_prefix`` blocks have a SwiGLU MLP of ``d_ff``.  The
  others an expert layer: router scores s = sigmoid(x W_r) (softmax where
  ``scoring`` says so) over all ``n_routed`` experts; the top ``top_k`` of
  s + b (b the selection bias, with ``selection_bias``) are chosen and
  weighted by s, normalised over the k, times ``routed_scale``.  Each
  held expert (``n_held`` from ``first_held``) is computed on every token
  and masked by that weight; the absent experts add nothing, as on a
  chip of an expert-parallel deployment.  The shared
  experts, one SwiGLU of ``n_shared * d_shared``, are added whole.

Departures from the published model: the weights are random; rotary
pairs dimension i with i + rope/2 (the halves of the rope slice), where
DeepSeek's checkpoint pairs interleaved dimensions, which is a fixed
permutation of the rope columns of W_q and W_dkv; and the expert MLP
names its two input projections ``we_in`` (under the SiLU) and
``we_gate``.

Weights follow the program's initialisation: one key per parameter,
split from the seed's key in the sorted order of the parameter paths
(embed, final_norm, head, then each dense-prefix block, then the stacked
blocks; within a block attn, ln1, ln2, mlp, each by sorted name),
N(0, 0.02^2) for the embedding, the head, the router and its bias,
N(0, 1/fan_in) for the matrices, ones for the norm scales; each is then
rounded to ``param_dtype`` as the program stores it, except the
selection bias, kept in float32.  The computation is float32.

``precision`` is ``"highest"`` for the reference (float32 products at
full precision), or a lower type the control casts every product's
operands to (``float8_e4m3fn`` below the configuration's bfloat16).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _leaves(cfg: dict) -> list[tuple[str, tuple, str]]:
    """(path, shape, init) of every parameter, in the order the
    program's keys are split."""
    d, h = cfg["d_model"], cfg["n_heads"]
    la, mo = cfg["mla"], cfg["moe"]
    r, nope, rope = la["kv_lora_rank"], la["qk_nope_head_dim"], \
        la["qk_rope_head_dim"]
    vd = la["v_head_dim"]
    if la.get("q_lora_rank") is not None:
        raise ValueError("the reference takes a direct query projection")
    vp = cfg["padded_vocab"]
    n_prefix = cfg["dense_prefix"]
    n_stack = cfg["n_layers"] - n_prefix
    held = mo.get("n_held") or mo["n_routed"]
    fe, e = mo["d_expert"], mo["n_routed"]
    fs = (mo.get("d_shared") or fe) * max(mo.get("n_shared", 0), 1)

    def block(pre, lead, dense):
        out = [(f"{pre}/attn/kv_norm", lead + (r,), "ones"),
               (f"{pre}/attn/w_dkv", lead + (d, r + rope), "fan_in"),
               (f"{pre}/attn/w_o", lead + (h * vd, d), "fan_in"),
               (f"{pre}/attn/w_q", lead + (d, h * (nope + rope)), "fan_in"),
               (f"{pre}/attn/w_uk", lead + (r, h * nope), "fan_in"),
               (f"{pre}/attn/w_uv", lead + (r, h * vd), "fan_in"),
               (f"{pre}/ln1", lead + (d,), "ones"),
               (f"{pre}/ln2", lead + (d,), "ones")]
        if dense:
            f = cfg["d_ff"]
            return out + [(f"{pre}/mlp/w_gate", lead + (d, f), "fan_in"),
                          (f"{pre}/mlp/w_in", lead + (d, f), "fan_in"),
                          (f"{pre}/mlp/w_out", lead + (f, d), "fan_in")]
        out.append((f"{pre}/mlp/router", lead + (d, e), "normal02"))
        if mo.get("selection_bias"):
            out.append((f"{pre}/mlp/router_bias", lead + (e,), "bias"))
        out += [(f"{pre}/mlp/we_gate", lead + (held, d, fe), "fan_in"),
                (f"{pre}/mlp/we_in", lead + (held, d, fe), "fan_in"),
                (f"{pre}/mlp/we_out", lead + (held, fe, d), "fan_in")]
        if mo.get("n_shared"):
            out += [(f"{pre}/mlp/ws_gate", lead + (d, fs), "fan_in"),
                    (f"{pre}/mlp/ws_in", lead + (d, fs), "fan_in"),
                    (f"{pre}/mlp/ws_out", lead + (fs, d), "fan_in")]
        return out

    leaves = [("embed", (vp, d), "normal02"), ("final_norm", (d,), "ones")]
    if not cfg["tie_embeddings"]:
        leaves.append(("head", (vp, d), "normal02"))
    for name in sorted(f"{i}_attn" for i in range(n_prefix)):
        leaves += block(f"prefix/{name}", (), True)
    if n_stack:
        leaves += block("stack/0_attn", (n_stack,), False)
    return leaves


def init_weights(cfg: dict, key) -> dict:
    """The weights by path, drawn from ``key`` (call under ``jax.jit``)."""
    leaves = _leaves(cfg)
    keys = jax.random.split(key, len(leaves))
    dt = jnp.dtype(cfg["param_dtype"])
    out = {}
    for (path, shape, init), k in zip(leaves, keys):
        if init == "ones":
            out[path] = jnp.ones(shape, dt)
        elif init in ("normal02", "bias"):
            x = 0.02 * jax.random.normal(k, shape, jnp.float32)
            out[path] = x if init == "bias" else x.astype(dt)
        else:
            std = 1.0 / math.sqrt(shape[-2])
            out[path] = (std * jax.random.normal(k, shape, jnp.float32)
                         ).astype(dt)
    return out


def _mm(a, b, precision):
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    lo = jnp.dtype(precision)
    return jnp.matmul(a.astype(lo), b.astype(lo),
                      preferred_element_type=jnp.float32)


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (S, H, r): dimension i rotated against i + r/2 by position."""
    s, half = x.shape[0], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(x, w_in, w_gate, w_out, precision):
    g = jax.nn.silu(_mm(x, w_in, precision)) * _mm(x, w_gate, precision)
    return _mm(g, w_out, precision)


def _attention(cfg, p, a, precision):
    la = cfg["mla"]
    s, h = a.shape[0], cfg["n_heads"]
    r, nope, rope = la["kv_lora_rank"], la["qk_nope_head_dim"], \
        la["qk_rope_head_dim"]
    theta = cfg["rope_theta"]
    q = _mm(a, p["attn/w_q"], precision).reshape(s, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    lat = _mm(a, p["attn/w_dkv"], precision)
    c = _norm(lat[:, :r], p["attn/kv_norm"], cfg["norm_eps"])
    k_r = _rope(lat[:, None, r:], theta)
    k_n = _mm(c, p["attn/w_uk"], precision).reshape(s, h, nope)
    v = _mm(c, p["attn/w_uv"], precision).reshape(s, h, la["v_head_dim"])
    k = jnp.concatenate([k_n, jnp.broadcast_to(k_r, (s, h, rope))], -1)
    sc = _mm(q.transpose(1, 0, 2), k.transpose(1, 2, 0), precision)
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal, sc / math.sqrt(nope + rope), -jnp.inf)
    o = _mm(jax.nn.softmax(sc, axis=-1), v.transpose(1, 0, 2), precision)
    return _mm(o.transpose(1, 0, 2).reshape(s, -1), p["attn/w_o"], precision)


def routing(cfg, p, x, precision="highest"):
    """Each token's weight on each of the ``n_routed`` experts, zero where
    the router did not choose it: (S, n_routed)."""
    mo = cfg["moe"]
    logits = _mm(x, p["mlp/router"], precision)
    if mo.get("scoring", "softmax") == "sigmoid":
        s = jax.nn.sigmoid(logits)
    else:
        s = jax.nn.softmax(logits, axis=-1)
    choice = s + p["mlp/router_bias"] if mo.get("selection_bias") else s
    _, idx = jax.lax.top_k(choice, mo["top_k"])
    w = jnp.take_along_axis(s, idx, -1)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    w = w * mo.get("routed_scale", 1.0)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros(s.shape, jnp.float32).at[rows, idx].set(w)


def expert_layer(cfg, p, x, precision="highest", shared=True):
    """The held experts' part of the expert layer on ``x`` (S, d), each
    held expert computed on every token and masked by its routing weight,
    plus the shared experts when ``shared``."""
    mo = cfg["moe"]
    held = mo.get("n_held") or mo["n_routed"]
    first = mo.get("first_held", 0)
    gate = routing(cfg, p, x, precision)[:, first:first + held]
    y = _swiglu(x[None], p["mlp/we_in"], p["mlp/we_gate"], p["mlp/we_out"],
                precision)                                   # (held, S, d)
    out = jnp.einsum("se,esd->sd", gate, y,
                     precision=jax.lax.Precision.HIGHEST)
    if shared and mo.get("n_shared"):
        out = out + _swiglu(x, p["mlp/ws_in"], p["mlp/ws_gate"],
                            p["mlp/ws_out"], precision)
    return out


def _block(cfg, p, x, dense, precision):
    eps = cfg["norm_eps"]
    x = x + _attention(cfg, p, _norm(x, p["ln1"], eps), precision)
    m = _norm(x, p["ln2"], eps)
    if dense:
        return x + _swiglu(m, p["mlp/w_in"], p["mlp/w_gate"], p["mlp/w_out"],
                           precision)
    return x + expert_layer(cfg, p, m, precision)


def _layer(w: dict, pre: str) -> dict:
    """One block's weights, keyed below ``pre``, in float32."""
    return {k[len(pre) + 1:]: v.astype(jnp.float32) for k, v in w.items()
            if k.startswith(pre + "/")}


def hidden(cfg: dict, w: dict, tokens, precision="highest"):
    """Final-norm hidden states of every position of ``tokens`` (S,)."""
    x = w["embed"][tokens].astype(jnp.float32)
    for name in sorted(f"{i}_attn" for i in range(cfg["dense_prefix"])):
        x = _block(cfg, _layer(w, f"prefix/{name}"), x, True, precision)
    if cfg["n_layers"] > cfg["dense_prefix"]:
        stack = {k[len("stack/0_attn/"):]: v for k, v in w.items()
                 if k.startswith("stack/0_attn/")}

        def body(x, p):
            p = {k: v.astype(jnp.float32) for k, v in p.items()}
            return _block(cfg, p, x, False, precision), None
        x, _ = jax.lax.scan(body, x, stack)
    return _norm(x, w["final_norm"].astype(jnp.float32), cfg["norm_eps"])


def logits_at(cfg: dict, w: dict, tokens, positions, precision="highest"):
    """Logits (len(positions), vocab) predicting the token after each of
    ``positions``."""
    x = hidden(cfg, w, tokens, precision)[positions]
    head = w["embed" if cfg["tie_embeddings"] else "head"].astype(jnp.float32)
    return _mm(x, head.T, precision)[:, :cfg["vocab_size"]]
