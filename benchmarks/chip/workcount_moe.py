"""The least work of a latent-attention, sparse-expert decoder (the
configuration file's ``model`` block with ``mla`` and ``moe``), from
shapes only.

These count what the model needs, not what today's implementation does,
so no share of a roofline or of a peak can pass 100%: the routed experts
are counted by the (token, held expert) pairs the program reports and the
held experts a decode step reached, and latent attention in the form its
cache allows.
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _sizes(cfg: dict):
    la, mo = cfg["mla"], cfg["moe"]
    return (la["kv_lora_rank"], la["qk_nope_head_dim"],
            la["qk_rope_head_dim"], la["v_head_dim"], mo)


def held_experts(cfg: dict) -> int:
    mo = cfg["moe"]
    return mo.get("n_held") or mo["n_routed"]


def moe_layers(cfg: dict) -> int:
    return cfg["n_layers"] - cfg["dense_prefix"]


def moe_params(cfg: dict) -> dict:
    """Parameters by part: ``attn`` (latent attention and the two norms of
    every block), ``dense_mlp`` (the dense-prefix MLPs), ``shared`` and
    ``router`` (with its selection bias) of every expert layer, ``expert``
    (one routed expert), ``held`` and ``routed`` (the experts held here
    and all of them, over the expert layers), ``head``, ``embed`` and
    ``final_norm``."""
    d, h = cfg["d_model"], cfg["n_heads"]
    r, nope, rope, vd, mo = _sizes(cfg)
    q_rank = cfg["mla"].get("q_lora_rank")
    q = d * h * (nope + rope) if q_rank is None \
        else d * q_rank + q_rank + q_rank * h * (nope + rope)
    attn = q + d * (r + rope) + r + r * h * nope + r * h * vd + h * vd * d
    n_moe = moe_layers(cfg)
    expert = 3 * d * mo["d_expert"]
    fs = (mo.get("d_shared") or mo["d_expert"]) * mo.get("n_shared", 0)
    router = d * mo["n_routed"] + (mo["n_routed"] if mo.get("selection_bias")
                                   else 0)
    vocab = cfg["padded_vocab"]
    return {"attn": cfg["n_layers"] * (attn + 2 * d),
            "dense_mlp": cfg["dense_prefix"] * 3 * d * cfg["d_ff"],
            "shared": n_moe * 3 * d * fs, "router": n_moe * router,
            "expert": expert, "held": n_moe * held_experts(cfg) * expert,
            "routed": n_moe * mo["n_routed"] * expert,
            "head": 0 if cfg["tie_embeddings"] else vocab * d,
            "embed": vocab * d, "final_norm": d}


def _dense_params(p: dict) -> int:
    """Parameters every token passes through, the routed experts aside."""
    return p["attn"] + p["dense_mlp"] + p["shared"] + p["router"]


def share_params(cfg: dict) -> int:
    """Parameters this chip stores: everything but the absent experts."""
    p = moe_params(cfg)
    return _dense_params(p) + p["held"] + p["head"] + p["embed"] \
        + p["final_norm"]


def uncut_params(cfg: dict) -> int:
    """Parameters of the whole model, every routed expert included."""
    p = moe_params(cfg)
    return _dense_params(p) + p["routed"] + p["head"] + p["embed"] \
        + p["final_norm"]


def latent_bytes_per_position(cfg: dict) -> int:
    """Bytes of one position's latent cache (compressed K/V and the rotary
    key) over all layers, in the activation type the cache holds."""
    r, _, rope, _, _ = _sizes(cfg)
    return cfg["n_layers"] * (r + rope) * BYTES[cfg["dtype"]]


def expert_bytes(cfg: dict) -> float:
    """Bytes of one routed expert's weights as stored."""
    return moe_params(cfg)["expert"] * BYTES[cfg["param_dtype"]]


def decode_step_bytes(cfg: dict, live_positions: int,
                      experts_reached: int) -> float:
    """The least bytes one decode step reads: every stored weight but the
    embedding table (a step reads one row a lane) and the routed experts,
    the ``experts_reached`` held experts (over all expert layers) that got
    a token, and the latent cache of the live positions."""
    p = moe_params(cfg)
    stored = _dense_params(p) + p["head"] + p["final_norm"]
    return stored * BYTES[cfg["param_dtype"]] \
        + experts_reached * expert_bytes(cfg) \
        + live_positions * latent_bytes_per_position(cfg)


def _attention_flops(cfg: dict, absorbed: bool) -> float:
    """Operations of one query position against one cached position, over
    all layers and heads: scores and weighted values, in the latent space
    (``absorbed``, as decode from the latent cache) or on up-projected K
    and V (as prefill)."""
    r, nope, rope, vd, _ = _sizes(cfg)
    per_head = 2.0 * (r + rope) + 2.0 * r if absorbed \
        else 2.0 * (nope + rope) + 2.0 * vd
    return cfg["n_layers"] * cfg["n_heads"] * per_head


def token_flops(cfg: dict, context: int, logits: bool,
                absorbed: bool) -> float:
    """Operations of one token attending to ``context`` positions, the
    routed experts aside: 2 per parameter it passes through (the
    up-projections of W_uk and W_uv are applied once a token in either
    form), the attention over its context, and 2 * d_model * vocab when
    its logits are computed."""
    flops = 2.0 * _dense_params(moe_params(cfg)) \
        + _attention_flops(cfg, absorbed) * context
    if logits:
        flops += 2.0 * cfg["d_model"] * cfg["vocab_size"]
    return flops


def prefill_flops(cfg: dict, n: int) -> float:
    """Operations of a prefill of ``n`` tokens, each attending to the
    positions up to its own, with the last one's logits; routed experts
    aside."""
    fixed = 2.0 * _dense_params(moe_params(cfg))
    return n * fixed + _attention_flops(cfg, False) * n * (n + 1) / 2 \
        + 2.0 * cfg["d_model"] * cfg["vocab_size"]


def expert_pair_flops(cfg: dict) -> float:
    """Operations of one (token, routed expert) pair."""
    return 2.0 * moe_params(cfg)["expert"]
