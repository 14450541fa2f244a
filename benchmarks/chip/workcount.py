"""The least work of each operation the benchmark times, from shapes only.

These count what the operation needs, not what today's implementation
does: a dense-DFT kernel and an FFT read the same work, so no share of a
roofline or of a peak can pass 100%.
"""

from __future__ import annotations

import math


def fft_frame(h: int, w: int) -> dict:
    """One ``fft`` frame of ``h`` x ``w``: read the frame and write the
    intensity in float32, and (5/2) N log2 N operations of a complex FFT
    of N = h*w points."""
    n = h * w
    return {"bytes": 8 * n, "flops": 2.5 * n * math.log2(n)}


def least_seconds(work: dict, peaks: dict) -> float:
    """The larger of bytes over HBM bandwidth and operations over the
    bf16 peak."""
    return max(work["bytes"] / peaks["hbm_bytes_per_s"],
               work["flops"] / peaks["bf16_flops_per_s"])


def lm_params(cfg: dict) -> dict:
    """Parameters of a dense decoder (attention with ``n_heads`` query and
    ``n_kv_heads`` key/value heads, a gated MLP, two norms a block), split
    into the blocks, the output head and the embedding table."""
    d, f = cfg["d_model"], cfg["d_ff"]
    hd = cfg.get("head_dim") or d // cfg["n_heads"]
    attn = d * cfg["n_heads"] * hd * 2 + d * cfg["n_kv_heads"] * hd * 2
    mlp = 3 * d * f
    block = attn + mlp + 2 * d
    vocab = cfg["padded_vocab"]
    return {"blocks": cfg["n_layers"] * block,
            "head": 0 if cfg["tie_embeddings"] else vocab * d,
            "embed": vocab * d, "final_norm": d}


def lm_token_flops(cfg: dict, context: int, logits: bool) -> float:
    """Operations to process one token that attends to ``context``
    positions: 2 per block parameter, 4 * layers * kv width * context for
    the scores and the weighted values, and 2 * d_model * vocab when the
    token's logits are computed."""
    d = cfg["d_model"]
    hd = cfg.get("head_dim") or d // cfg["n_heads"]
    p = lm_params(cfg)
    flops = 2.0 * p["blocks"] + 4.0 * cfg["n_layers"] * cfg["n_heads"] * hd \
        * context
    if logits:
        flops += 2.0 * d * cfg["vocab_size"]
    return flops


def lm_kv_bytes_per_position(cfg: dict) -> int:
    """Bytes of K and V of one position, over all layers, in the
    activation type the cache holds."""
    hd = cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]
    act = {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["dtype"]]
    return cfg["n_layers"] * 2 * cfg["n_kv_heads"] * hd * act


def lm_decode_step_bytes(cfg: dict, live_positions: int) -> float:
    """The least bytes one decode step reads: the block and head
    parameters as the configuration stores them (the embedding table is
    left out: a step reads one row a lane), and K and V of the live
    positions only."""
    p = lm_params(cfg)
    w = {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["param_dtype"]]
    return (p["blocks"] + p["head"] + p["final_norm"]) * w \
        + live_positions * lm_kv_bytes_per_position(cfg)
