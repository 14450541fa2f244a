"""Architecture registry + input specs.

``get_config(arch)`` / ``get_smoke_config(arch)`` return the full and
reduced configs; ``input_specs(cfg, shape)`` returns ShapeDtypeStruct
stand-ins for every model input of a (config, shape) cell — weak-type
correct, shardable, zero allocation — the dry-run's only input source.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

from repro.configs.shapes import SHAPES, Shape, applicable, applicable_shapes
from repro.models.config import ModelConfig

__all__ = ["ARCHS", "get_config", "get_smoke_config", "input_specs",
           "SHAPES", "Shape", "applicable", "applicable_shapes"]

ARCHS: dict[str, str] = {
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "qwen2-72b": "qwen2_72b",
    "qwen2.5-32b": "qwen2_5_32b",
    "stablelm-1.6b": "stablelm_1_6b",
    "nemotron-4-340b": "nemotron_4_340b",
    "recurrentgemma-9b": "recurrentgemma_9b",
    "llava-next-34b": "llava_next_34b",
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
    "deepseek-v3-671b": "deepseek_v3_671b",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
    "xlstm-125m": "xlstm_125m",
}


def _module(arch: str):
    if arch not in ARCHS:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(ARCHS)}")
    return importlib.import_module(f"repro.configs.{ARCHS[arch]}")


def get_config(arch: str) -> ModelConfig:
    return _module(arch).CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    return _module(arch).SMOKE


def input_specs(cfg: ModelConfig, shape: str | Shape,
                *, with_labels: bool | None = None) -> dict:
    """ShapeDtypeStruct stand-ins for one (arch x shape) cell's step inputs.

    train  -> the ``loss``/train-step batch;
    prefill-> the prefill batch (no labels);
    decode -> the one-token batch (the cache comes from
              ``jax.eval_shape(model.init_cache, ...)``, not from here).
    """
    sh = SHAPES[shape] if isinstance(shape, str) else shape
    b, s = sh.global_batch, sh.seq_len
    i32 = jnp.int32
    act = cfg.activation_dtype
    if sh.kind == "decode":
        return {"tokens": jax.ShapeDtypeStruct((b, 1), i32)}
    labels = sh.kind == "train" if with_labels is None else with_labels
    out: dict = {}
    if cfg.is_encdec:
        out["frames"] = jax.ShapeDtypeStruct((b, s // 2, cfg.d_model), act)
        out["tokens"] = jax.ShapeDtypeStruct((b, s), i32)
        if labels:
            out["labels"] = jax.ShapeDtypeStruct((b, s), i32)
        return out
    s_text = s - (cfg.frontend_tokens if cfg.frontend == "vision" else 0)
    out["tokens"] = jax.ShapeDtypeStruct((b, s_text), i32)
    if cfg.frontend == "vision":
        out["patches"] = jax.ShapeDtypeStruct((b, cfg.frontend_tokens, cfg.d_model), act)
    if labels:
        out["labels"] = jax.ShapeDtypeStruct((b, s_text), i32)
    return out
