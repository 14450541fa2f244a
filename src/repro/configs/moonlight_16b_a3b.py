"""moonlight-16b-a3b [moe]: 27L d_model=2048 16H, MLA with no q-LoRA
(kv_rank=512, nope=128, rope=64, v=128), first layer dense (d_ff=11264),
then 64 routed experts top-6 + 2 shared (expert d_ff=1408), sigmoid
router with a selection bias (noaux_tc, one group), top-k weights
normalised and scaled by 2.446, rope_theta=5e4, vocab=163840, untied,
bf16 [hf:moonshotai/Moonlight-16B-A3B, model_type deepseek_v3].

``CONFIG`` holds every expert; a chip of an expert-parallel deployment
holds ``n_held`` of them from ``first_held``."""
import dataclasses
from repro.models.config import ModelConfig, MLAConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=11264,
    vocab_size=163840, dense_prefix=1, rope_theta=50_000.0, norm_eps=1e-5,
    mla=MLAConfig(q_lora_rank=None, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(n_routed=64, top_k=6, d_expert=1408, n_shared=2,
                  d_shared=1408, shard_mode="ep", scoring="sigmoid",
                  selection_bias=True, routed_scale=2.446,
                  capacity_factor=None),
    param_dtype="bfloat16", logit_chunks=16,
)

SMOKE = dataclasses.replace(
    CONFIG, n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
    vocab_size=500, vocab_pad_multiple=64, param_dtype="float32",
    logit_chunks=2,
    mla=MLAConfig(q_lora_rank=None, kv_lora_rank=16, qk_nope_head_dim=8,
                  qk_rope_head_dim=4, v_head_dim=8),
    moe=dataclasses.replace(CONFIG.moe, n_routed=8, top_k=2, d_expert=32,
                            d_shared=32),
)
