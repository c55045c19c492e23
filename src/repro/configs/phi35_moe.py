"""phi35-moe [moe] — the paper's second evaluation model, Phi-3.5-MoE's
own block (``microsoft/Phi-3.5-MoE-instruct``, ``model_type`` ``phimoe``).

32L d_model=4096 32H (GQA kv=8, head 128) vocab=32064; MoE 16 experts
top-2, per-expert d_ff=6400 (157 MB/expert bf16). LayerNorm with bias
(eps 1e-5); q, k, v and o projections with biases; an LM head with a
bias; LongRoPE rotary scaling (theta 1e4, 4096 original positions);
SparseMixer top-2 routing (eps 0.01), weights not renormalised; SwiGLU
experts. [arXiv:2404.14219]

The 64 short LongRoPE factors and the short mscale are in the published
``config.json``, which is not in this repository: ``SHORT`` is an
assumed ramp (not constant, so a program that ignores the factors is
seen to), and the mscale is the one Phi-3 derives for a 32-fold
extension, sqrt(1 + ln 32 / ln 4096). The long factors apply only past
4096 positions, which the program refuses (``ModelConfig.check_capacity``).
"""
import math

from repro.config import LongRoPE, ModelConfig, MoEConfig, register

ORIGINAL_POSITIONS = 4096
SHORT = tuple(1.0 + i / 63 for i in range(64))
MSCALE = math.sqrt(1 + math.log(131072 / ORIGINAL_POSITIONS)
                   / math.log(ORIGINAL_POSITIONS))


@register("phi35-moe")
def config() -> ModelConfig:
    return ModelConfig(
        name="phi35-moe",
        family="moe",
        num_layers=32,
        d_model=4096,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        d_ff=0,
        vocab_size=32064,
        moe=MoEConfig(num_experts=16, top_k=2, d_ff=6400,
                      router="sparsemixer", router_jitter=0.01),
        qkv_bias=True,
        attn_out_bias=True,
        lm_head_bias=True,
        norm="layer",
        norm_eps=1e-5,
        rope_theta=10000.0,
        rope_scaling=LongRoPE(
            short_factor=SHORT, short_mscale=MSCALE,
            original_max_position_embeddings=ORIGINAL_POSITIONS),
        max_seq_len=131072,
    )
