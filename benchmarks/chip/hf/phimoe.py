"""Phi-3.5-MoE's published keys (Hugging Face ``config.json``,
``model_type`` ``phimoe``) as the program's ModelConfig.

Every key of a configuration file of this type is in ``READ`` (mapped to
a field, or checked against what the program does) or in ``SKIPPED``
(no effect on inference, with the reason); ``spec.model_config`` refuses
any other key. The program serves Phi-3.5-MoE's block: LayerNorm with a
bias (eps ``rms_norm_eps``), q, k, v and o projections with biases
(``attention_bias``), an LM head with a bias (``lm_head_bias``), LongRoPE
rotary scaling (``rope_scaling`` of type ``longrope``), SparseMixer top-2
routing with eps ``router_jitter_noise``, SwiGLU experts.
"""
from repro.config import LongRoPE, ModelConfig, MoEConfig

READ = frozenset((
    "model_type", "hidden_size", "intermediate_size", "num_attention_heads",
    "num_key_value_heads", "num_hidden_layers", "num_local_experts",
    "num_experts_per_tok", "vocab_size", "rope_theta", "rope_scaling",
    "original_max_position_embeddings", "rms_norm_eps",
    "tie_word_embeddings", "max_position_embeddings", "torch_dtype",
    "hidden_act", "sliding_window", "attention_bias", "lm_head_bias",
    "router_jitter_noise"))

# the published keys the block cannot be built without: a file that
# lacks one is refused by name, not with a KeyError
NEEDED = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "num_hidden_layers", "num_local_experts",
          "num_experts_per_tok", "vocab_size", "rope_theta", "rope_scaling",
          "rms_norm_eps", "max_position_embeddings", "torch_dtype",
          "router_jitter_noise")

SKIPPED = {
    "input_jitter_noise": "multiplicative noise on the router's input: "
                          "training only",
    "router_aux_loss_coef": "weight of the load-balance loss: training",
    "attention_dropout": "dropout: training only",
    "hidden_dropout": "dropout: training only",
    "output_router_logits": "returns the router's logits for the "
                            "auxiliary loss: training",
    "initializer_range": "scale of the initial weights: the benchmark "
                         "draws its own (weights.py)",
    "bos_token_id": "a tokenizer id: the traffic draws token ids",
    "eos_token_id": "a tokenizer id: serving stops at the traffic's "
                    "output length",
}


def _refuse(key, value, wants):
    raise ValueError(f"model_type 'phimoe': {key}={value!r}; the program "
                     f"serves {wants}")


def _longrope(keys: dict, head_dim: int) -> LongRoPE:
    rs = keys.get("rope_scaling")
    if not isinstance(rs, dict) or rs.get("type") != "longrope":
        _refuse("rope_scaling", rs, "LongRoPE (type 'longrope')")
    original = rs["original_max_position_embeddings"]
    if keys.get("original_max_position_embeddings", original) != original:
        _refuse("original_max_position_embeddings",
                keys["original_max_position_embeddings"],
                f"the rope_scaling's own ({original})")
    for kind in ("short_factor", "long_factor"):
        if len(rs[kind]) != head_dim // 2:
            _refuse(f"rope_scaling.{kind}", f"{len(rs[kind])} factors",
                    f"one factor per rotary frequency ({head_dim // 2})")
    # the long factors and mscale serve only past ``original`` positions,
    # which the engine refuses: checked here, not carried
    if not isinstance(rs.get("long_mscale"), (int, float)):
        _refuse("rope_scaling.long_mscale", rs.get("long_mscale"),
                "a number")
    return LongRoPE(short_factor=tuple(float(f) for f in rs["short_factor"]),
                    short_mscale=float(rs["short_mscale"]),
                    original_max_position_embeddings=int(original))


def model_config(name: str, keys: dict) -> ModelConfig:
    missing = [k for k in NEEDED if k not in keys]
    if missing:
        raise ValueError(f"model_type 'phimoe': published key(s) "
                         f"{', '.join(missing)} missing; "
                         f"benchmarks/chip/hf/phimoe.py needs them")
    if keys.get("hidden_act", "silu") != "silu":
        _refuse("hidden_act", keys["hidden_act"], "SwiGLU experts (silu)")
    window = keys.get("sliding_window")
    if window is not None and window < keys["max_position_embeddings"]:
        _refuse("sliding_window", window,
                "global attention only (null, or at least "
                "max_position_embeddings)")
    if keys["num_experts_per_tok"] != 2:
        _refuse("num_experts_per_tok", keys["num_experts_per_tok"],
                "SparseMixer's published top-2")
    heads = keys["num_attention_heads"]
    head_dim = keys["hidden_size"] // heads
    bias = bool(keys.get("attention_bias", False))
    return ModelConfig(
        name=name, family="moe",
        num_layers=keys["num_hidden_layers"],
        d_model=keys["hidden_size"], num_heads=heads,
        num_kv_heads=keys["num_key_value_heads"], head_dim=head_dim,
        d_ff=0, vocab_size=keys["vocab_size"],
        moe=MoEConfig(num_experts=keys["num_local_experts"],
                      top_k=keys["num_experts_per_tok"],
                      d_ff=keys["intermediate_size"], router="sparsemixer",
                      router_jitter=float(keys["router_jitter_noise"])),
        qkv_bias=bias, attn_out_bias=bias,
        lm_head_bias=bool(keys.get("lm_head_bias", False)),
        norm="layer",
        rope_theta=float(keys["rope_theta"]),
        rope_scaling=_longrope(keys, head_dim),
        norm_eps=float(keys["rms_norm_eps"]),
        tie_embeddings=bool(keys.get("tie_word_embeddings", False)),
        max_seq_len=keys["max_position_embeddings"],
        dtype=keys["torch_dtype"])
