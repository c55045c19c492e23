"""Mixtral's published keys (Hugging Face ``config.json``, ``model_type``
``mixtral``) as the program's ModelConfig.

Every key of a configuration file of this type is in ``READ`` (mapped to
a field, or checked against what the program does) or in ``SKIPPED``
(no effect on inference, with the reason); ``spec.model_config`` refuses
any other key. The program serves Mixtral's block: RMSNorm, no biases,
plain rotary embeddings, a softmax router renormalised over its top-k
picks, SwiGLU experts.
"""
from repro.config import ModelConfig, MoEConfig

READ = frozenset((
    "model_type", "hidden_size", "intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "num_hidden_layers",
    "num_local_experts", "num_experts_per_tok", "vocab_size", "rope_theta",
    "rms_norm_eps", "tie_word_embeddings", "max_position_embeddings",
    "torch_dtype", "hidden_act", "sliding_window"))

# only keys a committed configuration carries; a file that brings another
# brings its entry here
SKIPPED = {
    "router_aux_loss_coef": "weight of the load-balance loss: training",
}


def _refuse(key, value, wants):
    raise ValueError(f"model_type 'mixtral': {key}={value!r}; the program "
                     f"serves {wants}")


def model_config(name: str, keys: dict) -> ModelConfig:
    if keys.get("hidden_act", "silu") != "silu":
        _refuse("hidden_act", keys["hidden_act"], "SwiGLU experts (silu)")
    window = keys.get("sliding_window")
    if window is not None and window < keys["max_position_embeddings"]:
        _refuse("sliding_window", window,
                "global attention only (null, or at least "
                "max_position_embeddings)")
    heads = keys["num_attention_heads"]
    return ModelConfig(
        name=name, family="moe",
        num_layers=keys["num_hidden_layers"],
        d_model=keys["hidden_size"], num_heads=heads,
        num_kv_heads=keys["num_key_value_heads"],
        head_dim=keys.get("head_dim") or keys["hidden_size"] // heads,
        d_ff=0, vocab_size=keys["vocab_size"],
        moe=MoEConfig(num_experts=keys["num_local_experts"],
                      top_k=keys["num_experts_per_tok"],
                      d_ff=keys["intermediate_size"]),
        rope_theta=float(keys["rope_theta"]),
        norm_eps=float(keys["rms_norm_eps"]),
        tie_embeddings=bool(keys.get("tie_word_embeddings", False)),
        max_seq_len=keys["max_position_embeddings"],
        dtype=keys["torch_dtype"])
