"""Operations and bytes the algorithm needs, from a configuration's shapes.

These are the yardstick of every roofline and utilization metric. They
count what the mathematics requires — never the padded groups, capacity
slack or weight copies of the implementation being measured — so a change
that removes waste reads against the same numbers. A multiply-add is two
operations; weights and activations are bfloat16 (2 bytes).

``m`` is the program's ModelConfig (``spec.model_config``).
"""
from __future__ import annotations

BF16 = 2


def dense_flops_per_token_layer(m) -> int:
    """Projections, router and the top-k experts of one token in one
    layer (attention scores excluded)."""
    D, hd = m.d_model, m.head_dim
    H, Hk = m.num_heads, m.num_kv_heads
    E, K, F = m.moe.num_experts, m.moe.top_k, m.moe.d_ff
    qkv = 2 * D * (H + 2 * Hk) * hd
    out = 2 * H * hd * D
    router = 2 * D * E
    experts = K * 3 * 2 * D * F
    return qkv + out + router + experts


def attention_flops(m, queries_keys: int) -> int:
    """Scores and weighted values for ``queries_keys`` (query, key)
    pairs in one layer: 2·hd for q·k and 2·hd for p·v, per query head."""
    return 4 * m.num_heads * m.head_dim * queries_keys


def lm_head_flops(m) -> int:
    return 2 * m.d_model * m.vocab_size


def decode_token_flops(m, context: int) -> int:
    """Model operations of one decoded token that attends ``context``
    keys (itself included), LM head included."""
    per_layer = dense_flops_per_token_layer(m) + attention_flops(m, context)
    return m.num_layers * per_layer + lm_head_flops(m)


def expert_bytes(m) -> int:
    """One expert's three matrices."""
    return 3 * m.d_model * m.moe.d_ff * BF16


def decode_gmm(m, unique_experts: int, rows: int) -> tuple:
    """(operations, bytes) the grouped expert FFN of one decode step
    needs in one layer: each unique expert's weights read once, and
    ``rows`` token-expert assignments computed."""
    flops = rows * 3 * 2 * m.d_model * m.moe.d_ff
    act = rows * (2 * m.d_model) * BF16          # rows in and rows out
    return flops, unique_experts * expert_bytes(m) + act


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
