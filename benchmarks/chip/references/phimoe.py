"""Plain float32 reference forward of Phi-3.5-MoE's block (``model_type``
``phimoe``).

Written from the published equations (arXiv:2404.14219; transformers'
``PhimoeForCausalLM``): LayerNorm with a bias (eps ``rms_norm_eps``);
grouped-query causal attention whose q, k, v and o projections carry
biases (``attention_bias``), with LongRoPE rotary embeddings (each
inverse frequency divided by its factor, cos and sin multiplied by the
mscale; the short factors and mscale while the sequence is at most
``original_max_position_embeddings`` long, the long ones past it);
SparseMixer top-2 routing as the published ``sparsemixer`` infers
(below); SwiGLU experts summed with those weights; the final LayerNorm
and an LM head with a bias (``lm_head_bias``). It reads the
configuration's published keys, not the program's view of them, imports
nothing from the program and takes nothing the program made: the weights
come from ``weights.make_params`` again, from the seed, and every leaf's
shape is checked against what the keys give.

One sequence at a time, one layer at a time, one expert at a time, all at
``HIGHEST`` matmul precision in float32; every expert runs over every
token and the router's weights select. ``fp8`` gives the control
(``common.py``).
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.references.common import (attention, check_shapes,
                                               matmul, pad, rotate)


def expected_shapes(keys: Dict) -> Dict[str, tuple]:
    """Every weight leaf and its shape, from the published keys."""
    D, V = keys["hidden_size"], keys["vocab_size"]
    H, Hk = keys["num_attention_heads"], keys["num_key_value_heads"]
    hd = D // H
    L, E = keys["num_hidden_layers"], keys["num_local_experts"]
    F = keys["intermediate_size"]
    s = "scan/s0/"
    shapes = {"embed": (V, D), "final_norm/scale": (D,),
              "final_norm/bias": (D,),
              s + "attn/wq": (L, D, H * hd), s + "attn/wk": (L, D, Hk * hd),
              s + "attn/wv": (L, D, Hk * hd), s + "attn/wo": (L, H * hd, D),
              s + "moe/router": (L, D, E), s + "moe/w1": (L, E, D, F),
              s + "moe/w3": (L, E, D, F), s + "moe/w2": (L, E, F, D)}
    for ln in ("ln1", "ln2"):
        shapes[f"{s}{ln}/scale"] = shapes[f"{s}{ln}/bias"] = (L, D)
    if keys.get("attention_bias", False):
        shapes.update({s + "attn/bq": (L, H * hd), s + "attn/bk": (L, Hk * hd),
                       s + "attn/bv": (L, Hk * hd), s + "attn/bo": (L, D)})
    if not keys.get("tie_word_embeddings", False):
        shapes["lm_head"] = (V, D)
    if keys.get("lm_head_bias", False):
        shapes["logits/bias"] = (V,)
    return shapes


def layernorm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def longrope(x, theta, factors, mscale):
    """x [S, heads, hd] at positions 0..S-1; ``factors`` [hd/2]."""
    S, _, hd = x.shape
    inv = 1.0 / (factors * theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32)
                                     / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    return rotate(x, (jnp.cos(ang) * mscale)[:, None, :],
                  (jnp.sin(ang) * mscale)[:, None, :])


def sparsemixer(scores, eps):
    """The published ``sparsemixer`` at inference, top-2, on router
    logits [S, E]: each pick is the argmax (the first pick set to -inf
    for the second); logits whose (pick - s) / max(|s|, pick) exceeds
    2·eps are masked to -inf, and the pick's weight is the softmax of the
    rest at the pick. Returns the [S, E] gate, zero off the picks."""
    S, E = scores.shape
    rows = jnp.arange(S)
    gate = jnp.zeros((S, E), jnp.float32)
    masked = scores
    for _ in range(2):
        top = masked.max(-1, keepdims=True)
        pick = jnp.argmax(masked, -1)
        factor = jnp.maximum(jnp.abs(scores), top)
        far = (top - scores) / factor > 2 * eps
        w = jax.nn.softmax(jnp.where(far, -jnp.inf, masked), -1)[rows, pick]
        gate = gate.at[rows, pick].set(w)
        masked = masked.at[rows, pick].set(-jnp.inf)
    return gate


@functools.partial(jax.jit, static_argnames=("shape", "fp8"))
def layer(x, scan: Dict, li, factors, shape: tuple, fp8: bool):
    """Layer ``li`` of the stacked layer weights ``scan`` on x [S, D]
    float32, each expert's matrices read where they lie (no copy of the
    layer's table). ``shape`` = (heads, kv_heads, head_dim, eps, theta,
    mscale, router eps)."""
    H, Hk, hd, eps, theta, mscale, router_eps = shape
    S = x.shape[0]
    lw = jax.tree.map(lambda t: t[li], {k: scan[k] for k in
                                         ("ln1", "ln2", "attn")})
    a = lw["attn"]

    def proj(h, w, b):
        y = matmul(h, a[w], fp8)
        return y + a[b] if b in a else y

    h = layernorm(x, lw["ln1"], eps)
    q = longrope(proj(h, "wq", "bq").reshape(S, H, hd), theta, factors,
                 mscale)
    k = longrope(proj(h, "wk", "bk").reshape(S, Hk, hd), theta, factors,
                 mscale)
    v = proj(h, "wv", "bv").reshape(S, Hk, hd)
    o = matmul(attention(q, k, v, fp8), a["wo"], fp8)
    x = x + (o + a["bo"] if "bo" in a else o)

    m = scan["moe"]
    h2 = layernorm(x, lw["ln2"], eps)
    gate = sparsemixer(matmul(h2, m["router"][li], fp8), router_eps)

    def expert(y, e):
        w1, w3, w2 = m["w1"][li, e], m["w3"][li, e], m["w2"][li, e]
        f = jax.nn.silu(matmul(h2, w1, fp8)) * matmul(h2, w3, fp8)
        return y + gate[:, e, None] * matmul(f, w2, fp8), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(gate.shape[1]))
    return x + y


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def head(x, final_norm, lm_head, bias, eps: float, fp8: bool):
    return matmul(layernorm(x, final_norm, eps), lm_head.T, fp8) + bias


def logits(params: Dict, keys: Dict, tokens: np.ndarray, length: int,
           fp8: bool = False) -> jax.Array:
    """Logits [len(tokens), V] float32 of one sequence, computed over the
    sequence padded to ``length``."""
    check_shapes(params, expected_shapes(keys), "phimoe")
    if keys["num_experts_per_tok"] != 2:
        raise ValueError("model_type 'phimoe': SparseMixer is published "
                         "for top-2")
    rs = keys["rope_scaling"]
    regime = "long" if length > rs["original_max_position_embeddings"] \
        else "short"
    factors = jnp.asarray(rs[f"{regime}_factor"], jnp.float32)
    x = params["embed"][jnp.asarray(pad(tokens, length))].astype(jnp.float32)
    scan = params["scan"]["s0"]
    H = keys["num_attention_heads"]
    eps = float(keys["rms_norm_eps"])
    shape = (H, keys["num_key_value_heads"], keys["hidden_size"] // H, eps,
             float(keys["rope_theta"]), float(rs[f"{regime}_mscale"]),
             float(keys["router_jitter_noise"]))
    for li in range(keys["num_hidden_layers"]):
        x = layer(x, scan, li, factors, shape, fp8)
    table = params.get("lm_head", params["embed"])
    bias = params["logits"]["bias"] if "logits" in params \
        else jnp.zeros((table.shape[0],), jnp.float32)
    return head(x, params["final_norm"], table, bias, eps,
                fp8)[:len(tokens)]
