"""Plain float32 reference forward of Mixtral's block (``model_type``
``mixtral``).

Written from the published equations (arXiv:2401.04088; transformers'
``MixtralForCausalLM``): RMSNorm, grouped-query causal attention with
rotary embeddings (rotate-half convention), a softmax router whose top-k
probabilities are renormalised over the k picks, and SwiGLU experts
summed with those weights. It reads the configuration's published keys,
not the program's view of them, imports nothing from the program and
takes nothing the program made: the weights come from
``weights.make_params`` again, from the seed, and every leaf's shape is
checked against what the keys give.

One sequence at a time, one layer at a time, one expert at a time, all at
``HIGHEST`` matmul precision in float32; every expert runs over every
token and the router's weights select (plain, and the same work whatever
the routing). ``fp8`` gives the control (``common.py``).
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.references.common import (attention, check_shapes,
                                               matmul, pad, rmsnorm, rope)


def expected_shapes(keys: Dict) -> Dict[str, tuple]:
    """Every weight leaf and its shape, from the published keys."""
    D, V = keys["hidden_size"], keys["vocab_size"]
    H, Hk = keys["num_attention_heads"], keys["num_key_value_heads"]
    hd = keys.get("head_dim") or D // H
    L, E = keys["num_hidden_layers"], keys["num_local_experts"]
    F = keys["intermediate_size"]
    s = "scan/s0/"
    shapes = {"embed": (V, D), "final_norm": (D,),
              s + "ln1": (L, D), s + "ln2": (L, D),
              s + "attn/wq": (L, D, H * hd), s + "attn/wk": (L, D, Hk * hd),
              s + "attn/wv": (L, D, Hk * hd), s + "attn/wo": (L, H * hd, D),
              s + "moe/router": (L, D, E), s + "moe/w1": (L, E, D, F),
              s + "moe/w3": (L, E, D, F), s + "moe/w2": (L, E, F, D)}
    if not keys.get("tie_word_embeddings", False):
        shapes["lm_head"] = (V, D)
    return shapes


@functools.partial(jax.jit, static_argnames=("shape", "fp8"))
def layer(x, lw: Dict, shape: tuple, fp8: bool):
    """One transformer layer on x [S, D] float32. ``shape`` =
    (heads, kv_heads, head_dim, top_k, eps, theta)."""
    H, Hk, hd, K, eps, theta = shape
    S = x.shape[0]
    a = lw["attn"]
    h = rmsnorm(x, lw["ln1"], eps)
    q = rope(matmul(h, a["wq"], fp8).reshape(S, H, hd), theta)
    k = rope(matmul(h, a["wk"], fp8).reshape(S, Hk, hd), theta)
    v = matmul(h, a["wv"], fp8).reshape(S, Hk, hd)
    x = x + matmul(attention(q, k, v, fp8), a["wo"], fp8)

    m = lw["moe"]
    h2 = rmsnorm(x, lw["ln2"], eps)
    probs = jax.nn.softmax(matmul(h2, m["router"], fp8), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, K)
    top_w = top_w / top_w.sum(-1, keepdims=True)
    E = probs.shape[-1]
    gate = jnp.zeros((S, E), jnp.float32).at[
        jnp.arange(S)[:, None], top_i].set(top_w)

    def expert(y, e):
        w1, w3, w2 = m["w1"][e], m["w3"][e], m["w2"][e]
        f = jax.nn.silu(matmul(h2, w1, fp8)) * matmul(h2, w3, fp8)
        return y + gate[:, e, None] * matmul(f, w2, fp8), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(E))
    return x + y


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def head(x, final_norm, lm_head, eps: float, fp8: bool):
    return matmul(rmsnorm(x, final_norm, eps), lm_head.T, fp8)


def logits(params: Dict, keys: Dict, tokens: np.ndarray, length: int,
           fp8: bool = False) -> jax.Array:
    """Logits [len(tokens), V] float32 of one sequence, computed over the
    sequence padded to ``length``."""
    check_shapes(params, expected_shapes(keys), "mixtral")
    x = params["embed"][jnp.asarray(pad(tokens, length))].astype(jnp.float32)
    scan = params["scan"]["s0"]
    H = keys["num_attention_heads"]
    eps = float(keys["rms_norm_eps"])
    shape = (H, keys["num_key_value_heads"],
             keys.get("head_dim") or keys["hidden_size"] // H,
             keys["num_experts_per_tok"], eps, float(keys["rope_theta"]))
    for li in range(keys["num_hidden_layers"]):
        lw = jax.tree.map(lambda a: a[li], scan)
        x = layer(x, lw, shape, fp8)
    table = params.get("lm_head", params["embed"])
    return head(x, params["final_norm"], table, eps, fp8)[:len(tokens)]
