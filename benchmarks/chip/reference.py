"""Plain float32 reference forward of a decoder-only MoE transformer.

Written from the published equations of the configurations' block
(Mixtral, arXiv:2401.04088; Phi-3.5-MoE, arXiv:2404.14219, with the
departures its configuration file lists): RMSNorm, grouped-query causal
attention with rotary embeddings (rotate-half convention), a softmax
router whose top-k probabilities are renormalised over the k picks, and
SwiGLU experts summed with those weights. It imports nothing from the
program and takes nothing the program made: the weights come from
``weights.make_params`` again, from the seed.

One sequence at a time, one layer at a time, one expert at a time, all at
``HIGHEST`` matmul precision in float32; every expert runs over every
token and the router's weights select (plain, and the same work whatever
the routing). Sequences are padded at the end to a fixed length, so the
programs compile once per cell: causality keeps padding out of every
real position.

``Precision`` also gives the control: every matmul's two operands rounded
to float8 e4m3 (per-row absmax scaling for activations, per-column for
weights), accumulated in float32 — the step below the bfloat16 the
configurations serve in.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8 e4m3 with absmax scaling along ``axis``, back in
    float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, E4M3_MAX / amax, 1.0)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q / scale


def matmul(x: jax.Array, w: jax.Array, fp8: bool) -> jax.Array:
    """x [..., K] @ w [K, N] in float32; with ``fp8`` both operands are
    first rounded to e4m3 (activations per row, weights per column)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if fp8:
        x = _fp8(x, -1)
        w = _fp8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x [S, heads, hd] at positions 0..S-1, rotate-half convention."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


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
    q = q.reshape(S, Hk, H // Hk, hd)
    if fp8:
        q, k = _fp8(q, -1), _fp8(k, -1)
    s = jnp.einsum("qkgd,tkd->kgqt", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    if fp8:
        p, v = _fp8(p, -1), _fp8(v, 0)
    o = jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HIGHEST)
    x = x + matmul(o.reshape(S, H * hd), a["wo"], fp8)

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


def logits(params: Dict, model_cfg, tokens: np.ndarray, length: int,
           fp8: bool = False) -> jax.Array:
    """Logits [len(tokens), V] float32 of one sequence, computed over the
    sequence padded to ``length``."""
    n = len(tokens)
    padded = np.zeros((length,), np.int32)
    padded[:n] = tokens
    x = params["embed"][jnp.asarray(padded)].astype(jnp.float32)
    scan = params["scan"]["s0"]
    shape = (model_cfg.num_heads, model_cfg.num_kv_heads, model_cfg.head_dim,
             model_cfg.moe.top_k, model_cfg.norm_eps, model_cfg.rope_theta)
    for li in range(model_cfg.num_layers):
        lw = jax.tree.map(lambda a: a[li], scan)
        x = layer(x, lw, shape, fp8)
    table = params.get("lm_head", params["embed"])
    return head(x, params["final_norm"], table, model_cfg.norm_eps,
                fp8)[:n]
