"""Shared neural-net layers: RMSNorm, LayerNorm, RoPE/M-RoPE (LongRoPE
scaling), SwiGLU, embeddings.

All functions are pure; parameters are plain dict pytrees created by the
matching ``*_params`` initializer. Compute dtype is bf16, normalization and
softmax statistics in fp32.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

Params = Dict[str, jax.Array]


def _dense_init(key, shape, in_axis: int = 0, dtype=jnp.bfloat16):
    fan_in = shape[in_axis]
    return (jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(dtype)


# --------------------------------------------------------------------------
# RMSNorm
# --------------------------------------------------------------------------

def rmsnorm_params(d: int) -> jax.Array:
    return jnp.ones((d,), jnp.float32)


def rmsnorm(w: jax.Array, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)) * w).astype(x.dtype)


# --------------------------------------------------------------------------
# LayerNorm (a scale and a bias) and the norm of a config
# --------------------------------------------------------------------------

def layernorm(p: Params, x: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    xc = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return (xc * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
            ).astype(x.dtype)


def norm_params(d: int, kind: str):
    """A norm's weights: RMSNorm's scale, or LayerNorm's scale and bias
    (``ModelConfig.norm``)."""
    if kind == "layer":
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}
    return rmsnorm_params(d)


def norm(p, x: jax.Array, cfg) -> jax.Array:
    """The config's norm (``cfg.norm``, eps ``cfg.norm_eps``): the one
    helper the backbone and the serving engine's decode step both call."""
    if cfg.norm == "layer":
        return layernorm(p, x, cfg.norm_eps)
    return rmsnorm(p, x, cfg.norm_eps)


# --------------------------------------------------------------------------
# RoPE (standard + LongRoPE scaling + multimodal 3-D M-RoPE)
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float, factors=None) -> jax.Array:
    """Inverse frequencies [hd/2]; LongRoPE's per-frequency ``factors``
    divide them."""
    base = theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    if factors is not None:
        base = jnp.asarray(factors, jnp.float32) * base
    return 1.0 / base


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling=None) -> jax.Array:
    """x: [..., S, H, hd]; positions: broadcastable to [..., S] (int).
    ``scaling`` (a ``LongRoPE``) divides each frequency by its short
    factor and multiplies cos and sin by the short mscale: the regime of
    every capacity the program serves (``ModelConfig.check_capacity``)."""
    hd = x.shape[-1]
    factors = None if scaling is None else scaling.short_factor
    freqs = rope_freqs(hd, theta, factors)                         # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs      # [..., S, hd/2]
    cos = jnp.cos(angles)[..., None, :]                            # [..., S, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    if scaling is not None:
        cos, sin = cos * scaling.short_mscale, sin * scaling.short_mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def mrope_sections(head_dim: int):
    """Qwen2-VL uses (16, 24, 24) for hd=128, i.e. h/w get 3/8 of hd/2 each."""
    half = head_dim // 2
    hw = int(round(0.375 * half))
    return (half - 2 * hw, hw, hw)


def apply_mrope(x: jax.Array, positions3: jax.Array, theta: float,
                sections=None) -> jax.Array:
    """Qwen2-VL multimodal RoPE.

    x: [B, S, H, hd]; positions3: [3, B, S] (temporal/height/width ids).
    Frequency channels are partitioned into three sections, each rotated by
    its own position stream. For pure text all three streams coincide.
    """
    hd = x.shape[-1]
    if sections is None:
        sections = mrope_sections(hd)
    assert sum(sections) == hd // 2, (sections, hd)
    freqs = rope_freqs(hd, theta)                                   # [hd/2]
    # angles per stream: [3, B, S, hd/2]
    angles = positions3[..., None].astype(jnp.float32) * freqs
    # select stream per frequency-channel section
    sel = jnp.repeat(jnp.arange(3), jnp.array(sections),
                     total_repeat_length=hd // 2)                   # [hd/2]
    angles = jnp.take_along_axis(
        jnp.moveaxis(angles, 0, -1), sel[None, None, :, None], axis=-1)[..., 0]
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# SwiGLU dense FFN
# --------------------------------------------------------------------------

def ffn_params(key, d_model: int, d_ff: int) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w1": _dense_init(k1, (d_model, d_ff)),   # gate
        "w3": _dense_init(k2, (d_model, d_ff)),   # up
        "w2": _dense_init(k3, (d_ff, d_model)),   # down
    }


def ffn_apply(p: Params, x: jax.Array) -> jax.Array:
    from repro.sharding import constrain
    h = jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])
    spec = (("pod", "data"),) + (None,) * (h.ndim - 2) + ("model",)
    h = constrain(h, *spec)
    return h @ p["w2"]


# --------------------------------------------------------------------------
# Embedding
# --------------------------------------------------------------------------

def embed_params(key, vocab: int, d_model: int) -> jax.Array:
    scale = d_model ** -0.5
    return (jax.random.normal(key, (vocab, d_model), jnp.float32) * scale
            ).astype(jnp.bfloat16)


def embed_lookup(table: jax.Array, tokens: jax.Array) -> jax.Array:
    return jnp.take(table, tokens, axis=0)


def logits_from_embed(table: jax.Array, x: jax.Array,
                      softcap: float = 0.0) -> jax.Array:
    lg = jnp.einsum("bsd,vd->bsv", x, table)
    if softcap > 0:
        lg = softcap * jnp.tanh(lg.astype(jnp.float32) / softcap)
    return lg
