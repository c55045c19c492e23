"""What every reference forward shares: float32 ``HIGHEST`` matmuls and
their float8 control, RMSNorm, rotary embeddings, causal grouped-query
attention, padding, and the check of a weight tree's shapes.

The control: every matmul's two operands rounded to float8 e4m3 (per-row
absmax scaling for activations, per-column for weights), accumulated in
float32 — the step below the bfloat16 the configurations serve in.
Nothing here imports the program.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def round_fp8(x: jax.Array, axis: int) -> jax.Array:
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
        x = round_fp8(x, -1)
        w = round_fp8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rotate(x, cos, sin):
    """x [S, heads, hd] rotated by cos, sin [S, 1, hd/2], rotate-half
    convention."""
    hd = x.shape[-1]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def rope(x, theta):
    """x [S, heads, hd] at positions 0..S-1, plain rotary embeddings."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    return rotate(x, jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :])


def attention(q, k, v, fp8: bool):
    """Causal grouped-query attention of q [S, H, hd] over k, v
    [S, Hk, hd]; returns [S, H·hd]."""
    S, H, hd = q.shape
    Hk = k.shape[1]
    q = q.reshape(S, Hk, H // Hk, hd)
    if fp8:
        q, k = round_fp8(q, -1), round_fp8(k, -1)
    s = jnp.einsum("qkgd,tkd->kgqt", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    if fp8:
        p, v = round_fp8(p, -1), round_fp8(v, 0)
    o = jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HIGHEST)
    return o.reshape(S, H * hd)


def pad(tokens: np.ndarray, length: int) -> np.ndarray:
    """The sequence padded at the end to ``length``, so the programs
    compile once per cell: causality keeps padding out of every real
    position."""
    padded = np.zeros((length,), np.int32)
    padded[:len(tokens)] = tokens
    return padded


def check_shapes(params, expected: Dict[str, Tuple[int, ...]],
                 model_type: str) -> None:
    """Raises where the weight tree has a leaf the published keys do not
    give, lacks one they do, or has one of another shape; ``expected``
    maps each leaf's '/'-joined path to its shape."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    got = {"/".join(str(getattr(k, "key", k)) for k in path):
           tuple(leaf.shape) for path, leaf in flat}
    wrong = sorted(f"{n}: {got.get(n)} (published keys give "
                   f"{expected.get(n)})"
                   for n in set(got) | set(expected)
                   if got.get(n) != expected.get(n))
    if wrong:
        raise ValueError(f"model_type {model_type!r}: the weight tree "
                         f"disagrees with the published keys: "
                         + "; ".join(wrong))
