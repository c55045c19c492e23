"""Jitted public wrappers for the grouped-MoE kernels.

`moe_ffn` runs the full grouped SwiGLU expert FFN on the [E, C, D]
dispatch buffer: fused gate kernel + down-projection gmm. All dims are
padded to 128 multiples here (MXU tile), so callers never think about
tiling. Interpret mode only where :func:`repro.kernels.interpret_mode`
says so (a CPU backend chosen with ``JAX_PLATFORMS=cpu``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import interpret_mode
from .moe_gmm import gmm, swiglu_gmm


def _pad128(x: jax.Array, *axes: int) -> jax.Array:
    widths = [(0, 0)] * x.ndim
    needed = False
    for ax in axes:
        pad = (-x.shape[ax]) % 128
        widths[ax] = (0, pad)
        needed = needed or pad
    return jnp.pad(x, widths) if needed else x


@jax.jit
def moe_ffn(x: jax.Array, w1: jax.Array, w3: jax.Array,
            w2: jax.Array) -> jax.Array:
    """Grouped SwiGLU FFN: x [E, C, D] -> [E, C, D]."""
    E, C, D = x.shape
    xp = _pad128(x, 1, 2)
    h = swiglu_gmm(xp, _pad128(w1, 1, 2), _pad128(w3, 1, 2),
                   interpret=interpret_mode())
    y = gmm(h, _pad128(w2, 1, 2), interpret=interpret_mode())
    return y[:, :C, :D]


@jax.jit
def grouped_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """Padded grouped matmul wrapper: [E, C, D] @ [E, D, F]."""
    _, C, _ = x.shape
    F = w.shape[-1]
    out = gmm(_pad128(x, 1, 2), _pad128(w, 1, 2), interpret=interpret_mode())
    return out[:, :C, :F]
