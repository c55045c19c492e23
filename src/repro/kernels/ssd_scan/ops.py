"""Jitted wrapper: model-layout adapter for the SSD Pallas kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import interpret_mode
from .ssd_scan import ssd_scan


@jax.jit
def ssd_chunked_kernel(x: jax.Array, dt: jax.Array, A_log: jax.Array,
                       Bmat: jax.Array, Cmat: jax.Array):
    """Same contract as repro.models.ssm.ssd_chunked (zero init state).

    x: [B, S, nh, hp]; dt: [B, S, nh]; A_log: [nh]; B/C: [B, S, ds].
    Returns (y [B, S, nh, hp], h [B, nh, ds, hp]).
    """
    Bb, S, nh, hp = x.shape
    ds = Bmat.shape[-1]
    a = (-jnp.exp(A_log.astype(jnp.float32)) * dt)          # [B, S, nh]
    xd = (x.astype(jnp.float32) * dt[..., None]).astype(x.dtype)

    # fold (batch, head) -> G; broadcast B/C across heads
    aG = a.transpose(0, 2, 1).reshape(Bb * nh, S)
    xG = xd.transpose(0, 2, 1, 3).reshape(Bb * nh, S, hp)
    bG = jnp.broadcast_to(Bmat[:, None], (Bb, nh, S, ds)).reshape(
        Bb * nh, S, ds).astype(x.dtype)
    cG = jnp.broadcast_to(Cmat[:, None], (Bb, nh, S, ds)).reshape(
        Bb * nh, S, ds).astype(x.dtype)

    y, h = ssd_scan(aG, xG, bG, cG, interpret=interpret_mode())
    y = y.reshape(Bb, nh, S, hp).transpose(0, 2, 1, 3)
    return y.astype(x.dtype), h.reshape(Bb, nh, ds, hp)
