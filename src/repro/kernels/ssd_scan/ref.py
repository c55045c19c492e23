"""Pure-jnp oracle for the SSD intra-chunk kernel (one chunk)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_chunk_ref(a_cs: jax.Array, x: jax.Array, B: jax.Array, C: jax.Array,
                  h_in: jax.Array):
    """One SSD chunk, one head group.

    a_cs: [Q] cumulative log-decay; x: [Q, hp] (already dt-scaled);
    B, C: [Q, ds]; h_in: [ds, hp] incoming state.
    Returns (y [Q, hp], h_out [ds, hp]).

    The body follows the kernel's op order (inputs cast to float32 first,
    ``jnp.dot`` with a float32 accumulator) and :func:`ssd_multi_chunk_ref`
    runs it under ``jax.jit``, as interpret mode runs the kernel body:
    compiled and eager float32 arithmetic round the carried state
    differently in the last ulp.
    """
    f32 = jnp.float32
    xf, Bf, Cf, h = (t.astype(f32) for t in (x, B, C, h_in))
    Q = a_cs.shape[0]
    scores = jnp.dot(Cf, Bf.T, preferred_element_type=f32)     # [Q, Q]
    diff = a_cs[:, None] - a_cs[None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.where(mask, jnp.exp(diff), 0.0)
    y = jnp.dot(scores * L, xf, preferred_element_type=f32)    # intra
    y = y + jnp.exp(a_cs)[:, None] * jnp.dot(
        Cf, h, preferred_element_type=f32)
    decay_end = jnp.exp(a_cs[-1] - a_cs)
    h_out = jnp.exp(a_cs[-1]) * h + jnp.dot(
        (Bf * decay_end[:, None]).T, xf, preferred_element_type=f32)
    return y.astype(x.dtype), h_out


def ssd_multi_chunk_ref(a: jax.Array, x: jax.Array, B: jax.Array,
                        C: jax.Array, h0: jax.Array):
    """Sequential chunks for a single head: a [Nc, Q], x [Nc, Q, hp],
    B/C [Nc, Q, ds], h0 [ds, hp] -> (y [Nc, Q, hp], h [ds, hp])."""
    chunk = jax.jit(ssd_chunk_ref)
    h = h0
    ys = []
    for c in range(a.shape[0]):
        y, h = chunk(jnp.cumsum(a[c]), x[c], B[c], C[c], h)
        ys.append(y)
    return jnp.stack(ys), h
