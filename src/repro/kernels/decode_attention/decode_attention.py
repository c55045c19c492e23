"""Pallas TPU flash-decode kernel: one query token vs a long KV cache.

Decode attention at 32k-500k context is purely HBM-bandwidth-bound on the
KV cache stream. The kernel tiles the sequence axis; grid is

  (B, S/bs)   with the S axis innermost (sequential),

keeping per-(batch, kv-head) online-softmax state (m, l, acc) in VMEM
scratch across S steps — the classic flash-decode single-pass scheme. The
q block [Hk, group, hd] stays resident; each step streams one
[bs, Hk, hd] K tile and V tile through VMEM and loops over the KV heads
statically (a one-head ``(.., 1, hd)`` block of an ``Hk = 8`` cache
breaks Mosaic's (8, 128) block rule). Position/window masking is
computed from the grid coordinate with an iota, so arbitrary cache fill
levels work.

Block choice: bs=512 rows of (Hk=8, hd=128) bf16 = 1 MiB per K/V tile;
with double buffering ~4 MiB VMEM — under the 16 MiB scoped budget, and
wide enough that the HBM stream hits peak bandwidth.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, bs: int, n_s: int, window: int):
    s_idx = pl.program_id(1)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[pl.program_id(0)]
    Hk, group, hd = q_ref.shape[1:]
    shape = (group, bs)
    j = s_idx * bs + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    valid = j <= pos
    if window > 0:
        valid &= j > pos - window
    scale = hd ** -0.5
    for h in range(Hk):                               # static: Mosaic tiles
        q = q_ref[0, h].astype(jnp.float32)           # [group, hd]
        k = k_ref[0, :, h, :].astype(jnp.float32)     # [bs, hd]
        v = v_ref[0, :, h, :].astype(jnp.float32)
        s = jnp.dot(q * scale, k.T,
                    preferred_element_type=jnp.float32)   # [group, bs]
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[h]                             # [group, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(s_idx == n_s - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array, pos: jax.Array,
                 *, window: int = -1, bs: int = 512,
                 interpret: bool = False) -> jax.Array:
    """q: [B, H, hd]; k/v: [B, S, Hk, hd]; pos: scalar or [B] int32 (a
    vector carries per-row cache fill levels — the serving engine's
    continuous batch decodes every slot at its own position) ->
    [B, H, hd]."""
    B, H, hd = q.shape
    S, Hk = k.shape[1], k.shape[2]
    group = H // Hk
    bs = min(bs, S)
    assert S % bs == 0, (S, bs)
    n_s = S // bs
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    qg = q.reshape(B, Hk, group, hd)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, bs=bs, n_s=n_s, window=window),
        grid=(B, n_s),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                 # pos
            pl.BlockSpec((1, Hk, group, hd), lambda b, s: (b, 0, 0, 0)),
            pl.BlockSpec((1, bs, Hk, hd), lambda b, s: (b, s, 0, 0)),
            pl.BlockSpec((1, bs, Hk, hd), lambda b, s: (b, s, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hk, group, hd), lambda b, s: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hk, group, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((Hk, group, 1), jnp.float32),
            pltpu.VMEM((Hk, group, 1), jnp.float32),
            pltpu.VMEM((Hk, group, hd), jnp.float32),
        ],
        interpret=interpret,
    )(pos, qg, k, v)
    return out.reshape(B, H, hd)
