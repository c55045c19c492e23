"""Pallas TPU paged flash-decode kernel: K/V gathered via a page table.

The dense kernel streams one contiguous ``[B, S, Hk, hd]`` cache; here
the cache is the serving engine's global paged pool ``[num_pages,
page_size, Hk, hd]`` and each batch row names its pages through the
flashinfer CSR layout (``page_indptr`` / ``page_indices`` /
``last_page_len``). The grid is

  (B, max_pages)   with the page axis innermost (sequential),

and the per-(batch, kv-head) online-softmax state (m, l, acc) lives in
VMEM scratch across page steps, exactly like the dense kernel. Each step
fetches the page's whole ``[page_size, Hk, hd]`` tile and loops over the
KV heads statically (a one-head ``(.., 1, hd)`` block of an ``Hk = 8``
pool breaks Mosaic's (8, 128) block rule). The page
indirection happens in the BlockSpec index maps: the CSR arrays ride the
grid as scalar-prefetch operands (``PrefetchScalarGridSpec``), so the
index map reads ``page_indices[page_indptr[b] + p]`` and the DMA engine
fetches each physical ``[page_size, Hk, hd]`` K/V tile straight from the
pool — no gathered copy of the row's KV is ever materialized. Rows
shorter than ``max_pages`` pages clamp to their last page and mask the
re-fetched tile; rows must hold at least one page.

``paged_decode_ref`` in ref.py replays the identical update order with
the same jnp ops, so interpret-mode outputs match it bitwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(indptr_ref, indices_ref, lastlen_ref,
                  q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, page_size: int, n_p: int, window: int):
    b = pl.program_id(0)
    p_idx = pl.program_id(1)

    @pl.when(p_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    n_pages = indptr_ref[b + 1] - indptr_ref[b]
    pos = (n_pages - 1) * page_size + lastlen_ref[b] - 1
    Hk, group, hd = q_ref.shape[1:]
    shape = (group, page_size)
    j = p_idx * page_size + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    valid = (j <= pos) & (p_idx < n_pages)
    if window > 0:
        valid &= j > pos - window
    scale = hd ** -0.5
    for h in range(Hk):                               # static: Mosaic tiles
        q = q_ref[0, h].astype(jnp.float32)           # [group, hd]
        k = k_ref[0, :, h, :].astype(jnp.float32)     # [page_size, hd]
        v = v_ref[0, :, h, :].astype(jnp.float32)
        s = jnp.dot(q * scale, k.T,
                    preferred_element_type=jnp.float32)   # [group, page_size]
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[h]                             # [group, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(p_idx == n_p - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _kv_page_map(b, p, indptr, indices, lastlen):
    # Clamp past-the-end steps to the row's last page (masked in-kernel);
    # every row holds >= 1 page so indptr[b+1] - 1 >= indptr[b].
    i = jnp.minimum(indptr[b] + p, indptr[b + 1] - 1)
    return (indices[i], 0, 0, 0)


def paged_flash_decode(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       page_indptr: jax.Array, page_indices: jax.Array,
                       last_page_len: jax.Array, *, max_pages: int,
                       window: int = -1,
                       interpret: bool = False) -> jax.Array:
    """q: [B, H, hd]; k_pages/v_pages: [num_pages, page_size, Hk, hd];
    page_indptr: [B+1]; page_indices: [total_pages]; last_page_len: [B]
    (>= 1 — row b's valid length is ``(n_pages_b - 1) * page_size +
    last_page_len_b``, its final token sitting at position length-1);
    max_pages: static per-row page bound (the grid extent).
    Returns [B, H, hd]."""
    B, H, hd = q.shape
    page_size, Hk = k_pages.shape[1], k_pages.shape[2]
    group = H // Hk
    qg = q.reshape(B, Hk, group, hd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, Hk, group, hd),
                         lambda b, p, ii, ix, ll: (b, 0, 0, 0)),
            pl.BlockSpec((1, page_size, Hk, hd), _kv_page_map),
            pl.BlockSpec((1, page_size, Hk, hd), _kv_page_map),
        ],
        out_specs=pl.BlockSpec((1, Hk, group, hd),
                               lambda b, p, ii, ix, ll: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hk, group, 1), jnp.float32),
            pltpu.VMEM((Hk, group, 1), jnp.float32),
            pltpu.VMEM((Hk, group, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, page_size=page_size,
                          n_p=max_pages, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hk, group, hd), q.dtype),
        interpret=interpret,
    )(page_indptr.astype(jnp.int32), page_indices.astype(jnp.int32),
      last_page_len.astype(jnp.int32), qg, k_pages, v_pages)
    return out.reshape(B, H, hd)
