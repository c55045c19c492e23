"""Pallas TPU chunked-prefill attention: segment queries vs paged KV.

The serving engine streams a prompt forward as C-token *segments*
(repro.serving.engine, ``prefill_segment``): segment queries sit at
absolute positions ``pos0_b .. pos0_b + C - 1`` and attend every KV
token already in the request's pages — including the segment's own,
which the caller scatters into the pool before scoring. Like the paged
flash-decode kernel this streams the pool ``[num_pages, page_size, Hk,
hd]`` through the flashinfer CSR page table (``page_indptr`` /
``page_indices`` / ``last_page_len``) with the grid

  (B, max_pages)   page axis innermost (sequential),

scalar-prefetch page indirection in the BlockSpec index maps (the DMA
engine fetches each physical page tile straight from the pool — no
gathered per-row KV copy), and VMEM online-softmax state. Each step
fetches the page's whole ``[page_size, Hk, hd]`` tile and loops over the
KV heads statically: Mosaic only accepts a block whose last two dims
are (8, 128)-divisible or whole, so a one-head ``(.., 1, hd)`` block of
an ``Hk = 8`` pool is refused. The decode kernel carries one query row
per head; here the state is ``[Hk, C * group, ...]`` and the causal
mask is per query row: key ``j`` is visible to query row
``r`` iff ``j <= pos0_b + r // group`` and ``j`` is inside the row's
valid length. Query rows past the prompt (ragged last segment) see a
full causal window of real keys and produce well-defined junk the
caller discards.

``paged_prefill_ref`` in ref.py replays the identical update order with
the same jnp ops, so interpret-mode outputs match it bitwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _prefill_kernel(indptr_ref, indices_ref, lastlen_ref, pos0_ref,
                    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                    *, page_size: int, n_p: int, group: int, window: int):
    b = pl.program_id(0)
    p_idx = pl.program_id(1)

    @pl.when(p_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    n_pages = indptr_ref[b + 1] - indptr_ref[b]
    last = (n_pages - 1) * page_size + lastlen_ref[b] - 1
    pos0 = pos0_ref[b]
    Hk, R, hd = q_ref.shape[1:]                       # R = C * group rows
    shape = (R, page_size)
    j = p_idx * page_size + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    qpos = pos0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0) // group
    valid = (j <= qpos) & (j <= last) & (p_idx < n_pages)
    if window > 0:
        valid &= j > qpos - window
    scale = hd ** -0.5
    for h in range(Hk):                               # static: Mosaic tiles
        q = q_ref[0, h].astype(jnp.float32)           # [R, hd]
        k = k_ref[0, :, h, :].astype(jnp.float32)     # [page_size, hd]
        v = v_ref[0, :, h, :].astype(jnp.float32)
        s = jnp.dot(q * scale, k.T,
                    preferred_element_type=jnp.float32)   # [R, page_size]
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[h]                             # [R, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(p_idx == n_p - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def _kv_page_map(b, p, indptr, indices, lastlen, pos0):
    # Clamp past-the-end steps to the row's last page (masked in-kernel);
    # every row holds >= 1 page so indptr[b+1] - 1 >= indptr[b].
    i = jnp.minimum(indptr[b] + p, indptr[b + 1] - 1)
    return (indices[i], 0, 0, 0)


def paged_flash_prefill(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                        page_indptr: jax.Array, page_indices: jax.Array,
                        last_page_len: jax.Array, pos0: jax.Array, *,
                        max_pages: int, window: int = -1,
                        interpret: bool = False) -> jax.Array:
    """q: [B, C, H, hd] — one C-token prompt segment per row, row b's
    first query at absolute position ``pos0[b]``; k_pages/v_pages:
    [num_pages, page_size, Hk, hd] (the segment's own KV already
    written); page_indptr: [B+1]; page_indices: [total_pages];
    last_page_len: [B] (>= 1); pos0: [B] int32; max_pages: static
    per-row page bound (the grid extent). Returns [B, C, H, hd]."""
    B, C, H, hd = q.shape
    page_size, Hk = k_pages.shape[1], k_pages.shape[2]
    group = H // Hk
    R = C * group
    qg = q.reshape(B, C, Hk, group, hd).transpose(0, 2, 1, 3, 4).reshape(
        B, Hk, R, hd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, Hk, R, hd),
                         lambda b, p, ii, ix, ll, p0: (b, 0, 0, 0)),
            pl.BlockSpec((1, page_size, Hk, hd), _kv_page_map),
            pl.BlockSpec((1, page_size, Hk, hd), _kv_page_map),
        ],
        out_specs=pl.BlockSpec((1, Hk, R, hd),
                               lambda b, p, ii, ix, ll, p0: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hk, R, 1), jnp.float32),
            pltpu.VMEM((Hk, R, 1), jnp.float32),
            pltpu.VMEM((Hk, R, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, page_size=page_size,
                          n_p=max_pages, group=group, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hk, R, hd), q.dtype),
        interpret=interpret,
    )(page_indptr.astype(jnp.int32), page_indices.astype(jnp.int32),
      last_page_len.astype(jnp.int32), pos0.astype(jnp.int32),
      qg, k_pages, v_pages)
    return out.reshape(B, Hk, C, group, hd).transpose(0, 2, 1, 3, 4).reshape(
        B, C, H, hd)
