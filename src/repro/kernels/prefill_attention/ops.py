"""Jitted wrapper for the chunked-prefill paged-attention kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .. import interpret_mode
from .paged import paged_flash_prefill


@partial(jax.jit, static_argnames=("max_pages", "window"))
def _paged_prefill_attention(q, k_pages, v_pages, page_indptr, page_indices,
                             last_page_len, pos0, max_pages, window):
    return paged_flash_prefill(q, k_pages, v_pages, page_indptr,
                               page_indices, last_page_len, pos0,
                               max_pages=max_pages, window=window,
                               interpret=interpret_mode())


def paged_prefill_attention(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, page_indptr: jax.Array,
                            page_indices: jax.Array, last_page_len: jax.Array,
                            pos0: jax.Array, max_pages: int,
                            window: int = -1) -> jax.Array:
    """q: [B, C, H, hd] — one C-token prompt segment per batch row, row
    b's first query at absolute position ``pos0[b]`` (scalar pos0
    broadcasts); k_pages/v_pages: [num_pages, page_size, Hk, hd] with
    the segment's own KV already written; page_indptr [B+1] /
    page_indices / last_page_len [B]: the serving pool's CSR page
    tables (every row >= 1 page); max_pages: static per-row page bound.
    Returns [B, C, H, hd]."""
    B = q.shape[0]
    pos0 = jnp.asarray(pos0, jnp.int32)
    if pos0.ndim > 1:
        raise ValueError(
            f"pos0 must be a scalar or a [B] vector, got shape {pos0.shape}")
    if pos0.ndim == 1 and pos0.shape[0] != B:
        raise ValueError(
            f"per-row pos0 length {pos0.shape[0]} != batch {B}")
    if page_indptr.shape[0] != B + 1:
        raise ValueError(
            f"page_indptr carries {page_indptr.shape[0] - 1} rows for a "
            f"batch of {B}")
    if last_page_len.shape[0] != B:
        raise ValueError(
            f"last_page_len carries {last_page_len.shape[0]} rows for a "
            f"batch of {B}")
    return _paged_prefill_attention(q, k_pages, v_pages, page_indptr,
                                    page_indices, last_page_len,
                                    jnp.broadcast_to(pos0, (B,)),
                                    int(max_pages), window)
