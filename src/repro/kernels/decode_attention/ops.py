"""Jitted wrappers for the flash-decode kernels (dense and paged)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .decode_attention import flash_decode
from .. import interpret_mode
from .paged import paged_flash_decode


@partial(jax.jit, static_argnames=("window",))
def _decode_attention(q, k, v, pos, window):
    return flash_decode(q, k, v, pos, window=window,
                        interpret=interpret_mode())


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     pos: jax.Array, window: int = -1) -> jax.Array:
    """q: [B, H, hd]; k/v: [B, S, Hk, hd]; pos: scalar int32 (one shared
    fill level) or [B] vector (per-row fill levels — what the serving
    engine's continuous batch passes). Any other rank is rejected here,
    at the op boundary, instead of surfacing as a reshape error inside
    the kernel."""
    pos = jnp.asarray(pos, jnp.int32)
    B = q.shape[0]
    if pos.ndim > 1:
        raise ValueError(
            f"pos must be a scalar or a [B] vector, got shape {pos.shape}")
    if pos.ndim == 1 and pos.shape[0] != B:
        raise ValueError(
            f"per-row pos length {pos.shape[0]} != batch {B}")
    return _decode_attention(q, k, v, jnp.broadcast_to(pos, (B,)), window)


@partial(jax.jit, static_argnames=("max_pages", "window"))
def _paged_decode_attention(q, k_pages, v_pages, page_indptr, page_indices,
                            last_page_len, max_pages, window):
    return paged_flash_decode(q, k_pages, v_pages, page_indptr,
                              page_indices, last_page_len,
                              max_pages=max_pages, window=window,
                              interpret=interpret_mode())


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_indptr: jax.Array,
                           page_indices: jax.Array, last_page_len: jax.Array,
                           max_pages: int, window: int = -1) -> jax.Array:
    """q: [B, H, hd]; k_pages/v_pages: [num_pages, page_size, Hk, hd];
    page_indptr [B+1] / page_indices / last_page_len [B]: the serving
    pool's CSR page tables (every row >= 1 page); max_pages: static
    per-row page bound."""
    if page_indptr.shape[0] != q.shape[0] + 1:
        raise ValueError(
            f"page_indptr carries {page_indptr.shape[0] - 1} rows for a "
            f"batch of {q.shape[0]}")
    if last_page_len.shape[0] != q.shape[0]:
        raise ValueError(
            f"last_page_len carries {last_page_len.shape[0]} rows for a "
            f"batch of {q.shape[0]}")
    return _paged_decode_attention(q, k_pages, v_pages, page_indptr,
                                   page_indices, last_page_len,
                                   int(max_pages), window)
