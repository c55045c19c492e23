"""Pure-jnp oracles for chunked-prefill attention over paged KV."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def prefill_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                          pos0: jax.Array, lengths: jax.Array,
                          window: int = -1) -> jax.Array:
    """Naive oracle: C-query GQA attention over a dense KV view.

    q: [B, C, H, hd]; k/v: [B, S, Hk, hd]; pos0: [B] — query row r of
    batch b sits at absolute position ``pos0[b] + r``; lengths: [B] —
    keys j < lengths[b] exist. Returns [B, C, H, hd].
    """
    B, C, H, hd = q.shape
    S, Hk = k.shape[1], k.shape[2]
    group = H // Hk
    pos0 = jnp.asarray(pos0, jnp.int32)
    qg = q.reshape(B, C, Hk, group, hd).astype(jnp.float32) * hd ** -0.5
    s = jnp.einsum("bchgd,bkhd->bchgk", qg, k.astype(jnp.float32))
    j = jnp.arange(S)
    qpos = pos0[:, None] + jnp.arange(C)[None]        # [B, C]
    valid = j[None, None, :] <= qpos[:, :, None]      # [B, C, S]
    valid &= j[None, None, :] < jnp.asarray(lengths)[:, None, None]
    if window > 0:
        valid &= (qpos[:, :, None] - j[None, None, :]) < window
    s = jnp.where(valid[:, :, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bchgk,bkhd->bchgd", w, v.astype(jnp.float32))
    return o.reshape(B, C, H, hd).astype(q.dtype)


def paged_prefill_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                      page_indptr, page_indices, last_page_len, pos0, *,
                      max_pages: int, window: int = -1) -> jax.Array:
    """Reference twin of :func:`..paged.paged_flash_prefill`.

    Replays the kernel's page-by-page online-softmax update with the
    SAME jnp ops on the SAME block shapes, in the same order, traced
    under one jit — so interpret-mode kernel outputs match BITWISE (an
    eager per-op replay drifts in the last float32 ulp through
    different dot/transpose fusion). The page-table arrays and pos0 are
    consumed as static host values; test-sized inputs only.
    """
    B, C, H, hd = q.shape
    page_size, Hk = k_pages.shape[1], k_pages.shape[2]
    group = H // Hk
    indptr = np.asarray(page_indptr)
    indices = np.asarray(page_indices)
    lastlen = np.asarray(last_page_len)
    pos0_np = np.asarray(pos0)
    scale = hd ** -0.5

    def replay(q, k_pages, v_pages):
        qg = q.reshape(B, C, Hk, group, hd).transpose(0, 2, 1, 3, 4).reshape(
            B, Hk, C * group, hd)
        rows = []
        for b in range(B):
            n_pages = int(indptr[b + 1] - indptr[b])
            last = (n_pages - 1) * page_size + int(lastlen[b]) - 1
            heads = []
            for h in range(Hk):
                qf = qg[b, h].astype(jnp.float32)
                m = jnp.full((C * group, 1), -1e30, jnp.float32)
                l = jnp.zeros((C * group, 1), jnp.float32)
                acc = jnp.zeros((C * group, hd), jnp.float32)
                for p_idx in range(max_pages):
                    i = min(indptr[b] + p_idx, indptr[b + 1] - 1)
                    k = k_pages[indices[i], :, h, :].astype(jnp.float32)
                    v = v_pages[indices[i], :, h, :].astype(jnp.float32)
                    s = jnp.dot(qf * scale, k.T,
                                preferred_element_type=jnp.float32)
                    j = p_idx * page_size + jax.lax.broadcasted_iota(
                        jnp.int32, s.shape, 1)
                    qpos = int(pos0_np[b]) + jax.lax.broadcasted_iota(
                        jnp.int32, s.shape, 0) // group
                    valid = (j <= qpos) & (j <= last) & (p_idx < n_pages)
                    if window > 0:
                        valid &= j > qpos - window
                    s = jnp.where(valid, s, -1e30)
                    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
                    p = jnp.exp(s - m_new)
                    alpha = jnp.exp(m - m_new)
                    l = l * alpha + p.sum(axis=-1, keepdims=True)
                    acc = acc * alpha + jnp.dot(
                        p, v, preferred_element_type=jnp.float32)
                    m = m_new
                heads.append((acc / jnp.maximum(l, 1e-30)).astype(q.dtype))
            rows.append(jnp.stack(heads))
        return jnp.stack(rows).reshape(B, Hk, C, group, hd).transpose(
            0, 2, 1, 3, 4).reshape(B, C, H, hd)

    return jax.jit(replay)(q, k_pages, v_pages)
