"""GQA attention: chunked-flash prefill, cached decode, cross-attention.

Design notes
------------
* Prefill/train uses a pure-XLA *chunked flash* formulation: ``lax.scan``
  over KV chunks with online-softmax running statistics. Peak memory is
  O(S * chunk) instead of O(S^2), which is what makes the 32k-prefill cells
  compile within HBM. Paged segment prefill scores with the Pallas kernel
  (kernels/prefill_attention); decode uses the einsum path below, dense
  and paged alike — the decode-attention kernels are on no serving path.
* Decode (q_len == 1) uses exact einsum attention over the cache capacity
  with a position mask; scores are [B, H, 1, S] which is small. The cache
  is updated in place at ``pos`` via dynamic_update_slice (donated buffer).
* Sliding windows are dynamic scalars so that layers with different window
  sizes can share one scanned HLO body (-1 == global).
* GQA: q heads H, kv heads Hk, group = H // Hk via reshape to
  [B, S, Hk, group, hd] — no materialized repeat of K/V.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.sharding import constrain
from .layers import _dense_init, apply_mrope, apply_rope

Params = Dict[str, jax.Array]

NEG_INF = -1e30


def attn_params(key, d_model: int, num_heads: int, num_kv_heads: int,
                head_dim: int, qkv_bias: bool = False) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (d_model, num_heads * head_dim)),
        "wk": _dense_init(ks[1], (d_model, num_kv_heads * head_dim)),
        "wv": _dense_init(ks[2], (d_model, num_kv_heads * head_dim)),
        "wo": _dense_init(ks[3], (num_heads * head_dim, d_model)),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((num_heads * head_dim,), jnp.bfloat16)
        p["bk"] = jnp.zeros((num_kv_heads * head_dim,), jnp.bfloat16)
        p["bv"] = jnp.zeros((num_kv_heads * head_dim,), jnp.bfloat16)
    return p


def _project_qkv(p: Params, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, S, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


def _rope_qk(q, k, positions, cfg):
    if cfg.mrope:
        q = apply_mrope(q, positions, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k


# --------------------------------------------------------------------------
# Chunked-flash full-sequence attention (train / prefill)
# --------------------------------------------------------------------------

def _mask_for(Sq: int, chunk: int, c_start, window, causal: bool,
              q_offset=0):
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = c_start + jnp.arange(chunk)
    dist = q_pos[:, None] - k_pos[None, :]               # [Sq, chunk]
    mask = jnp.ones((Sq, chunk), bool)
    if causal:
        mask &= dist >= 0
    win = jnp.asarray(window, jnp.int32)
    mask &= jnp.where(win > 0, dist < win, True)
    return mask


def _rep(x, group):
    x = jnp.repeat(x, group, axis=2)
    return constrain(x, ("pod", "data"), None, "model", None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    window: int = -1, causal: bool = True,
                    chunk: int = 1024) -> jax.Array:
    """Online-softmax attention, scanning over KV chunks.

    q: [B, Sq, H, hd]; k, v: [B, Sk, Hk, hd]. window: scalar (-1 = global).
    Returns [B, Sq, H, hd] (bf16 as input dtype).

    Sharding: scores live on the *full* H dim (KV heads are broadcast to H
    per chunk), so the model axis shards them even when Hk < axis size —
    the [Hk, group] layout would silently replicate a 16x larger buffer.

    Memory: custom VJP (FlashAttention-2-style). Plain autodiff of the
    chunk scan stacks every chunk's f32 scores as residuals — the full
    [Sq, Sk] attention matrix — which is exactly what flash attention
    exists to avoid. The backward here saves only (q, k, v, out, lse) and
    recomputes per-chunk scores.
    """
    out, _ = _flash_fwd_scan(q, k, v, window, causal, chunk)
    return out


def _flash_fwd_scan(q, k, v, window, causal: bool, chunk: int, q_offset=0):
    B, Sq, H, hd = q.shape
    Sk, Hk = k.shape[1], k.shape[2]
    group = H // Hk
    chunk = min(chunk, Sk)
    n_chunks = Sk // chunk
    assert Sk % chunk == 0, (Sk, chunk)

    qf = q.astype(jnp.float32) * hd ** -0.5
    kc = k.reshape(B, n_chunks, chunk, Hk, hd)
    vc = v.reshape(B, n_chunks, chunk, Hk, hd)

    def body(carry, inputs):
        acc, m, l = carry                      # [B,Sq,H,hd], [B,Sq,H], [B,Sq,H]
        kcb, vcb, c_start = inputs             # [B,chunk,Hk,hd] x2, scalar
        krep = _rep(kcb, group)
        vrep = _rep(vcb, group)
        s = jnp.einsum("bqhd,bkhd->bqhk", qf, krep.astype(jnp.float32))
        s = constrain(s, ("pod", "data"), None, "model", None)
        mask = _mask_for(Sq, s.shape[-1], c_start, window, causal, q_offset)
        s = jnp.where(mask[None, :, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bqhk,bkhd->bqhd", p, vrep.astype(jnp.float32))
        return (acc, m_new, l_new), None

    acc0 = jnp.zeros((B, Sq, H, hd), jnp.float32)
    m0 = jnp.full((B, Sq, H), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, H), jnp.float32)
    starts = jnp.arange(n_chunks) * chunk
    (acc, m, l), _ = jax.lax.scan(
        body, (acc0, m0, l0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), starts))
    l = jnp.maximum(l, 1e-30)
    out = (acc / l[..., None]).astype(q.dtype)
    lse = m + jnp.log(l)                       # [B, Sq, H]
    return out, lse


def _flash_fwd(q, k, v, window, causal, chunk):
    out, lse = _flash_fwd_scan(q, k, v, window, causal, chunk)
    return out, (q, k, v, out, lse)


def _flash_bwd(window, causal, chunk, res, dout):
    q, k, v, out, lse = res
    B, Sq, H, hd = q.shape
    Sk, Hk = k.shape[1], k.shape[2]
    group = H // Hk
    chunk_ = min(chunk, Sk)
    n_chunks = Sk // chunk_

    qf = q.astype(jnp.float32) * hd ** -0.5
    do = dout.astype(jnp.float32)
    # D_i = rowsum(dO * O) — the softmax-backward diagonal term
    delta = jnp.sum(do * out.astype(jnp.float32), axis=-1)     # [B, Sq, H]
    kc = k.reshape(B, n_chunks, chunk_, Hk, hd)
    vc = v.reshape(B, n_chunks, chunk_, Hk, hd)
    starts = jnp.arange(n_chunks) * chunk_

    def body(dq, inputs):
        kcb, vcb, c_start = inputs
        krep = _rep(kcb, group).astype(jnp.float32)
        vrep = _rep(vcb, group).astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bqhk", qf, krep)
        mask = _mask_for(Sq, chunk_, c_start, window, causal)
        s = jnp.where(mask[None, :, None, :], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                        # [B,Sq,H,ck]
        dv_rep = jnp.einsum("bqhk,bqhd->bkhd", p, do)
        dp = jnp.einsum("bqhd,bkhd->bqhk", do, vrep)
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("bqhk,bkhd->bqhd", ds, krep) * hd ** -0.5
        dk_rep = jnp.einsum("bqhk,bqhd->bkhd", ds, qf)
        # fold the H = Hk*group broadcast back down
        dk = dk_rep.reshape(B, chunk_, Hk, group, hd).sum(axis=3)
        dv = dv_rep.reshape(B, chunk_, Hk, group, hd).sum(axis=3)
        return dq, (dk, dv)

    dq0 = jnp.zeros((B, Sq, H, hd), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(
        body, dq0, (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), starts))
    dk = jnp.moveaxis(dks, 0, 1).reshape(B, Sk, Hk, hd)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(B, Sk, Hk, hd)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def self_attention(p: Params, x: jax.Array, positions: jax.Array, cfg,
                   window: jax.Array | int = -1, causal: bool = True) -> jax.Array:
    """Full-sequence self attention (train / prefill)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg)
    q, k = _rope_qk(q, k, positions, cfg)
    q = constrain(q, ("pod", "data"), None, "model", None)
    k = constrain(k, ("pod", "data"), None, None, None)
    o = flash_attention(q, k, v, window=window, causal=causal)
    o = o.reshape(B, S, cfg.num_heads * cfg.head_dim)
    return o @ p["wo"]


# --------------------------------------------------------------------------
# Segment-streamed prefill (q_len == C prompt tokens at offset pos)
# --------------------------------------------------------------------------

def segment_attention(p: Params, x: jax.Array, cache: Params, pos: jax.Array,
                      positions: jax.Array, cfg,
                      window: jax.Array | int = -1) -> Tuple[jax.Array, Params]:
    """Prompt-segment attention against a request's dense KV cache.

    x: [B, C, D] — one C-token prompt segment whose first token sits at
    absolute position ``pos`` (int32 scalar); cache k/v: [B, S, Hk, hd].
    The segment's K/V is scattered into slots ``pos..pos+C-1`` (rows past
    capacity drop), then the queries run the SAME chunked-flash scan as
    the one-shot prefill over the full capacity axis with the causal mask
    offset by ``pos`` — every op from the score einsum on is shared with
    :func:`self_attention`, and flash rows are independent, so a row's
    output is bitwise identical to the one-shot forward's row.
    Returns (output [B, C, D], updated cache).
    """
    B, C, _ = x.shape
    S = cache["k"].shape[1]
    q, k_new, v_new = _project_qkv(p, x, cfg)
    q, k_new = _rope_qk(q, k_new, positions, cfg)

    idx = jnp.asarray(pos, jnp.int32) + jnp.arange(C)      # [C] absolute
    dst = jnp.where(idx < S, idx, S)                       # overflow drops
    k_cache = cache["k"].at[:, dst].set(k_new, mode="drop")
    v_cache = cache["v"].at[:, dst].set(v_new, mode="drop")

    q = constrain(q, ("pod", "data"), None, "model", None)
    k_att = constrain(k_cache, ("pod", "data"), None, None, None)
    o, _ = _flash_fwd_scan(q, k_att, v_cache, window, True, 1024,
                           q_offset=jnp.asarray(pos, jnp.int32))
    o = o.reshape(B, C, cfg.num_heads * cfg.head_dim)
    return o @ p["wo"], {"k": k_cache, "v": v_cache}


def segment_attention_paged(p: Params, x: jax.Array, cache: Params,
                            pos: jax.Array, positions: jax.Array,
                            pages: jax.Array, cfg,
                            window: jax.Array | int = -1,
                            write_min: Optional[jax.Array] = None,
                            write_max: Optional[jax.Array] = None
                            ) -> Tuple[jax.Array, Params]:
    """Prompt-segment attention against the global paged KV pool.

    x: [B, C, D]; cache k/v: [num_pages, page_size, Hk, hd]; pages:
    [B, max_pages] page table (padded entries are causally masked); pos:
    the segment's first absolute position. K/V rows land through the page
    table only where ``write_min <= idx < write_max`` — shared prefix
    pages (other requests still reference them) and pad rows past the
    prompt are never rewritten; out-of-range rows redirect to page id
    ``num_pages`` and drop.

    Scoring streams the pool through the Pallas chunked paged-prefill
    kernel (:mod:`repro.kernels.prefill_attention`) when the sliding
    window is static and ``write_max`` bounds the valid KV length — the
    kernel's page-table indirection reads each physical page once
    instead of gathering the [B, max_pages*page_size, Hk, hd] dense view
    first. Otherwise (traced window / unbounded write) it falls back to
    the gather + offset flash scan, which is bitwise-identical to the
    dense :func:`segment_attention` path.
    Returns (output [B, C, D], updated pool).
    """
    B, C, _ = x.shape
    N, page_size = cache["k"].shape[0], cache["k"].shape[1]
    max_pages = pages.shape[1]
    S = max_pages * page_size                    # logical capacity
    Hk, hd = cfg.num_kv_heads, cfg.head_dim
    q, k_new, v_new = _project_qkv(p, x, cfg)
    q, k_new = _rope_qk(q, k_new, positions, cfg)

    idx = jnp.asarray(pos, jnp.int32) + jnp.arange(C)      # [C] absolute
    ok = idx < S
    if write_min is not None:
        ok &= idx >= write_min
    if write_max is not None:
        ok &= idx < write_max
    slot = jnp.minimum(idx, S - 1)
    page = jnp.take_along_axis(
        pages, jnp.broadcast_to((slot // page_size)[None, :], (B, C)), axis=1)
    page = jnp.where(ok[None, :], page, N)                 # [B, C]
    off = jnp.broadcast_to((slot % page_size)[None, :], (B, C))
    k_pool = cache["k"].at[page, off].set(k_new, mode="drop")
    v_pool = cache["v"].at[page, off].set(v_new, mode="drop")

    if write_max is not None and isinstance(window, int):
        # Pallas paged-prefill path: full-width CSR rows (n_pages ==
        # max_pages for every row) make the kernel's valid-length mask
        # `(n_pages-1)*page_size + lastlen - 1` come out to exactly
        # write_max - 1; pad page ids (N, out of pool bounds) redirect
        # to page 0 — their keys sit past every query's causal horizon,
        # so the kernel never unmasks them.
        from repro.kernels.prefill_attention import paged_prefill_attention
        plen = jnp.broadcast_to(jnp.asarray(write_max, jnp.int32), (B,))
        indptr = jnp.arange(B + 1, dtype=jnp.int32) * max_pages
        indices = jnp.where(pages < N, pages, 0).reshape(-1)
        lastlen = plen - (max_pages - 1) * page_size
        pos0 = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
        o = paged_prefill_attention(q, k_pool, v_pool, indptr, indices,
                                    lastlen, pos0, max_pages=max_pages,
                                    window=window)
    else:
        k_cache = k_pool[pages].reshape(B, S, Hk, hd)
        v_cache = v_pool[pages].reshape(B, S, Hk, hd)
        q = constrain(q, ("pod", "data"), None, "model", None)
        k_att = constrain(k_cache, ("pod", "data"), None, None, None)
        o, _ = _flash_fwd_scan(q, k_att, v_cache, window, True, 1024,
                               q_offset=jnp.asarray(pos, jnp.int32))
    o = o.reshape(B, C, cfg.num_heads * hd)
    return o @ p["wo"], {"k": k_pool, "v": v_pool}


# --------------------------------------------------------------------------
# Cached decode (q_len == 1)
# --------------------------------------------------------------------------

def init_kv_cache(batch: int, capacity: int, num_kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16) -> Params:
    return {
        "k": jnp.zeros((batch, capacity, num_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, capacity, num_kv_heads, head_dim), dtype),
    }


def decode_attention(p: Params, x: jax.Array, cache: Params, pos: jax.Array,
                     cfg, window: jax.Array | int = -1) -> Tuple[jax.Array, Params]:
    """One-token attention against a cache of static capacity.

    x: [B, 1, D]; cache k/v: [B, S, Hk, hd]; pos: int32 scalar or [B]
    vector — number of valid cached tokens per batch row (a vector lets a
    continuous-batching scheduler serve requests at different sequence
    positions in one padded step); the new token has position ``pos`` and
    is written into slot ``pos`` (clamped to capacity-1).
    Returns (output [B, 1, D], updated cache).
    """
    B, _, _ = x.shape
    S = cache["k"].shape[1]
    Hk, hd = cfg.num_kv_heads, cfg.head_dim
    group = cfg.num_heads // Hk
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))

    q, k_new, v_new = _project_qkv(p, x, cfg)
    if cfg.mrope:
        posq = jnp.broadcast_to(pos_b[None, :, None], (3, B, 1))
    else:
        posq = pos_b[:, None]
    q, k_new = _rope_qk(q, k_new, posq, cfg)

    # Write each row's new kv into its slot (donated in the serving step).
    slot = jnp.minimum(pos_b, S - 1)                       # [B]
    k_cache = cache["k"].at[jnp.arange(B), slot].set(k_new[:, 0])
    v_cache = cache["v"].at[jnp.arange(B), slot].set(v_new[:, 0])
    k_cache = constrain(k_cache, ("pod", "data"), "model", None, None)
    v_cache = constrain(v_cache, ("pod", "data"), "model", None, None)

    qg = q.reshape(B, 1, Hk, group, hd).astype(jnp.float32) * hd ** -0.5
    s = jnp.einsum("bqhgd,bkhd->bhgk", qg, k_cache.astype(jnp.float32))
    j = jnp.arange(S)
    valid = j[None, :] <= slot[:, None]                    # [B, S]
    win = jnp.asarray(window, jnp.int32)
    valid &= jnp.where(win > 0, (pos_b[:, None] - j[None, :]) < win, True)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", w, v_cache.astype(jnp.float32))
    o = o.reshape(B, 1, cfg.num_heads * hd).astype(x.dtype)
    return o @ p["wo"], {"k": k_cache, "v": v_cache}


def init_paged_kv_cache(num_pages: int, page_size: int, num_kv_heads: int,
                        head_dim: int, dtype=jnp.bfloat16) -> Params:
    """Global paged KV pool: pages replace the per-row capacity axis."""
    return {
        "k": jnp.zeros((num_pages, page_size, num_kv_heads, head_dim), dtype),
        "v": jnp.zeros((num_pages, page_size, num_kv_heads, head_dim), dtype),
    }


def decode_attention_paged(p: Params, x: jax.Array, cache: Params,
                           pos: jax.Array, pages: jax.Array, cfg,
                           window: jax.Array | int = -1,
                           active: Optional[jax.Array] = None
                           ) -> Tuple[jax.Array, Params]:
    """One-token attention against the global paged KV pool.

    x: [B, 1, D]; cache k/v: [num_pages, page_size, Hk, hd] — the pool
    shared by every request; pages: [B, max_pages] int32 — each row's
    page table padded with any value (padded entries sit past ``pos``
    and are causally masked); pos: scalar or [B] valid-token counts;
    active: [B] bool — an inactive row's write is DROPPED (its page-table
    row may alias pages owned by live requests, unlike the dense layout
    where a stale row's slot belongs to nobody else).

    Bit-identity with :func:`decode_attention`: the pool is gathered
    through the page table into the same ``[B, max_pages*page_size, Hk,
    hd]`` contiguous view the dense path scores against, and every op
    from the einsum on is shared verbatim — so for ``capacity =
    max_pages * page_size`` an active row's output (and therefore the
    generated tokens) is bitwise identical to the dense engine's.
    Returns (output [B, 1, D], updated pool).
    """
    B, _, _ = x.shape
    N, page_size = cache["k"].shape[0], cache["k"].shape[1]
    max_pages = pages.shape[1]
    S = max_pages * page_size                    # logical capacity
    Hk, hd = cfg.num_kv_heads, cfg.head_dim
    group = cfg.num_heads // Hk
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))

    q, k_new, v_new = _project_qkv(p, x, cfg)
    if cfg.mrope:
        posq = jnp.broadcast_to(pos_b[None, :, None], (3, B, 1))
    else:
        posq = pos_b[:, None]
    q, k_new = _rope_qk(q, k_new, posq, cfg)

    # Write each row's new kv through its page table; inactive rows write
    # out of bounds (page id N) and drop.
    slot = jnp.minimum(pos_b, S - 1)                       # [B]
    page = jnp.take_along_axis(pages, (slot // page_size)[:, None],
                               axis=1)[:, 0]               # [B] physical
    if active is not None:
        page = jnp.where(active, page, N)
    off = slot % page_size
    k_pool = cache["k"].at[page, off].set(k_new[:, 0], mode="drop")
    v_pool = cache["v"].at[page, off].set(v_new[:, 0], mode="drop")

    # Gather the row's pages into the dense path's [B, S, Hk, hd] view.
    k_cache = k_pool[pages].reshape(B, S, Hk, hd)
    v_cache = v_pool[pages].reshape(B, S, Hk, hd)
    k_cache = constrain(k_cache, ("pod", "data"), "model", None, None)
    v_cache = constrain(v_cache, ("pod", "data"), "model", None, None)

    qg = q.reshape(B, 1, Hk, group, hd).astype(jnp.float32) * hd ** -0.5
    s = jnp.einsum("bqhgd,bkhd->bhgk", qg, k_cache.astype(jnp.float32))
    j = jnp.arange(S)
    valid = j[None, :] <= slot[:, None]                    # [B, S]
    win = jnp.asarray(window, jnp.int32)
    valid &= jnp.where(win > 0, (pos_b[:, None] - j[None, :]) < win, True)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", w, v_cache.astype(jnp.float32))
    o = o.reshape(B, 1, cfg.num_heads * hd).astype(x.dtype)
    return o @ p["wo"], {"k": k_pool, "v": v_pool}


# --------------------------------------------------------------------------
# Cross-attention (enc-dec)
# --------------------------------------------------------------------------

def cross_attention(p: Params, x: jax.Array, memory_kv: Params) -> jax.Array:
    """x: [B, Sq, D] attends over precomputed encoder memory K/V."""
    B, Sq, _ = x.shape
    k, v = memory_kv["k"], memory_kv["v"]        # [B, Sm, Hk, hd]
    hd = k.shape[3]
    H = p["wq"].shape[1] // hd
    q = (x @ p["wq"]).reshape(B, Sq, H, hd)
    q = constrain(q, ("pod", "data"), None, "model", None)
    o = flash_attention(q, k, v, causal=False)   # chunked: no [Sq, Sm] blowup
    o = o.reshape(B, Sq, H * hd)
    return o @ p["wo"]


def encode_memory_kv(p: Params, memory: jax.Array, num_kv_heads: int,
                     head_dim: int) -> Params:
    """Precompute cross-attention K/V from encoder output."""
    B, Sm, _ = memory.shape
    k = (memory @ p["wk"]).reshape(B, Sm, num_kv_heads, head_dim)
    v = (memory @ p["wv"]).reshape(B, Sm, num_kv_heads, head_dim)
    return {"k": k, "v": v}
