"""Two-tier collaborative MoE execution — the paper's workflow (Fig. 4),
decomposed into composable stages:

  probe    — land any in-flight reservations, service the router's top-k
             picks against the set-associative cache (demand bookkeeping,
             speculative-hit attribution) and bucket the step's
             assignments by unique expert (repro.core.cache, inside jit).
  execute  — *grouped*: the assignments run through an [G, C, D] dispatch
             buffer and the grouped Pallas kernels
             (repro.kernels.moe_gmm.ops.moe_ffn). Each unique expert's
             weights are read ONCE per step, from one tier — resident
             experts from the *device tier* (the [N*M, ...] cache slot
             buffer in fast memory), non-resident experts from the *host
             tier* (full expert table, host memory space on real
             hardware) — into one [G, ...] buffer per matrix that feeds
             both the kernels and the commit.
  commit   — state update + post-fetch: newly inserted experts' weights
             are copied from that buffer into their assigned cache slots,
             once per unique expert. The write feeds only *future* steps
             (no data path to this layer's output), so XLA overlaps the
             copy with downstream compute — the TPU analogue of the
             paper's second copy engine / dual CUDA streams.
  prefetch — speculative cross-layer pre-fetch (DAOP / Pre-gated style):
             reserve slots for the experts the *next* layer's router is
             predicted to pick and stream their weights in ahead of the
             next probe. Reservations are invisible until the next
             probe lands them, so a prefetch issued at layer *l* serves
             demand hits from layer *l+1* on — the live-path twin of the
             simulator's async fetch engine.

:func:`collaborative_moe` is the probe→execute→commit composition (no
prefetch); the serving engine drives the stages directly so it can overlap
the prefetch for layer *l+1* with layer *l*'s commit.

Every move of an expert's weights — the gather, the post-fetch and the
prefetch — copies whole [D, F] (or [F, D]) matrices, one contiguous slice
each, under a ``lax.cond`` per group (:func:`_move`): the move reads one
tier, and only when it happens. An XLA gather or scatter over the expert
axis is lowered into tiles and loops that run far below the chip's
memory bandwidth at published widths.

Each stage runs under a ``jax.named_scope`` — ``moe_probe``,
``moe_gather`` (the per-unique-expert weight gather), ``moe_dispatch``
(dispatch buffer and combine), ``moe_experts`` (the grouped kernels),
``moe_commit`` and ``moe_prefetch`` — so every HLO instruction a stage
emits names it in its ``op_name`` metadata, in the decode, segment and
warm-replay programs alike. Scopes are metadata: the compiled program is
the same without them.

The seed implementation executed every assignment separately (dense
per-assignment weight gathers + a vmapped single-row FFN) — it is retained
as :func:`collaborative_moe_reference` for parity tests and benchmarks.
Grouping also fixes a latent seed bug: when two concurrent requests picked
the same non-resident expert, the seed's second assignment was marked a
cache hit (the bookkeeping insert from the first assignment) and read the
*stale* slot buffer; the grouped path derives each unique expert's tier
from its residency *before* the step, so both assignments read the host
tier and compute correctly.

All state (CacheState + slot buffer) threads functionally through the
serving step; donate both so the updates are in-place on device.

TPU note: on real hardware ``host`` lives in pinned host memory
(``jax.device_put(..., TransferToMemoryKind("pinned_host"))``); on this CPU
container both tiers are ordinary buffers and the *cost model*
(repro.core.costmodel) carries the performance semantics.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import CacheConfig
from repro.kernels.moe_gmm.ops import moe_ffn
from repro.kernels.moe_gmm.ref import moe_ffn_ref
from . import cache as cache_lib

Params = Dict[str, jax.Array]


class ExpertTiers(NamedTuple):
    """The two memory tiers for one model's MoE expert weights.

    host_*: [L_moe, E, ...] — the full expert table (slow tier).
    slot_*: [N*M, ...]      — the device cache slot buffer (fast tier).
    state : CacheState      — tags/age/clock.
    """
    host_w1: jax.Array     # [L, E, D, F]
    host_w3: jax.Array
    host_w2: jax.Array     # [L, E, F, D]
    slot_w1: jax.Array     # [N*M, D, F]
    slot_w3: jax.Array
    slot_w2: jax.Array     # [N*M, F, D]
    state: cache_lib.CacheState

    @property
    def tables(self) -> Tuple[jax.Array, jax.Array, jax.Array]:
        return self.host_w1, self.host_w3, self.host_w2

    @property
    def slots(self) -> Tuple[jax.Array, jax.Array, jax.Array]:
        return self.slot_w1, self.slot_w3, self.slot_w2


def memory_kinds() -> Tuple[Optional[str], str]:
    """(host_kind, device_kind) for the literal two-tier placement.

    host_kind prefers ``pinned_host`` (TPU: host DRAM over PCIe) and falls
    back to ``unpinned_host``; None when the backend exposes no host space.
    device_kind is the backend's default memory. On this CPU container both
    resolve to ``unpinned_host`` — the placement degenerates to ordinary
    buffers but the program structure (and tests) stay identical.
    """
    dev = jax.devices()[0]
    kinds = {m.kind for m in dev.addressable_memories()}
    host = next((k for k in ("pinned_host", "unpinned_host") if k in kinds),
                None)
    return host, dev.default_memory().kind


def host_offload_supported() -> bool:
    return memory_kinds()[0] is not None


def offload_host_tier(tiers: ExpertTiers, device=None) -> ExpertTiers:
    """Place the host-tier expert table in the host memory space.

    This is the literal JAX expression of the paper's slow tier: the full
    expert table leaves accelerator HBM; hit-path reads touch only the
    HBM-resident slot buffers, miss-path reads stream over the host link.
    (Works on CPU and TPU backends; on TPU this is host DRAM over PCIe.)
    """
    from jax.sharding import SingleDeviceSharding
    host_kind, _ = memory_kinds()
    if host_kind is None:
        raise RuntimeError(
            "backend exposes no host memory space "
            "(need pinned_host or unpinned_host)")
    dev = device or jax.devices()[0]
    s = SingleDeviceSharding(dev, memory_kind=host_kind)
    return tiers._replace(
        host_w1=jax.device_put(tiers.host_w1, s),
        host_w3=jax.device_put(tiers.host_w3, s),
        host_w2=jax.device_put(tiers.host_w2, s),
    )


def init_tiers(host_w1, host_w3, host_w2, ccfg: CacheConfig,
               num_experts: int = 0, key=None) -> ExpertTiers:
    S = ccfg.num_slots
    D, F = host_w1.shape[-2], host_w1.shape[-1]
    state = cache_lib.init_cache_state(ccfg, num_experts, key)
    tiers = ExpertTiers(
        host_w1=host_w1, host_w3=host_w3, host_w2=host_w2,
        slot_w1=jnp.zeros((S, D, F), host_w1.dtype),
        slot_w3=jnp.zeros((S, D, F), host_w3.dtype),
        slot_w2=jnp.zeros((S, F, D), host_w2.dtype),
        state=state,
    )
    if ccfg.policy == "random":
        # static placement: preload the pinned experts once
        tiers = _preload_static(tiers, ccfg)
    return tiers


def _preload_static(tiers: ExpertTiers, ccfg: CacheConfig) -> ExpertTiers:
    n, m = ccfg.num_indexes, ccfg.num_ways
    layers = jnp.repeat(jnp.arange(n), m)
    experts = tiers.state.tags.reshape(-1)
    w1 = tiers.host_w1[layers, experts]
    w3 = tiers.host_w3[layers, experts]
    w2 = tiers.host_w2[layers, experts]
    return tiers._replace(slot_w1=w1, slot_w3=w3, slot_w2=w2)


def _ffn_one(w1, w3, w2, x):
    """SwiGLU expert FFN for one token row x: [D]."""
    h = jax.nn.silu(x @ w1) * (x @ w3)
    return h @ w2


def _group_by_expert(flat_e: jax.Array, num_experts: int
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Bucket assignments by expert id (sort-based, static shapes).

    flat_e: [A] int32 (−1 = masked). Returns (gid [A] — group index per
    assignment, pos [A] — row within the group's capacity, rep_e [G] —
    expert id per group, padded groups get −1). The group axis is
    G = min(A, E+1): at most A distinct picks and at most E experts plus
    one group of masked (−1) assignments, which sort first into group 0.
    Group capacity stays A (worst case: every assignment picks the same
    expert), so the dispatch buffer is [G, A, D].
    """
    A = flat_e.shape[0]
    G = min(A, num_experts + 1)
    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), se[1:] != se[:-1]]) if A > 1 else \
        jnp.ones((1,), bool)
    gid_sorted = jnp.cumsum(first) - 1
    seg_start = jax.lax.cummax(jnp.where(first, jnp.arange(A), 0))
    pos_sorted = jnp.arange(A) - seg_start
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(A))
    rep_e = jnp.full((G,), -1, flat_e.dtype).at[gid_sorted].set(
        se, mode="drop")
    return gid_sorted[inv], pos_sorted[inv], rep_e


class ProbeResult(NamedTuple):
    """Everything probe() learned about one layer's demand picks.

    state    — post-access cache bookkeeping (landed, tags/age/flags
               updated); commit() installs it.
    hits     — [A] reported demand hits (in-flight reservations miss).
    spec_hits— [A] demand hits manufactured by a landed reservation.
    valid    — [A] unmasked assignments (active row, expert >= 0).
    flat_e   — [A] expert id per assignment (-1 = masked).
    gid/pos  — [A] dispatch coordinates (group index / row in group).
    rep_e    — [G] unique expert id per group (-1 = padded group).
    resident — [G] group residency at probe time: execute() reads these
               groups from the slot buffer, the rest from the host tier.
    res_way  — [G] way of resident groups.
    """
    state: cache_lib.CacheState
    hits: jax.Array
    spec_hits: jax.Array
    valid: jax.Array
    flat_e: jax.Array
    gid: jax.Array
    pos: jax.Array
    rep_e: jax.Array
    resident: jax.Array
    res_way: jax.Array


@jax.named_scope("moe_probe")
def probe(tiers: ExpertTiers, layer: jax.Array, top_i: jax.Array,
          ccfg: CacheConfig,
          active: Optional[jax.Array] = None) -> ProbeResult:
    """Stage 1 — cache check + grouping for one layer's top-k picks.

    Lands outstanding reservations first (one probe boundary = one
    transfer deadline), services the demand access, and buckets the
    step's assignments by unique expert for the grouped kernels.
    Residency for *execution* is probed against the landed PRE-access
    state: a slot claimed this step holds its weights only from the next
    step on (the post-fetch is off the critical path)."""
    T, K = top_i.shape
    flat_e = top_i.reshape(-1).astype(jnp.int32)
    if active is not None:
        flat_e = jnp.where(jnp.repeat(active, K), flat_e, -1)
    valid = flat_e >= 0
    state0 = cache_lib.land(tiers.state)
    new_state, hits, _, spec_hits = cache_lib.access_ex(
        state0, layer, flat_e, ccfg.policy)
    gid, pos, rep_e = _group_by_expert(flat_e, tiers.host_w1.shape[1])
    resident, res_way = cache_lib.lookup(state0, layer, rep_e)
    return ProbeResult(state=new_state, hits=hits, spec_hits=spec_hits,
                       valid=valid, flat_e=flat_e, gid=gid, pos=pos,
                       rep_e=rep_e, resident=resident, res_way=res_way)


@jax.jit
def _move(dst: Tuple[jax.Array, ...], i, src: Optional[Tuple[jax.Array, ...]],
          j: Tuple, pred) -> Tuple[jax.Array, ...]:
    """``d[i] = s[j]`` for each matrix pair of ``dst`` and ``src`` where
    ``pred``, else ``dst`` unchanged; ``src`` None writes zeros.

    Whole matrices move: ``j`` indexes each source's leading axes, ``i``
    each destination's first, and the trailing two axes are copied whole
    by a ``dynamic_slice`` and a ``dynamic_update_slice`` — contiguous, at
    the memory's bandwidth. The ``lax.cond`` reads ``src`` only when the
    move happens (a select would read it always) and updates ``dst`` in
    place. Jitted so that un-jitted callers compile each shape's cond
    once."""
    def row(d, s):
        if s is None:
            return jnp.zeros((1,) + d.shape[1:], d.dtype)
        return jax.lax.dynamic_slice(
            s, (*j, 0, 0), (1,) * len(j) + s.shape[len(j):]
        ).reshape((1,) + s.shape[len(j):])

    def put(ds):
        return tuple(jax.lax.dynamic_update_slice(d, row(d, s), (i, 0, 0))
                     for d, s in zip(ds, src or (None,) * len(ds)))
    return jax.lax.cond(pred, put, lambda ds: ds, tuple(dst))


@jax.named_scope("moe_gather")
def _gather_group_weights(tiers: ExpertTiers, layer, pr: ProbeResult,
                          ccfg: CacheConfig):
    """Each unique expert's weights, read once and from one tier: a
    resident group's from its slot (fast tier), any other group's from the
    host table (slow tier), one ``_move`` each; padded groups
    (``rep_e < 0``) read nothing and are zeroed, so that their rows
    compute zeros. Every row is written exactly once, so the buffers start
    uninitialized (``lax.empty``): no fill. Returns one [G, D, F] /
    [G, F, D] buffer per matrix: the kernels' operand and the post-fetch's
    source (a resident slot holds its table row's values)."""
    G = pr.rep_e.shape[0]
    slots = cache_lib.slot_id(layer, pr.res_way, ccfg.num_ways)
    from_table = ~pr.resident & (pr.rep_e >= 0)
    w = tuple(jax.lax.empty((G,) + t.shape[2:], t.dtype)
              for t in tiers.tables)
    for g in range(G):
        w = _move(w, g, tiers.slots, (slots[g],), pr.resident[g])
        w = _move(w, g, tiers.tables, (layer, pr.rep_e[g]), from_table[g])
        w = _move(w, g, None, (), pr.rep_e[g] < 0)
    return w


@jax.named_scope("moe_dispatch")
def _stage_dispatch(x: jax.Array, K: int, pr: ProbeResult
                    ) -> Tuple[jax.Array, jax.Array]:
    """Assemble the [G, A, D] per-unique-expert dispatch buffer for one
    layer's assignments. Returns (tok [A] — token row per assignment,
    xbuf). ONE copy of this math feeds execute(), the offloaded variant
    and the hostexec dispatcher — the bit-exactness contracts between
    those paths ride on it."""
    T = x.shape[0]
    tok = jnp.repeat(jnp.arange(T), K)
    xa = x[tok]                                            # [A, D]
    A, G = pr.flat_e.shape[0], pr.rep_e.shape[0]
    xbuf = jnp.zeros((G, A, x.shape[-1]), x.dtype).at[pr.gid, pr.pos].set(xa)
    return tok, xbuf


@jax.named_scope("moe_experts")
def experts(xbuf: jax.Array, w) -> jax.Array:
    """The grouped SwiGLU kernels over the dispatch buffer, under the
    ``moe_experts`` scope; the kernels keep their own instruction name
    (``moe_ffn``)."""
    return moe_ffn(xbuf, *w)


def execute(tiers: ExpertTiers, layer: jax.Array, x: jax.Array,
            top_w: jax.Array, pr: ProbeResult, ccfg: CacheConfig
            ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array, jax.Array]]:
    """Stage 2 — grouped tiered execution through the gmm kernels.

    Returns (y [T, D], the step's unique experts' weights — reused by
    commit()'s post-fetch so each expert's weights are read once per
    step)."""
    T, K = top_w.shape
    tok, xbuf = _stage_dispatch(x, K, pr)
    w = _gather_group_weights(tiers, layer, pr, ccfg)
    ybuf = experts(xbuf, w)                                # [G, A, D]
    y = _combine(ybuf, pr.gid, pr.pos, tok, top_w, pr.valid, T, x.dtype)
    return y, w


@jax.named_scope("moe_commit")
def commit(tiers: ExpertTiers, layer: jax.Array, pr: ProbeResult, w,
           ccfg: CacheConfig) -> Tuple[ExpertTiers, jax.Array]:
    """Stage 3 — install the probe's cache state and post-fetch the newly
    inserted experts' weights ``w`` (execute()'s [G, ...] buffers) into
    their slots (async-schedulable: no data path back to this layer's
    output). Returns (tiers, fetch [G] bool)."""
    s_w1, s_w3, s_w2, fetch = _post_fetch(
        tiers, layer, pr.rep_e, pr.resident, pr.res_way, pr.state, w, ccfg)
    tiers = tiers._replace(slot_w1=s_w1, slot_w3=s_w3, slot_w2=s_w2,
                           state=pr.state)
    return tiers, fetch


def prediction_votes(flat_p: jax.Array) -> jax.Array:
    """Cross-batch vote count per predicted pick.

    flat_p: [A] int32 (-1 = masked). Votes are pairwise equality counts:
    an expert predicted by V assignments scores V on each of its picks;
    masked picks score 0. The count is the reservation's retention rank —
    :func:`prefetch` passes it as ``reserve``'s age-stamp priority, so
    when a later eviction must take a reserved way it takes the
    least-voted reservation first. Deliberately NOT an insertion reorder:
    claims are first-come-first-served and the demand probes that land
    reservations run in the same row order the picks arrive in, so
    reordering picks misaligns the claimed set from the earliest probes
    (measured: reordering by votes in either direction LOSES speculative
    hits on the live fig6 workload; priority-stamping gains them)."""
    valid = flat_p >= 0
    votes = ((flat_p[:, None] == flat_p[None, :])
             & valid[:, None] & valid[None, :]).sum(-1)
    return votes.astype(jnp.int32)


@jax.named_scope("moe_prefetch")
def prefetch(tiers: ExpertTiers, layer: jax.Array, pred_i: jax.Array,
             ccfg: CacheConfig, active: Optional[jax.Array] = None,
             rank_votes: bool = False
             ) -> Tuple[ExpertTiers, jax.Array, jax.Array, jax.Array]:
    """Stage 4 — speculative cross-layer prefetch into reserved slots.

    pred_i: [T, K] *predicted* expert picks for ``layer`` (typically the
    next layer's router run on the current hidden state). Reserves slots
    with policy-correct eviction but no demand accounting, then writes the
    issued experts' host-tier weights into the claimed slots, once per
    unique predicted expert. The reservations stay in-flight until the
    next probe lands them — a same-step probe still reads the host tier.

    ``rank_votes`` ranks the reservations by cross-batch vote count (see
    :func:`prediction_votes`): an expert several rows predict keeps its
    way longer than a single row's pick — batch-aware retention priority,
    computed after the ``active`` fold so padded rows never vote. Claim
    order is untouched (reordering picks misaligns the claimed set from
    the demand probes' row order — measured loss).

    Returns (tiers, rep_p [G] unique predicted expert per group,
    issued [G] bool — groups whose reservation claimed a slot (one host
    fetch each), n_issued scalar)."""
    T, K = pred_i.shape
    flat_p = pred_i.reshape(-1).astype(jnp.int32)
    if active is not None:
        flat_p = jnp.where(jnp.repeat(active, K), flat_p, -1)
    priority = prediction_votes(flat_p) if rank_votes else None
    new_state, issued_a, ways_a = cache_lib.reserve(
        tiers.state, layer, flat_p, ccfg.policy, priority=priority)
    gid, _, rep_p = _group_by_expert(flat_p, tiers.host_w1.shape[1])
    G = rep_p.shape[0]
    # duplicates of one expert reserve at most once, so at most one pick
    # per group carries issued=True — fold picks onto their groups
    issued = jnp.zeros((G,), bool).at[gid].max(issued_a)
    way = jnp.zeros((G,), jnp.int32).at[gid].add(
        jnp.where(issued_a, ways_a, 0))
    # stream the issued experts' weights into the reserved slots (the
    # speculative transfer the in-flight flag models; next probe lands it)
    dst = cache_lib.slot_id(layer, way, ccfg.num_ways)
    slots = tiers.slots
    for g in range(G):
        slots = _move(slots, dst[g], tiers.tables, (layer, rep_p[g]),
                      issued[g])
    tiers = tiers._replace(slot_w1=slots[0], slot_w3=slots[1],
                           slot_w2=slots[2], state=new_state)
    return tiers, rep_p, issued, issued_a.sum()


def _post_fetch(tiers: ExpertTiers, layer, rep_e, resident, res_way,
                new_state, w, ccfg: CacheConfig):
    """Write inserted experts' weights (group g's row of each buffer in
    ``w``) into their slots, once per unique expert. Probes the POST-step
    state: an expert is fetched iff its final (expert -> way) mapping is
    not already backed by the buffer — newly resident, or evicted-and-
    reinserted at a different way within the step (possible when picks
    exceed the ways). An expert inserted then evicted within the same step
    is correctly skipped. Fetched experts hold distinct ways, so the
    writes never overlap. Output `y` never reads these writes."""
    new_res, new_way = cache_lib.lookup(new_state, layer, rep_e)
    fetch = new_res & ~(resident & (new_way == res_way))
    dst = cache_lib.slot_id(layer, new_way, ccfg.num_ways)
    slots = tiers.slots
    for g in range(rep_e.shape[0]):
        slots = _move(slots, dst[g], w, (g,), fetch[g])
    return (*slots, fetch)


@jax.named_scope("moe_dispatch")
def _combine(ybuf, gid, pos, tok, top_w, valid, T, x_dtype):
    ya = ybuf[gid, pos]
    scale = top_w.reshape(-1) * valid.astype(jnp.float32)
    ya = ya * scale[:, None].astype(ya.dtype)
    return jnp.zeros((T, ybuf.shape[-1]), x_dtype).at[tok].add(ya) \
        .astype(x_dtype)


def _stats(pr: ProbeResult, fetch):
    return {
        "hits": pr.hits.sum(),
        "accesses": pr.valid.sum().astype(jnp.int32),
        "host_flops_assignments": (pr.valid & ~pr.hits).sum(),
        "fetched_experts": fetch.sum(),
        "prefetch_hits": pr.spec_hits.sum(),
    }


def collaborative_moe(tiers: ExpertTiers, layer: jax.Array, x: jax.Array,
                      top_i: jax.Array, top_w: jax.Array, ccfg: CacheConfig,
                      active: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, ExpertTiers, Dict[str, jax.Array]]:
    """Execute one MoE layer for a decode micro-batch through the tiers —
    the probe → execute → commit composition (no prefetch stage; the
    serving engine drives the stages itself to interleave prefetch).

    x: [T, D]; top_i/top_w: [T, K]. layer: traced scalar (the scan
    counter). active: optional [T] bool — rows of padded scheduler slots
    are masked out of the cache, the stats and the output when False.
    Returns (y [T, D], updated tiers, stats).
    """
    pr = probe(tiers, layer, top_i, ccfg, active=active)
    y, w = execute(tiers, layer, x, top_w, pr, ccfg)
    tiers, fetch = commit(tiers, layer, pr, w, ccfg)
    return y, tiers, _stats(pr, fetch)


def collaborative_moe_offloaded(tiers: ExpertTiers, layer: jax.Array,
                                x: jax.Array, top_i: jax.Array,
                                top_w: jax.Array, ccfg: CacheConfig,
                                active: Optional[jax.Array] = None
                                ) -> Tuple[jax.Array, ExpertTiers,
                                           Dict[str, jax.Array]]:
    """The paper's workflow with *literal* memory-space semantics.

    Requires ``offload_host_tier(tiers)`` first (host weights in the host
    memory space). Then, inside one jitted step:
      * non-resident experts' grouped FFNs execute under
        ``compute_on("device_host")`` reading host-space weights — the
        paper's CPU compute;
      * the dispatch buffer crosses to host and the results cross back —
        the paper's 0.11 ms activation round-trip;
      * post-fetch gathers newly inserted experts' weights host-side (once
        per unique expert) and device_puts them into the cache slot
        buffers — the paper's asynchronous PCIe weight copy (XLA schedules
        it off the output's critical path exactly as in the default
        implementation).

    Same numerics as :func:`collaborative_moe` (tested); use this variant
    on hardware where the host tier genuinely does not fit HBM. Resident
    groups run through the same grouped gmm kernels as the default path;
    host groups use the jnp oracle (Pallas does not lower to the host
    compute stream).
    """
    from jax.experimental.compute_on import compute_on
    from jax.sharding import SingleDeviceSharding

    # single-device serving path (the paper's setting); must run under
    # jit — memory-space transfers are compile-time placements
    host_kind, dev_kind = memory_kinds()
    if host_kind is None:
        raise RuntimeError("backend exposes no host memory space")
    dev = jax.devices()[0]
    host_s = SingleDeviceSharding(dev, memory_kind=host_kind)
    dev_s = SingleDeviceSharding(dev, memory_kind=dev_kind)

    # shared staged preamble: cache check + grouping (stage 1)
    T, K = top_i.shape
    pr = probe(tiers, layer, top_i, ccfg, active=active)
    gid, pos, rep_e = pr.gid, pr.pos, pr.rep_e
    resident, way = pr.resident, pr.res_way

    tok, xbuf = _stage_dispatch(x, K, pr)
    slots = jnp.where(resident,
                      cache_lib.slot_id(layer, jnp.maximum(way, 0),
                                        ccfg.num_ways), 0)
    e_ix = jnp.maximum(rep_e, 0)

    # device path (resident groups): reads only the HBM slot buffers
    ybuf_dev = moe_ffn(xbuf, tiers.slot_w1[slots], tiers.slot_w3[slots],
                       tiers.slot_w2[slots])

    # host path (non-resident groups): dispatch buffer crosses to host,
    # the grouped FFN runs there against host-space weights
    @compute_on("device_host")
    @jax.jit
    def host_groups(hw1, hw3, hw2, xh, eh, lh):
        # two-step indexing: mixed-space index broadcasting inside
        # compute_on trips XLA; dynamic layer slice + row gather doesn't
        w1 = jax.lax.dynamic_index_in_dim(hw1, lh, 0, keepdims=False)[eh]
        w3 = jax.lax.dynamic_index_in_dim(hw3, lh, 0, keepdims=False)[eh]
        w2 = jax.lax.dynamic_index_in_dim(hw2, lh, 0, keepdims=False)[eh]
        return moe_ffn_ref(xh, w1, w3, w2)

    xb_h = jax.device_put(xbuf, host_s)
    e_h = jax.device_put(e_ix, host_s)
    l_h = jax.device_put(layer, host_s)
    ybuf_host = jax.device_put(
        host_groups(tiers.host_w1, tiers.host_w3, tiers.host_w2,
                    xb_h, e_h, l_h), dev_s)
    ybuf = jnp.where(resident[:, None, None], ybuf_dev, ybuf_host)
    y = _combine(ybuf, gid, pos, tok, top_w, pr.valid, T, x.dtype)

    # post-fetch: host-side gather of the newly inserted experts (once per
    # unique expert), then the explicit host->device copy into the slots
    @compute_on("device_host")
    @jax.jit
    def host_gather(hw, eh, lh):
        return jax.lax.dynamic_index_in_dim(hw, lh, 0, keepdims=False)[eh]

    src1 = jax.device_put(host_gather(tiers.host_w1, e_h, l_h), dev_s)
    src3 = jax.device_put(host_gather(tiers.host_w3, e_h, l_h), dev_s)
    src2 = jax.device_put(host_gather(tiers.host_w2, e_h, l_h), dev_s)
    tiers, fetch = commit(tiers, layer, pr, (src1, src3, src2), ccfg)
    return y, tiers, _stats(pr, fetch)


def collaborative_moe_reference(tiers: ExpertTiers, layer: jax.Array,
                                x: jax.Array, top_i: jax.Array,
                                top_w: jax.Array, ccfg: CacheConfig
                                ) -> Tuple[jax.Array, ExpertTiers,
                                           Dict[str, jax.Array]]:
    """The seed per-assignment path: dense dual gathers + vmapped
    single-row FFNs + a sequential post-fetch scan. Kept as the parity
    oracle and benchmark baseline for :func:`collaborative_moe` — do not
    use in serving code. (Known limitation, inherited: duplicate picks of
    a non-resident expert across concurrent tokens read the stale slot
    buffer — the grouped path fixes this.)
    """
    T, K = top_i.shape
    A = T * K
    flat_e = top_i.reshape(-1)

    new_state, hits, ways = cache_lib.access_scan_reference(
        tiers.state, layer, flat_e, ccfg.policy)
    slots = cache_lib.slot_id(layer, jnp.maximum(ways, 0), ccfg.num_ways)
    slots = jnp.where(ways >= 0, slots, 0)

    tok = jnp.repeat(jnp.arange(T), K)
    xa = x[tok]                                            # [A, D]
    w1_dev = tiers.slot_w1[slots]
    w3_dev = tiers.slot_w3[slots]
    w2_dev = tiers.slot_w2[slots]
    w1_host = tiers.host_w1[layer, flat_e]
    w3_host = tiers.host_w3[layer, flat_e]
    w2_host = tiers.host_w2[layer, flat_e]

    y_dev = jax.vmap(_ffn_one)(w1_dev, w3_dev, w2_dev, xa)      # GPU path
    y_host = jax.vmap(_ffn_one)(w1_host, w3_host, w2_host, xa)  # CPU path
    ya = jnp.where(hits[:, None], y_dev, y_host)
    ya = ya * top_w.reshape(-1)[:, None].astype(ya.dtype)
    y = jnp.zeros_like(x).at[tok].add(ya)

    do_fetch = (~hits) & (ways >= 0)

    def fetch(carry, inp):
        s_w1, s_w3, s_w2 = carry
        slot, e, do = inp
        src1 = tiers.host_w1[layer, e]
        src3 = tiers.host_w3[layer, e]
        src2 = tiers.host_w2[layer, e]
        upd = lambda buf, src: jax.lax.dynamic_update_index_in_dim(
            buf, jnp.where(do, src, buf[slot]), slot, 0)
        return (upd(s_w1, src1), upd(s_w3, src3), upd(s_w2, src2)), None

    (s_w1, s_w3, s_w2), _ = jax.lax.scan(
        fetch, (tiers.slot_w1, tiers.slot_w3, tiers.slot_w2),
        (slots, flat_e, do_fetch))

    stats = {
        "hits": hits.sum(),
        "accesses": jnp.asarray(A, jnp.int32),
        "host_flops_assignments": (~hits).sum(),
        "fetched_experts": do_fetch.sum(),
    }
    tiers = tiers._replace(slot_w1=s_w1, slot_w3=s_w3, slot_w2=s_w2,
                           state=new_state)
    return y, tiers, stats
