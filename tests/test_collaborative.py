"""Two-tier collaborative MoE execution: correctness + async-schedulability."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import CacheConfig
from repro.core import cache as cache_lib
from repro.core import collaborative as collab
from repro.core.cache import init_cache_state


def _tiers(key, L=3, E=4, D=16, F=32, ccfg=None, policy="lru"):
    ks = jax.random.split(key, 3)
    ccfg = ccfg or CacheConfig(num_indexes=2, num_ways=2, policy=policy)
    w1 = jax.random.normal(ks[0], (L, E, D, F), jnp.float32) * 0.1
    w3 = jax.random.normal(ks[1], (L, E, D, F), jnp.float32) * 0.1
    w2 = jax.random.normal(ks[2], (L, E, F, D), jnp.float32) * 0.1
    return collab.init_tiers(w1, w3, w2, ccfg, num_experts=E,
                             key=jax.random.PRNGKey(7)), ccfg


def _dense_ref(tiers, layer, x, top_i, top_w):
    """Reference: plain MoE with the host-tier weights."""
    T, K = top_i.shape
    y = np.zeros_like(np.asarray(x))
    for t in range(T):
        for k in range(K):
            e = int(top_i[t, k])
            w1 = np.asarray(tiers.host_w1[layer, e])
            w3 = np.asarray(tiers.host_w3[layer, e])
            w2 = np.asarray(tiers.host_w2[layer, e])
            xt = np.asarray(x[t])
            h = (xt @ w1) / (1 + np.exp(-(xt @ w1))) * (xt @ w3)
            y[t] += float(top_w[t, k]) * (h @ w2)
    return y


@pytest.mark.parametrize("policy", ["lru", "fifo", "random"])
def test_collaborative_output_matches_dense_reference(policy):
    """Hit path, miss path, and mixed must all produce the SAME math as a
    plain MoE layer — the tiers change where weights are read, never the
    result (the paper's no-accuracy-tradeoff claim)."""
    key = jax.random.PRNGKey(0)
    tiers, ccfg = _tiers(key, policy=policy)
    x = jax.random.normal(key, (2, 16), jnp.float32)
    top_i = jnp.asarray([[0, 1], [2, 3]])
    top_w = jnp.asarray([[0.6, 0.4], [0.5, 0.5]], jnp.float32)
    for layer in (0, 1, 2):   # covered cold, covered, beyond coverage
        for rep in range(3):  # cold -> warm transitions
            y, tiers, stats = collab.collaborative_moe(
                tiers, jnp.int32(layer), x, top_i, top_w, ccfg)
            ref = _dense_ref(tiers, layer, x, top_i, top_w)
            np.testing.assert_allclose(np.asarray(y), ref, rtol=2e-4,
                                       atol=2e-4)


def test_post_fetch_populates_cache_for_next_step():
    key = jax.random.PRNGKey(1)
    tiers, ccfg = _tiers(key)
    x = jax.random.normal(key, (1, 16), jnp.float32)
    ti = jnp.asarray([[0, 1]])
    tw = jnp.asarray([[0.5, 0.5]], jnp.float32)
    _, tiers, s0 = collab.collaborative_moe(tiers, jnp.int32(0), x, ti, tw, ccfg)
    assert int(s0["hits"]) == 0 and int(s0["fetched_experts"]) == 2
    _, tiers, s1 = collab.collaborative_moe(tiers, jnp.int32(0), x, ti, tw, ccfg)
    assert int(s1["hits"]) == 2 and int(s1["fetched_experts"]) == 0
    # slot buffer now holds the actual expert weights
    tags = np.asarray(tiers.state.tags[0])
    for way, e in enumerate(tags):
        if e >= 0:
            np.testing.assert_array_equal(
                np.asarray(tiers.slot_w1[0 * ccfg.num_ways + way]),
                np.asarray(tiers.host_w1[0, e]))


def test_post_fetch_is_async_schedulable():
    """The paper's dual-copy-engine overlap maps to XLA scheduling freedom:
    the layer output must NOT data-depend on the slot-buffer update. We
    check this structurally: with the new slot buffers replaced by zeros,
    the output y is unchanged."""
    key = jax.random.PRNGKey(2)
    tiers, ccfg = _tiers(key)
    x = jax.random.normal(key, (1, 16), jnp.float32)
    ti = jnp.asarray([[0, 1]])
    tw = jnp.asarray([[0.5, 0.5]], jnp.float32)
    y1, t1, _ = collab.collaborative_moe(tiers, jnp.int32(0), x, ti, tw, ccfg)
    zeroed = tiers._replace(slot_w1=jnp.zeros_like(tiers.slot_w1),
                            slot_w3=jnp.zeros_like(tiers.slot_w3),
                            slot_w2=jnp.zeros_like(tiers.slot_w2))
    y2, _, _ = collab.collaborative_moe(zeroed, jnp.int32(0), x, ti, tw, ccfg)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2))


def test_offloaded_path_matches_default():
    """The host-space + compute_on("device_host") variant — the literal
    memory-space form of the paper's workflow — computes identically to
    the default path, across hit/miss/post-fetch transitions. Backends
    without pinned_host fall back to unpinned_host (this CPU container);
    backends with no host space at all skip."""
    if not collab.host_offload_supported():
        pytest.skip("backend exposes no host memory space")
    host_kind, _ = collab.memory_kinds()
    key = jax.random.PRNGKey(5)
    tiers, ccfg = _tiers(key)
    off = collab.offload_host_tier(tiers)
    assert off.host_w1.sharding.memory_kind == host_kind
    x = jax.random.normal(key, (2, 16), jnp.float32)
    ti = jnp.asarray([[0, 1], [2, 3]])
    tw = jnp.asarray([[0.5, 0.5], [0.6, 0.4]], jnp.float32)
    # memory-space transfers are compile-time placements: jit required
    step_off = jax.jit(lambda t, l, x, ti, tw:
                       collab.collaborative_moe_offloaded(t, l, x, ti, tw,
                                                          ccfg))
    for layer in (0, 1, 2):          # covered cold/warm + beyond coverage
        for rep in range(2):
            y_ref, tiers, s_ref = collab.collaborative_moe(
                tiers, jnp.int32(layer), x, ti, tw, ccfg)
            y_off, off, s_off = step_off(off, jnp.int32(layer), x, ti, tw)
            np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_off),
                                       rtol=1e-5, atol=1e-5)
            assert int(s_ref["hits"]) == int(s_off["hits"])
    # slot buffers converged identically through post-fetches
    np.testing.assert_allclose(np.asarray(tiers.slot_w1),
                               np.asarray(off.slot_w1), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("policy", ["lru", "fifo", "random"])
def test_grouped_matches_seed_per_assignment_path(policy):
    """Parity: the grouped gmm-backed execution must match the retained
    seed per-assignment path numerically across cold/warm/beyond-coverage
    transitions (f32 weights -> tight tolerance), on traces without
    duplicate picks (where the seed path is well-defined)."""
    key = jax.random.PRNGKey(11)
    tiers_g, ccfg = _tiers(key, policy=policy)
    tiers_r, _ = _tiers(key, ccfg=ccfg, policy=policy)
    rng = np.random.default_rng(0)
    x = jax.random.normal(key, (2, 16), jnp.float32)
    tw = jnp.asarray([[0.6, 0.4], [0.5, 0.5]], jnp.float32)
    for layer in (0, 1, 2):
        for rep in range(3):
            picks = rng.permutation(4)[:4].reshape(2, 2)   # dup-free
            ti = jnp.asarray(picks)
            y_g, tiers_g, s_g = collab.collaborative_moe(
                tiers_g, jnp.int32(layer), x, ti, tw, ccfg)
            y_r, tiers_r, s_r = collab.collaborative_moe_reference(
                tiers_r, jnp.int32(layer), x, ti, tw, ccfg)
            np.testing.assert_allclose(np.asarray(y_g), np.asarray(y_r),
                                       rtol=1e-5, atol=1e-5)
            for k in ("hits", "accesses", "host_flops_assignments"):
                assert int(s_g[k]) == int(s_r[k]), (k, layer, rep)
            # the grouped post-fetch copies only experts that survive the
            # step (the seed also copied within-step evictions): <=
            assert int(s_g["fetched_experts"]) <= int(s_r["fetched_experts"])
            np.testing.assert_allclose(np.asarray(tiers_g.slot_w1),
                                       np.asarray(tiers_r.slot_w1),
                                       rtol=1e-6, atol=1e-6)
            assert np.array_equal(np.asarray(tiers_g.state.tags),
                                  np.asarray(tiers_r.state.tags))


def test_grouped_handles_duplicate_picks_across_tokens():
    """Two concurrent tokens picking the same cold expert: the grouped
    path computes both from the host tier (the seed path read the stale
    slot buffer for the second — the bookkeeping insert of the first
    masqueraded as a cache hit)."""
    key = jax.random.PRNGKey(0)
    tiers, ccfg = _tiers(key)
    x = jax.random.normal(key, (2, 16), jnp.float32)
    ti = jnp.asarray([[0, 1], [0, 2]])                     # expert 0 twice
    tw = jnp.asarray([[0.6, 0.4], [0.5, 0.5]], jnp.float32)
    y, tiers, stats = collab.collaborative_moe(
        tiers, jnp.int32(0), x, ti, tw, ccfg)
    ref = _dense_ref(tiers, 0, x, ti, tw)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=2e-4, atol=2e-4)
    # bookkeeping keeps the paper's sequential-semantics hit counter; the
    # post-fetch copies only the experts resident AFTER the step (expert 1
    # is inserted then evicted within the step -> not copied)
    assert int(stats["hits"]) == 1 and int(stats["fetched_experts"]) == 2


def test_active_mask_excludes_padded_rows():
    """Inactive rows (padded scheduler slots) produce zero output, leave
    the cache untouched and are excluded from the stats."""
    key = jax.random.PRNGKey(4)
    tiers, ccfg = _tiers(key)
    x = jax.random.normal(key, (2, 16), jnp.float32)
    ti = jnp.asarray([[0, 1], [2, 3]])
    tw = jnp.asarray([[0.5, 0.5], [0.5, 0.5]], jnp.float32)
    active = jnp.asarray([True, False])
    y, tiers, stats = collab.collaborative_moe(
        tiers, jnp.int32(0), x, ti, tw, ccfg, active=active)
    assert int(stats["accesses"]) == 2 and int(stats["fetched_experts"]) == 2
    assert (np.asarray(y[1]) == 0).all()
    tags = set(np.asarray(tiers.state.tags[0]).tolist())
    assert 2 not in tags and 3 not in tags                  # row 1 masked
    ref = _dense_ref(tiers, 0, x, ti, tw)
    np.testing.assert_allclose(np.asarray(y[0]), ref[0], rtol=2e-4,
                               atol=2e-4)


def test_static_random_preload():
    key = jax.random.PRNGKey(3)
    ccfg = CacheConfig(num_indexes=3, num_ways=2, policy="random")
    tiers, _ = _tiers(key, ccfg=ccfg, policy="random")
    tags = np.asarray(tiers.state.tags)
    for l in range(3):
        for w in range(2):
            e = int(tags[l, w])
            np.testing.assert_array_equal(
                np.asarray(tiers.slot_w1[l * 2 + w]),
                np.asarray(tiers.host_w1[l, e]))


# ---------------------------------------------------------------------------
# slice moves vs the gather/scatter formulation they replaced
# ---------------------------------------------------------------------------

def _oracle_moe(tiers, layer, x, top_i, top_w, ccfg, active=None):
    """One layer through the expert cache as XLA gathers and scatters over
    the expert axis: the host table gathered for every group, resident
    slot rows scattered over it, and the post-fetch scattered from the
    host gather. The parity oracle of the slice moves."""
    pr = collab.probe(tiers, layer, top_i, ccfg, active=active)
    T, K = top_w.shape
    G = pr.rep_e.shape[0]
    slot_bufs = (tiers.slot_w1, tiers.slot_w3, tiers.slot_w2)
    e_ix = jnp.maximum(pr.rep_e, 0)
    host_w = (tiers.host_w1[layer, e_ix], tiers.host_w3[layer, e_ix],
              tiers.host_w2[layer, e_ix])
    res_g = jnp.nonzero(pr.resident, size=min(ccfg.num_ways, G),
                        fill_value=G)[0]
    way = pr.res_way[jnp.minimum(res_g, G - 1)]
    slots = cache_lib.slot_id(layer, jnp.maximum(way, 0), ccfg.num_ways)
    w = tuple(h.at[res_g].set(s[slots], mode="drop")
              for h, s in zip(host_w, slot_bufs))
    tok, xbuf = collab._stage_dispatch(x, K, pr)
    y = collab._combine(collab.experts(xbuf, w), pr.gid, pr.pos, tok,
                        top_w, pr.valid, T, x.dtype)
    new_res, new_way = cache_lib.lookup(pr.state, layer, pr.rep_e)
    fetch = new_res & ~(pr.resident & (new_way == pr.res_way))
    dst = jnp.where(fetch,
                    cache_lib.slot_id(layer, new_way, ccfg.num_ways),
                    tiers.slot_w1.shape[0])
    s1, s3, s2 = (s.at[dst].set(h, mode="drop")
                  for s, h in zip(slot_bufs, host_w))
    tiers = tiers._replace(slot_w1=s1, slot_w3=s3, slot_w2=s2,
                           state=pr.state)
    return y, tiers, collab._stats(pr, fetch), pr, fetch


def _oracle_prefetch(tiers, layer, pred_i, ccfg):
    """collab.prefetch with its slot writes as one scatter per matrix."""
    flat_p = pred_i.reshape(-1).astype(jnp.int32)
    state, issued_a, ways_a = cache_lib.reserve(tiers.state, layer, flat_p,
                                                ccfg.policy)
    gid, _, rep_p = collab._group_by_expert(flat_p, tiers.host_w1.shape[1])
    G = rep_p.shape[0]
    issued = jnp.zeros((G,), bool).at[gid].max(issued_a)
    way = jnp.zeros((G,), jnp.int32).at[gid].add(
        jnp.where(issued_a, ways_a, 0))
    dst = jnp.where(issued, cache_lib.slot_id(layer, way, ccfg.num_ways),
                    tiers.slot_w1.shape[0])
    e_ix = jnp.maximum(rep_p, 0)
    s1, s3, s2 = (s.at[dst].set(t[layer, e_ix], mode="drop")
                  for s, t in ((tiers.slot_w1, tiers.host_w1),
                               (tiers.slot_w3, tiers.host_w3),
                               (tiers.slot_w2, tiers.host_w2)))
    tiers = tiers._replace(slot_w1=s1, slot_w3=s3, slot_w2=s2, state=state)
    return tiers, rep_p, issued, issued_a.sum()


def _assert_tiers_equal(a, b):
    for fa, fb in zip(a, b):
        for xa, xb in zip(jax.tree.leaves(fa), jax.tree.leaves(fb)):
            np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


# (action, layer, picks, active): "moe" runs one layer, "prefetch" reserves
# the picks for `layer`. Two ways a set, two covered layers, four experts.
_ROW = [[0.6, 0.4], [0.5, 0.5]]
PARITY_CASES = {
    # expert 1 resident, 2 not; then layer 2, beyond the cache's coverage
    "resident_and_not": [("moe", 0, [[0, 1]], None),
                         ("moe", 0, [[1, 2]], None),
                         ("moe", 2, [[1, 3]], None)],
    # expert 0 sits in way 0, is evicted by 2 and 3 and comes back in
    # way 1 within one step: its slot row moves to the other way
    "remapped_way": [("moe", 0, [[0, 1]], None),
                     ("moe", 0, [[1, 0]], None),
                     ("moe", 0, [[2, 3], [0, 0]], None)],
    # two tokens pick the same expert, cold and then resident
    "duplicate_picks": [("moe", 1, [[0, 1], [0, 2]], None),
                        ("moe", 1, [[0, 2], [2, 0]], None)],
    # row 1 masked: its assignments form a group of expert -1, and one
    # more group is padding
    "padded_group": [("moe", 0, [[0, 1], [2, 3]], [True, False]),
                     ("moe", 0, [[1, 3], [0, 0]], [True, False])],
    # reservations stream weights in; the next probe lands and reads them
    "prefetch": [("moe", 0, [[0, 1]], None),
                 ("prefetch", 1, [[2, 3]], None),
                 ("moe", 1, [[3, 1]], None),
                 ("prefetch", 0, [[2, 1], [3, 3]], None),
                 ("moe", 0, [[2, 0]], None)],
}


@pytest.mark.parametrize("case", list(PARITY_CASES))
def test_slice_moves_match_gather_scatter_formulation(case, monkeypatch):
    """execute, commit and prefetch move each expert's weights as whole
    contiguous slices under a per-group cond; they give the same y (bit for
    bit), slot buffers, cache state and stats as the XLA gather/scatter
    formulation they replaced, and each case reaches what it names. The
    gathered buffers start as NaN here, as uninitialized memory may read
    on the chip, so a row no move writes shows in y."""
    monkeypatch.setattr(jax.lax, "empty", lambda shape, dtype, **_:
                        jnp.full(shape, jnp.nan, dtype))
    key = jax.random.PRNGKey(13)
    tiers, ccfg = _tiers(key)
    ref = tiers
    x2 = jax.random.normal(key, (2, 16), jnp.float32)
    seen = set()
    for action, layer, picks, active in PARITY_CASES[case]:
        ti = jnp.asarray(picks, jnp.int32)
        if action == "prefetch":
            tiers, *got = collab.prefetch(tiers, jnp.int32(layer), ti, ccfg)
            ref, *want = _oracle_prefetch(ref, jnp.int32(layer), ti, ccfg)
            for a, b in zip(got, want):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            seen.add("issued" if np.asarray(got[1]).any() else "")
        else:
            x = x2[:ti.shape[0]]
            tw = jnp.asarray(_ROW[:ti.shape[0]], jnp.float32)
            act = None if active is None else jnp.asarray(active)
            y, tiers, stats = collab.collaborative_moe(
                tiers, jnp.int32(layer), x, ti, tw, ccfg, active=act)
            y_ref, ref, stats_ref, pr, fetch = _oracle_moe(
                ref, jnp.int32(layer), x, ti, tw, ccfg, active=act)
            assert np.isfinite(np.asarray(y)).all()
            np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
            assert {k: int(v) for k, v in stats.items()} == \
                {k: int(v) for k, v in stats_ref.items()}
            res = np.asarray(pr.resident)
            rep_e = np.asarray(pr.rep_e)
            if res.any() and (~res & (rep_e >= 0)).any():
                seen.add("resident_and_not")
            if (res & np.asarray(fetch)).any():
                seen.add("remapped_way")
            if len(set(np.asarray(pr.flat_e).tolist())) < pr.flat_e.size:
                seen.add("duplicate_picks")
            if (rep_e < 0).sum() >= 2:
                seen.add("padded_group")
            if int(stats["prefetch_hits"]) > 0:
                seen.add("prefetch")
        _assert_tiers_equal(tiers, ref)
    assert case in seen, (case, seen)
