"""Segment-streamed prefill: the acceptance bar for the incremental
prompt pipeline.

Pins four contracts:
  * bit-identity — segmenting the prompt forward (any segment size:
    divisor, ragged last segment, one segment covering the whole prompt,
    single-token segments) reproduces the one-shot prefill's logits AND
    KV bitwise, dense and paged, through the full scheduler;
  * prefix elision — a repeat admission under paged KV + retention skips
    the shared span's forward outright (fewer forwarded prompt tokens,
    ``prefix_tokens_skipped`` counts, identical output tokens);
  * deferred first token — a streamed ticket has no logits until the
    stream drains; the guarded entry points say so instead of
    miscomputing, and a ``max_new_tokens=1`` request still completes
    through the deferred-sample path;
  * no leaks — an admission rejected AFTER its page allocation frees the
    table before the error reaches the caller (``pages_in_use`` returns
    to baseline).
"""
import jax
import numpy as np
import pytest

from repro.config import CacheConfig, get_config, reduced
from repro.models import init_params
from repro.serving import CollaborativeEngine, ContinuousBatchingScheduler, \
    EngineConfig


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("mixtral-8x7b"))
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    return cfg, params


def _engine(cfg, params, slots=2, capacity=32, **ecfg):
    ccfg = CacheConfig(num_indexes=cfg.num_layers, num_ways=2, policy="lru")
    return CollaborativeEngine(
        cfg, params, EngineConfig(cache=ccfg, max_batch=slots,
                                  capacity=capacity, **ecfg),
        key=jax.random.PRNGKey(3))


def _fleet(cfg, n=5, seed=11):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, int(rng.integers(4, 13)))
            .astype(np.int32) for _ in range(n)]


def _trim(leaf, cap, P):
    """Slice every capacity-sized axis down to the prompt: dense ragged
    segments write pad rows past plen (decode overwrites them before any
    read — causally masked), so only rows < P are contractual."""
    a = np.asarray(leaf)
    for ax, d in enumerate(a.shape):
        if d == cap:
            a = np.take(a, np.arange(P), axis=ax)
    return a


# ---------------------------------------------------------------------------
# dense bit-identity, engine level, across segment decompositions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seg", [4, 5, 16, 1],
                         ids=["divisor", "ragged", "covers", "single"])
def test_dense_segmented_bitwise_matches_one_shot(setup, seg):
    """prefill_chunked on a segment-streamed engine (start_prefill opens
    the ticket with no forward; the drain streams the segments) matches
    the one-shot engine's logits and live KV rows BITWISE, for a divisor
    segment (4 | 12), a ragged last segment (12 = 2*5 + 2), one segment
    covering the whole prompt (16 > 12), and single-token segments."""
    cfg, params = setup
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
    P, cap = len(prompt), 32

    base = _engine(cfg, params, capacity=cap)
    logits0, state0 = base.prefill_chunked(prompt, chunk=4)

    eng = _engine(cfg, params, capacity=cap, prefill_segment=seg)
    logits, state = eng.prefill_chunked(prompt)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(logits0))
    assert int(np.asarray(state["pos"]).reshape(-1)[0]) == P
    for got, want in zip(jax.tree_util.tree_leaves(state["scan"]),
                         jax.tree_util.tree_leaves(state0["scan"])):
        np.testing.assert_array_equal(_trim(got, cap, P),
                                      _trim(want, cap, P))
    assert eng.stats.prefill_segments == -(-P // seg)
    # segment warming routes the same tokens the trace replay would have
    assert eng.stats.prefill_tokens == base.stats.prefill_tokens == P


# ---------------------------------------------------------------------------
# scheduler-level parity: dense and paged streams vs the one-shot fleet
# ---------------------------------------------------------------------------

def _serve(cfg, params, prompts, **ecfg):
    eng = _engine(cfg, params, slots=3, capacity=32, **ecfg)
    sched = ContinuousBatchingScheduler(eng)
    reqs = [sched.submit(p, max_new_tokens=1 if i == 0 else 6)
            for i, p in enumerate(prompts)]
    outs = sched.run()
    return eng, {i: outs[r.rid] for i, r in enumerate(reqs)}


def test_scheduler_segmented_tokens_bit_identical(setup):
    """The same request fleet through the continuous-batching scheduler:
    one-shot admission vs dense segment streaming vs paged segment
    streaming (segments appending straight into the pool pages) produce
    bit-identical tokens; the streamed request admitted with
    ``max_new_tokens=1`` completes through the deferred first-token path
    (sampled on the drain tick, retired the same tick); the paged pool
    drains to zero."""
    cfg, params = setup
    prompts = _fleet(cfg)
    _, base = _serve(cfg, params, prompts)
    eng_d, dense = _serve(cfg, params, prompts, prefill_segment=4,
                          admit_chunks_per_tick=1)
    eng_p, paged = _serve(cfg, params, prompts, prefill_segment=4,
                          admit_chunks_per_tick=1, kv_paged=True,
                          page_size=8)
    assert len(base[0]) == 1                     # max_new_tokens=1 request
    for i in base:
        np.testing.assert_array_equal(dense[i], base[i])
        np.testing.assert_array_equal(paged[i], base[i])
    for eng in (eng_d, eng_p):
        assert eng.stats.prefill_segments > 0
        assert eng.stats.first_tokens == len(prompts)
    assert eng_p.kv_pool.pages_in_use == 0
    eng_p.kv_pool.check_invariants()


def test_prefix_hit_segmented_admission_parity(setup):
    """Retention + segment streaming: re-admitting an identical prompt
    adopts the retained prefix pages and the stream starts past the
    shared span — only the last prompt token forwards, the skip is
    counted, and the output tokens are bit-identical to the cold run."""
    cfg, params = setup
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
    eng = _engine(cfg, params, slots=2, capacity=32, prefill_segment=4,
                  admit_chunks_per_tick=1, kv_paged=True, page_size=4,
                  prefix_keep_pages=16)
    sched = ContinuousBatchingScheduler(eng)

    def admit():
        before = eng.stats.prefill_tokens
        r = sched.submit(prompt, max_new_tokens=5)
        outs = sched.run()
        return outs[r.rid], eng.stats.prefill_tokens - before

    out_cold, fwd_cold = admit()
    assert eng.kv_pool.prefix_pages_retained > 0   # parked at retirement
    out_hit, fwd_hit = admit()
    np.testing.assert_array_equal(out_hit, out_cold)
    assert fwd_cold == 12
    assert fwd_hit == 1                # only the last prompt token reran
    s = eng.stats
    assert s.prefix_tokens_skipped == 11
    assert s.prefix_hits == 1
    assert s.prefix_pages_retained > 0
    eng.kv_pool.check_invariants()


# ---------------------------------------------------------------------------
# deferred-first-token guards
def test_admit_slots_per_tick_streams_one_slot_at_a_time(setup):
    """With ``admit_slots_per_tick=1`` no tick advances more than one
    PREFILLING slot, the earliest admitted first (slot 0 drains before
    slot 1 starts), and the served tokens are the unlimited fleet's."""
    cfg, params = setup
    prompts = _fleet(cfg)
    _, base = _serve(cfg, params, prompts, prefill_segment=4,
                     admit_chunks_per_tick=1)
    eng = _engine(cfg, params, slots=3, capacity=32, prefill_segment=4,
                  admit_chunks_per_tick=1, admit_slots_per_tick=1)
    sched = ContinuousBatchingScheduler(eng)
    reqs = [sched.submit(p, max_new_tokens=1 if i == 0 else 6)
            for i, p in enumerate(prompts)]
    first_tick = {}
    tick = 0
    while sched.queue or any(s is not None for s in sched.slots):
        before = eng.stats.prefill_segments
        sched.step()
        tick += 1
        assert eng.stats.prefill_segments - before <= 1
        for r in reqs:
            if r.generated and r.rid not in first_tick:
                first_tick[r.rid] = tick
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(r.generated, base[i])
    # request 0 (slot 0) streams its segments before request 1 (slot 1)
    segs = -(-len(prompts[0]) // 4)
    assert first_tick[reqs[0].rid] == segs
    assert first_tick[reqs[1].rid] == segs + -(-len(prompts[1]) // 4)
    with pytest.raises(ValueError, match="admit_slots_per_tick"):
        EngineConfig(cache=eng.ecfg.cache, admit_slots_per_tick=-1)


# ---------------------------------------------------------------------------

def test_streamed_ticket_guards_before_drain(setup):
    """A paged streamed ticket mid-stream: sample_first and bind_slot
    refuse (no logits yet), advance_prefill without the batch state
    refuses (the stream appends into the batch pool), and the ticket
    drains to done through advance_prefill_state."""
    cfg, params = setup
    eng = _engine(cfg, params, capacity=32, prefill_segment=4,
                  kv_paged=True, page_size=8)
    state = eng.init_slots()
    ticket = eng.start_prefill(np.arange(9, dtype=np.int32) + 3)
    assert ticket.logits is None and ticket.kv_streamed
    with pytest.raises(RuntimeError, match="no logits yet"):
        eng.sample_first(ticket)
    with pytest.raises(RuntimeError, match="not drained"):
        eng.bind_slot(state, ticket, 0)
    with pytest.raises(RuntimeError, match="batch pool"):
        eng.advance_prefill(ticket)
    state, done = eng.advance_prefill_state(ticket, state,
                                            max_chunks=ticket.n_chunks)
    assert done and ticket.logits is not None
    state = eng.bind_slot(state, ticket, 0)
    assert int(np.asarray(state["pos"])[0]) == 9
    eng.release_slot(0)
    assert eng.kv_pool.pages_in_use == 0


# ---------------------------------------------------------------------------
# leak fix: rejected admissions free their pages
# ---------------------------------------------------------------------------

def test_start_prefill_error_frees_pages(setup):
    """The satellite regression: start_prefill allocates the page table
    FIRST, so any later validation error (here: a prompt filling the
    whole capacity, leaving no decode slot — allocable, not servable)
    must free it on the way out. ``pages_in_use`` returns to baseline on
    both the segmented and the trace-replay path."""
    cfg, params = setup
    bad = np.arange(32, dtype=np.int32) % cfg.vocab_size   # P == capacity

    eng = _engine(cfg, params, capacity=32, prefill_segment=4,
                  kv_paged=True, page_size=8)
    eng.init_slots()
    assert eng.kv_pool.can_admit(bad, 32)                  # pool-admissible
    with pytest.raises(ValueError, match="outside"):
        eng.start_prefill(bad)
    assert eng.kv_pool.pages_in_use == 0
    eng.kv_pool.check_invariants()

    eng2 = _engine(cfg, params, capacity=32, kv_paged=True, page_size=8)
    eng2.init_slots()
    with pytest.raises(ValueError, match="outside"):
        eng2.start_prefill(bad)
    assert eng2.kv_pool.pages_in_use == 0
    eng2.kv_pool.check_invariants()


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

def test_engine_config_validation():
    ccfg = CacheConfig(num_indexes=4, num_ways=2)
    with pytest.raises(ValueError, match="prefill_segment"):
        EngineConfig(cache=ccfg, prefill_segment=-1)
    with pytest.raises(ValueError, match="prefix_keep_pages"):
        EngineConfig(cache=ccfg, prefix_keep_pages=-1)
    with pytest.raises(ValueError, match="requires kv_paged"):
        EngineConfig(cache=ccfg, prefix_keep_pages=4)
    EngineConfig(cache=ccfg, prefix_keep_pages=4, kv_paged=True,
                 capacity=32, page_size=8)          # valid combination
