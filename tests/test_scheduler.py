"""Continuous-batching scheduler + batched engine behaviour tests.

The acceptance bar for the batched serving core: >=4 concurrent requests
decode through ONE shared expert cache in one padded step; padded slots
are bitwise-invisible to active rows; a batched step computes the same
logits as independent single-request decodes (bf16 tolerance); slots
recycle so more requests than slots drain to completion.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import CacheConfig, get_config, reduced
from repro.models import init_params
from repro.serving import CollaborativeEngine, ContinuousBatchingScheduler, \
    EngineConfig, QueueFull, Request


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("mixtral-8x7b"))
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    return cfg, params


def _engine(cfg, params, slots=4, capacity=64, **ecfg):
    ccfg = CacheConfig(num_indexes=cfg.num_layers, num_ways=2, policy="lru")
    return CollaborativeEngine(
        cfg, params, EngineConfig(cache=ccfg, max_batch=slots,
                                  capacity=capacity, **ecfg),
        key=jax.random.PRNGKey(3))


def _prompts(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, int(rng.integers(4, 9)))
            .astype(np.int32) for _ in range(n)]


def test_four_concurrent_requests_share_one_cache(setup):
    """>=4 requests in flight simultaneously, one shared expert cache."""
    cfg, params = setup
    eng = _engine(cfg, params, slots=4)
    sched = ContinuousBatchingScheduler(eng)
    reqs = [sched.submit(p, max_new_tokens=6) for p in _prompts(cfg, 4)]
    sched.step()
    assert sched.num_active == 4                  # all four decode together
    outs = sched.run()
    assert sorted(outs) == [r.rid for r in reqs]
    for r in reqs:
        assert len(outs[r.rid]) == 6
    stats = sched.stats
    # every decode step served the full batch through the one cache
    assert stats.accesses == stats.hits + stats.host_assignments
    assert stats.tokens == 4 * 5                  # 5 decode ticks per request
    # first-token accounting: each request's prefill-sampled token counts
    # once, so token totals match what the requests actually streamed
    assert stats.first_tokens == 4
    assert stats.generated_tokens == 4 * 6 \
        == sum(len(o) for o in outs.values())
    assert 0.0 <= stats.hit_rate <= 1.0
    assert stats.requests_submitted == stats.requests_finished == 4


def test_slots_recycle_when_requests_outnumber_slots(setup):
    cfg, params = setup
    eng = _engine(cfg, params, slots=2)
    sched = ContinuousBatchingScheduler(eng)
    reqs = [sched.submit(p, max_new_tokens=3 + i)
            for i, p in enumerate(_prompts(cfg, 5, seed=1))]
    outs = sched.run()
    assert len(outs) == 5
    for i, r in enumerate(reqs):
        assert len(outs[r.rid]) == 3 + i
    # with 2 slots, 5 requests were never all in flight, yet all completed
    assert sched.queue == type(sched.queue)()


def test_padded_slots_are_bitwise_invisible(setup):
    """Garbage in inactive slots (tokens, KV positions) must not change
    active rows' logits AT ALL — the isolation that makes continuous
    batching correct."""
    cfg, params = setup
    eng = _engine(cfg, params, slots=4)
    prompt = _prompts(cfg, 1)[0]
    tok, one_state = eng.prefill_request(prompt)

    def run(junk_tok, junk_pos):
        state = eng.init_slots()
        state = eng.write_slot(state, one_state, 0)
        state["pos"] = state["pos"].at[1:].set(junk_pos)
        fast0 = eng.fast                               # snapshot tiers
        tokens = np.full((4, 1), junk_tok, np.int32)
        tokens[0, 0] = tok
        active = np.array([True, False, False, False])
        logits, _, fast, stats = eng._decode(
            eng.params, jnp.asarray(tokens), state, fast0,
            jnp.asarray(active))
        return (np.asarray(logits[0, 0]), jax.tree.map(np.asarray, fast),
                {k: int(np.asarray(v).sum()) for k, v in stats.items()})

    # donation invalidates eng.fast: rebuild the engine per variant
    l1, f1, s1 = run(junk_tok=7, junk_pos=0)
    eng = _engine(cfg, params, slots=4)
    tok2, one_state = eng.prefill_request(prompt)
    assert tok2 == tok
    l2, f2, s2 = run(junk_tok=301, junk_pos=13)
    np.testing.assert_array_equal(l1, l2)
    jax.tree.map(np.testing.assert_array_equal, f1, f2)
    assert s1 == s2
    assert s1["accesses"] == cfg.num_layers * cfg.moe.top_k  # active row only


def test_batched_step_matches_single_request_logits(setup):
    """One padded 4-way decode step == four independent 1-way decode steps
    (same KV state, same cache-off... identical weights), row by row,
    within bf16 tolerance. Verifies no cross-slot leakage through
    attention, routing or the grouped MoE dispatch."""
    cfg, params = setup
    prompts = _prompts(cfg, 4, seed=2)

    # batched: prefill each request into its slot, one decode step
    eng = _engine(cfg, params, slots=4)
    state = eng.init_slots()
    next_tok = np.zeros((4, 1), np.int32)
    for t, p in enumerate(prompts):
        tok, one_state = eng.prefill_request(p)
        state = eng.write_slot(state, one_state, t)
        next_tok[t, 0] = tok
    logits_b, _ = eng.decode_batch(next_tok, state, np.ones(4, bool))
    logits_b = np.asarray(logits_b[:, 0], np.float32)

    # solo: same step for each request alone
    for t, p in enumerate(prompts):
        eng1 = _engine(cfg, params, slots=1)
        tok, one_state = eng1.prefill_request(p)
        assert tok == next_tok[t, 0]
        state1 = eng1.init_slots()
        state1 = eng1.write_slot(state1, one_state, 0)
        logits_s, _ = eng1.decode_batch(np.asarray([[tok]], np.int32),
                                        state1, np.ones(1, bool))
        np.testing.assert_allclose(
            logits_b[t], np.asarray(logits_s[0, 0], np.float32),
            rtol=2e-2, atol=2e-2)


def test_cancel_mid_decode_frees_slot_and_admits_waiting(setup):
    """cancel(rid) mid-decode: the request stops decoding immediately, a
    terminal (rid, token, done=True) event is emitted, and the freed slot
    admits a waiting request on the next tick."""
    cfg, params = setup
    eng = _engine(cfg, params, slots=2)
    sched = ContinuousBatchingScheduler(eng)
    reqs = [sched.submit(p, max_new_tokens=8)
            for p in _prompts(cfg, 3, seed=3)]
    sched.step()                                  # r0, r1 in flight
    assert sched.num_active == 2 and len(sched.queue) == 1
    victim = reqs[0]
    n_before = len(victim.generated)
    assert sched.cancel(victim.rid)
    assert victim.done and victim.cancelled
    # slot freed immediately; no further tokens for the cancelled request
    assert sched.num_active == 1
    finished, events = sched._tick()
    # -1 sentinel: every real token was already streamed exactly once
    assert events[0] == (victim.rid, -1, True)
    assert victim in finished                     # step() reports it done
    assert len(victim.generated) == n_before      # token stream rejected
    # the waiting request took the freed slot on that same tick
    assert sched.num_active == 2
    assert any(s is not None and s.rid == reqs[2].rid for s in sched.slots)
    outs = sched.run()
    assert sorted(outs) == [r.rid for r in reqs]
    assert len(outs[victim.rid]) == n_before < 8
    for r in (reqs[1], reqs[2]):
        assert len(outs[r.rid]) == 8
    # cancelling again (or an unknown rid) is a no-op, not an error
    assert not sched.cancel(victim.rid)
    assert not sched.cancel(10_000)


def test_cancel_from_on_token_callback_at_admission(setup):
    """An on_token handler that cancels its own request on the FIRST
    token (content-filter style) must take effect: the request is live in
    its slot when the callback fires, so cancel() frees it and no decode
    tokens follow."""
    cfg, params = setup
    eng = _engine(cfg, params, slots=1)
    sched = ContinuousBatchingScheduler(eng)
    req = sched.submit(
        _prompts(cfg, 1, seed=7)[0], max_new_tokens=8,
        on_token=lambda tok, done: done or sched.cancel(req.rid))
    outs = sched.run()
    assert req.cancelled
    assert len(outs[req.rid]) == 1                # the prefill token only
    assert sched.stats.requests_finished == 1


def test_cancel_finished_request_awaiting_retirement_is_noop(setup):
    """A request that finished on the last tick but still occupies its
    slot (retirement happens at the next tick's start) already streamed
    its terminal event — cancel() must refuse rather than emit a second
    done=True."""
    cfg, params = setup
    eng = _engine(cfg, params, slots=1)
    sched = ContinuousBatchingScheduler(eng)
    req = sched.submit(_prompts(cfg, 1, seed=6)[0], max_new_tokens=2)
    sched.step()                          # admit + first decode -> done
    assert req.done and sched.slots[0] is req
    assert not sched.cancel(req.rid)
    assert not sched._pending_events
    assert not req.cancelled
    sched.step()                          # normal retirement
    assert sched.finished == [req]


def test_cancel_queued_request_and_stream_terminal_event(setup):
    """A queued request cancels without ever decoding: stream() delivers
    exactly one event for it — (rid, -1, done=True) — and on_token fires
    once with done=True."""
    cfg, params = setup
    eng = _engine(cfg, params, slots=1)
    sched = ContinuousBatchingScheduler(eng)
    seen = []
    r0 = sched.submit(_prompts(cfg, 1, seed=4)[0], max_new_tokens=3)
    rq = sched.submit(_prompts(cfg, 1, seed=5)[0], max_new_tokens=3,
                      on_token=lambda tok, done: seen.append((tok, done)))
    assert sched.cancel(rq.rid)                   # still queued: no tokens
    assert seen == [(-1, True)]
    events = list(sched.stream())
    ev_rq = [e for e in events if e[0] == rq.rid]
    assert ev_rq == [(rq.rid, -1, True)]
    done_flags = [e for e in events if e[0] == r0.rid]
    assert len(done_flags) == 3 and done_flags[-1][2]
    assert sorted(r.rid for r in sched.finished) == [r0.rid, rq.rid]
    assert rq.output.size == 0


def test_request_equality_is_identity(setup):
    """Two distinct requests with EQUAL prompts must compare unequal
    without touching the ndarray (a dataclass-generated __eq__ would
    raise "truth value of an array is ambiguous" in `req in queue` /
    list.remove): rid is the key, identity the semantics."""
    prompt = np.arange(6, dtype=np.int32)
    r1 = Request(0, prompt.copy(), 4)
    r2 = Request(1, prompt.copy(), 4)
    assert r1 != r2                               # no ValueError
    assert r1 == r1
    assert r1 in [r2, r1] and r1 not in [r2]
    lst = [r1, r2]
    lst.remove(r2)
    assert lst == [r1]


# ---------------------------------------------------------------------------
# overlapped chunk-interleaved admission (the PREFILLING phase)
# ---------------------------------------------------------------------------

def _submit_mixed(sched, cfg, long_len=48, seed=11):
    """Two short established requests (fully warmed and decoding) + one
    long-prompt newcomer still in the queue."""
    rng = np.random.default_rng(seed)
    est = [sched.submit(rng.integers(0, cfg.vocab_size, 6),
                        max_new_tokens=16) for _ in range(2)]
    sched.step()                                  # admit both
    while sched.prefill_pending:                  # drain their short warms
        sched.step()
    newcomer = sched.submit(rng.integers(0, cfg.vocab_size, long_len),
                            max_new_tokens=6)
    return est, newcomer


def test_overlapped_admission_tokens_bit_identical(setup):
    """Acceptance: with overlap enabled, EVERY request's tokens are
    bit-identical to the synchronous-admission path — warming pace moves
    residency and latency, never numerics."""
    cfg, params = setup

    def run(admit_chunks):
        eng = _engine(cfg, params, slots=3, capacity=96, prefill_chunk=4,
                      admit_chunks_per_tick=admit_chunks)
        sched = ContinuousBatchingScheduler(eng)
        est, newcomer = _submit_mixed(sched, cfg)
        return sched.run(), sched.stats

    outs_sync, s_sync = run(0)
    outs_over, s_over = run(1)
    assert sorted(outs_sync) == sorted(outs_over)
    for rid in outs_sync:
        np.testing.assert_array_equal(outs_sync[rid], outs_over[rid])
    # both paths replay the same warm chunks, just paced differently
    assert s_over.prefill_chunks == s_sync.prefill_chunks
    assert s_over.prefill_accesses == s_sync.prefill_accesses


def test_overlapped_admission_decodes_established_while_warming(setup):
    """The head-of-line fix itself: while the newcomer's slot is in the
    PREFILLING phase, the established requests decode a token on every
    tick and the newcomer emits nothing beyond its prefill-sampled first
    token; its warm replay advances admit_chunks_per_tick chunks/tick."""
    cfg, params = setup
    eng = _engine(cfg, params, slots=3, capacity=96, prefill_chunk=4,
                  admit_chunks_per_tick=1)
    sched = ContinuousBatchingScheduler(eng)
    est, newcomer = _submit_mixed(sched, cfg)     # 48 tokens -> 12 chunks
    est_before = [len(r.generated) for r in est]
    chunks_before = eng.stats.prefill_chunks

    sched.step()                                  # admission tick
    assert sched.prefill_pending == 1
    assert sched.stats.prefill_pending == 1
    assert len(newcomer.generated) == 1           # the prefill token only
    assert eng.stats.prefill_chunks == chunks_before + 1
    warm_ticks = 0
    while sched.prefill_pending:
        n_est = [len(r.generated) for r in est]
        sched.step()
        warm_ticks += 1
        # established slots kept decoding under the admission
        assert [len(r.generated) for r in est] == [n + 1 for n in n_est]
    assert warm_ticks == 11                       # 12 chunks, 1 on admission
    assert len(newcomer.generated) == 2           # decoded on the last tick
    assert [len(r.generated) for r in est] == \
        [n + 12 for n in est_before]
    outs = sched.run()
    assert len(outs[newcomer.rid]) == 6


def test_cancel_during_prefilling_frees_slot_and_drops_ticket(setup):
    """Satellite: cancel(rid) mid-warm must free the slot, drop the
    ticket (no further chunks replay), and emit exactly one terminal
    (rid, -1, True) event."""
    cfg, params = setup
    eng = _engine(cfg, params, slots=1, capacity=96, prefill_chunk=4,
                  admit_chunks_per_tick=1)
    sched = ContinuousBatchingScheduler(eng)
    rng = np.random.default_rng(13)
    victim = sched.submit(rng.integers(0, cfg.vocab_size, 40),
                          max_new_tokens=8)
    waiting = sched.submit(rng.integers(0, cfg.vocab_size, 6),
                           max_new_tokens=3)
    sched.step()                                  # admit + 1 of 10 chunks
    assert sched.prefill_pending == 1
    chunks_at_cancel = eng.stats.prefill_chunks

    assert sched.cancel(victim.rid)
    assert sched.prefill_pending == 0             # ticket dropped
    assert sched.num_active == 0                  # slot freed immediately
    finished, events = sched._tick()
    assert events[0] == (victim.rid, -1, True)
    assert victim in finished
    ev_victim = [e for e in events if e[0] == victim.rid]
    assert ev_victim == [(victim.rid, -1, True)]  # exactly one terminal
    # the freed slot admitted the waiting request on that same tick; the
    # victim's remaining 9 chunks never replayed (only the waiter's 2)
    assert any(s is not None and s.rid == waiting.rid for s in sched.slots)
    outs = sched.run()
    assert len(outs[waiting.rid]) == 3
    assert len(outs[victim.rid]) == 1             # the prefill token only
    assert eng.stats.prefill_chunks == chunks_at_cancel + 2
    # cancelling again is a no-op
    assert not sched.cancel(victim.rid)


# ---------------------------------------------------------------------------
# bounded admission + pause/resume (backpressure)
# ---------------------------------------------------------------------------

def test_bounded_queue_rejects_and_blocks(setup):
    """max_queue bounds the waiting line: block=False raises the typed
    QueueFull (counted in queue_rejected); the blocking default drives
    ticks until space frees and then queues the request."""
    cfg, params = setup
    eng = _engine(cfg, params, slots=1)
    sched = ContinuousBatchingScheduler(eng, max_queue=1)
    prompts = _prompts(cfg, 4, seed=21)
    r0 = sched.submit(prompts[0], max_new_tokens=2)
    sched.step()                                  # r0 into the slot
    r1 = sched.submit(prompts[1], max_new_tokens=2)   # fills the queue
    with pytest.raises(QueueFull, match="max_queue"):
        sched.submit(prompts[2], max_new_tokens=2, block=False)
    assert sched.stats.queue_rejected == 1
    assert sched.stats.requests_submitted == 2    # rejected never queued
    r3 = sched.submit(prompts[3], max_new_tokens=2)   # blocks, then queues
    outs = sched.run()
    assert sorted(outs) == [r0.rid, r1.rid, r3.rid]
    for r in (r0, r1, r3):
        assert len(outs[r.rid]) == 2
    with pytest.raises(ValueError, match="max_queue"):
        ContinuousBatchingScheduler(eng, max_queue=0)


def test_blocking_submit_preserves_stream_events(setup):
    """Regression: ticks driven INSIDE a blocking submit() must not drop
    their stream events — a request that fully decodes while a producer
    is blocked still delivers every token and its terminal done=True
    through the next stream()."""
    cfg, params = setup
    eng = _engine(cfg, params, slots=1)
    sched = ContinuousBatchingScheduler(eng, max_queue=1)
    prompts = _prompts(cfg, 3, seed=23)
    r0 = sched.submit(prompts[0], max_new_tokens=3)
    sched.step()                          # r0 into the slot, 2 of 3 tokens
    #                                       (their events consumed by step)
    assert not r0.done
    r1 = sched.submit(prompts[1], max_new_tokens=2)
    r2 = sched.submit(prompts[2], max_new_tokens=2)  # blocks; r0 finishes
    assert r0.done                        # decoded during the blocked submit
    events = list(sched.stream())
    by_rid = {}
    for rid, tok, done in events:
        by_rid.setdefault(rid, []).append((tok, done))
    # r0's remaining token + done=True survived the blocking submit
    assert [d for _, d in by_rid[r0.rid]] == [True]
    for r in (r1, r2):
        assert [d for _, d in by_rid[r.rid]] == [False, True]
        assert [t for t, _ in by_rid[r.rid]] == list(r.output)


def test_pause_resume_admission(setup):
    """pause_admission() holds queued requests (stream() drains only the
    in-flight work, admission_stalls count the waiting ticks); resume
    serves them; a paused full queue raises QueueFull even when
    blocking."""
    cfg, params = setup
    eng = _engine(cfg, params, slots=2)
    sched = ContinuousBatchingScheduler(eng, max_queue=2)
    prompts = _prompts(cfg, 4, seed=22)
    r0 = sched.submit(prompts[0], max_new_tokens=3)
    sched.step()
    sched.pause_admission()
    assert sched.admission_paused
    r1 = sched.submit(prompts[1], max_new_tokens=3)
    outs = sched.run()                        # drains r0 only
    assert list(outs) == [r0.rid]
    assert sched.stats.requests_queued == 1
    assert sched.stats.admission_stalls > 0
    r2 = sched.submit(prompts[2], max_new_tokens=3)   # queue now full
    with pytest.raises(QueueFull, match="paused"):
        sched.submit(prompts[3], max_new_tokens=3)    # blocking can't drain
    sched.resume_admission()
    assert not sched.admission_paused
    outs = sched.run()
    assert sorted(outs) == [r0.rid, r1.rid, r2.rid]
    for rid in (r1.rid, r2.rid):
        assert len(outs[rid]) == 3


def test_staggered_positions_decode_correctly(setup):
    """Slots at different KV positions (different prompt lengths) coexist:
    the scheduler output for each request equals its solo scheduler run."""
    cfg, params = setup
    prompts = [np.arange(4, dtype=np.int32), np.arange(9, dtype=np.int32),
               np.arange(6, dtype=np.int32)]
    solo = []
    for p in prompts:
        eng1 = _engine(cfg, params, slots=1)
        s1 = ContinuousBatchingScheduler(eng1)
        r = s1.submit(p, max_new_tokens=4)
        solo.append(s1.run()[r.rid])
    eng = _engine(cfg, params, slots=3)
    sched = ContinuousBatchingScheduler(eng)
    reqs = [sched.submit(p, max_new_tokens=4) for p in prompts]
    outs = sched.run()
    for r, s in zip(reqs, solo):
        # first token comes from the (batch-independent) prefill: exact.
        assert outs[r.rid][0] == s[0]
        assert len(outs[r.rid]) == len(s)
