"""Observability tests: recorder mechanics (ring wraparound, span
nesting, no-op stand-in), histogram percentile parity against
``np.percentile``, Chrome trace export/validation, and the acceptance
invariant of the whole subsystem — serving with a live recorder produces
BIT-identical tokens to serving untraced, on both the dense and the
paged/segment-streamed paths, while covering every request's lifecycle
in the trace."""
import json

import jax
import numpy as np
import pytest

from repro.config import get_config, reduced
from repro.models import init_params
from repro.obs import (NULL_RECORDER, LogHistogram, NoopRecorder,
                       TraceRecorder, chrome_trace, validate_chrome_trace,
                       write_chrome_trace)
from repro.obs.export import lifecycle_coverage
from repro.obs.export import main as validate_main
from repro.obs.trace import now_ns
from repro.serving import build


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("mixtral-8x7b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _serve(cfg, params, recorder=None, **serving):
    serving.setdefault("capacity", 64)
    serving.setdefault("max_batch", 2)
    serving.setdefault("prefill_chunk", 4)
    _, sched = build(cfg, cache=dict(num_ways=4), serving=serving,
                     params=params, seed=0, recorder=recorder)
    rng = np.random.default_rng(7)
    for _ in range(3):
        sched.submit(rng.integers(0, cfg.vocab_size, int(rng.integers(5, 9))),
                     max_new_tokens=5)
    outs = sched.run()
    return outs, sched.stats


# ---------------------------------------------------------------------------
# recorder mechanics
# ---------------------------------------------------------------------------

def test_ring_buffer_wraparound_keeps_newest():
    rec = TraceRecorder(capacity=8)
    for i in range(20):
        rec.instant("t", f"ev{i}", ts_ns=rec.t0_ns + i)
    assert len(rec) == 8
    assert rec.dropped == 12
    names = [ev.name for ev in rec.events()]
    assert names == [f"ev{i}" for i in range(12, 20)]     # oldest-first
    ts = [ev.ts_ns for ev in rec.events()]
    assert ts == sorted(ts)


def test_recorder_capacity_validation_and_iter():
    with pytest.raises(ValueError):
        TraceRecorder(capacity=0)
    rec = TraceRecorder(capacity=4)
    rec.counter("t", "gauge", 3.5)
    (ev,) = list(rec)
    assert ev.kind == "C" and ev.args == {"value": 3.5}


def test_span_nesting_orders_child_before_parent():
    rec = TraceRecorder(capacity=16)
    with rec.span("t", "outer"):
        with rec.span("t", "inner", args={"k": 1}):
            pass
    inner, outer = rec.events()         # exit order: inner completes first
    assert (inner.name, outer.name) == ("inner", "outer")
    assert inner.kind == outer.kind == "X"
    # child temporally nested within the parent
    assert outer.ts_ns <= inner.ts_ns
    assert inner.ts_ns + inner.dur_ns <= outer.ts_ns + outer.dur_ns


def test_retroactive_complete_clamps_negative_duration():
    rec = TraceRecorder(capacity=4)
    t = now_ns()
    rec.complete("t", "span", t, t - 100)     # clock misuse never negative
    assert rec.events()[0].dur_ns == 0


def test_noop_recorder_is_inert():
    rec = NoopRecorder()
    assert not rec.enabled and len(rec) == 0
    rec.complete("t", "a", 0, 1)
    rec.instant("t", "b")
    rec.counter("t", "c", 1.0)
    with rec.span("t", "d"):
        pass
    assert rec.events() == [] and list(rec) == []
    assert NULL_RECORDER.enabled is False
    assert TraceRecorder().enabled is True


# ---------------------------------------------------------------------------
# streaming log-bucket histogram
# ---------------------------------------------------------------------------

def test_histogram_percentiles_match_numpy():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=2.0, sigma=1.2, size=5000)
    h = LogHistogram()
    for s in samples:
        h.observe(float(s))
    assert h.count == len(samples)
    assert h.mean == pytest.approx(float(samples.mean()))
    assert h.min == pytest.approx(float(samples.min()))
    assert h.max == pytest.approx(float(samples.max()))
    for q in (50.0, 90.0, 95.0, 99.0):
        exact = float(np.percentile(samples, q))
        # geometric buckets grow 8% per step: interpolated estimates
        # land within one bucket of the exact rank statistic
        assert h.percentile(q) == pytest.approx(exact, rel=0.09), q


def test_histogram_edge_cases():
    h = LogHistogram()
    assert h.percentile(50.0) == 0.0 and h.mean == 0.0
    h.observe(0.0)                      # non-positive: own underflow bucket
    h.observe(5.0)
    assert h.count == 2
    assert h.percentile(0.0) == pytest.approx(h.min)
    assert h.percentile(100.0) == pytest.approx(h.max)
    with pytest.raises(ValueError):
        h.percentile(101.0)
    single = LogHistogram()
    single.observe(42.0)
    for q in (0.0, 50.0, 99.0):
        assert single.percentile(q) == pytest.approx(42.0)
    d = single.to_json()
    assert set(d) == {"count", "mean", "p50", "p95", "p99"}
    assert json.loads(json.dumps(d)) == d


def test_histogram_percentiles_ordered():
    rng = np.random.default_rng(1)
    h = LogHistogram()
    for s in rng.exponential(10.0, size=1000):
        h.observe(float(s) + 1e-6)
    p50, p95, p99 = h.percentiles()
    assert p50 <= p95 <= p99
    assert h.min <= p50 and p99 <= h.max


# ---------------------------------------------------------------------------
# traced serving: bit-identity + trace completeness
# ---------------------------------------------------------------------------

# the host's phases of a tick: leaves of sched/tick and engine/decode_step
LEAVES = {("engine", n) for n in ("plan", "dispatch", "commit", "wait",
                                  "drain")} \
    | {("sched", n) for n in ("admission", "select", "emit")}


def _end(ev):
    return ev.ts_ns + ev.dur_ns


def _between_steps(rec):
    """(a, b, leaves inside [a, b]) for every pair of consecutive decode
    steps: a = the end of step n's ``wait``, b = the end of step n+1's
    ``dispatch``."""
    spans = [ev for ev in rec.events() if ev.kind == "X"]
    leaves = sorted((ev for ev in spans if (ev.track, ev.name) in LEAVES),
                    key=lambda ev: (ev.ts_ns, _end(ev)))
    waits = [ev for ev in leaves if ev.name == "wait"]
    disps = [ev for ev in leaves if ev.name == "dispatch"]
    assert len(waits) == len(disps) > 1
    out = []
    for w, d in zip(waits, disps[1:]):
        a, b = _end(w), _end(d)
        out.append((a, b, [ev for ev in leaves
                           if ev.ts_ns >= a and _end(ev) <= b]))
    return out


@pytest.mark.parametrize("mode", ["dense", "paged_segment"])
def test_traced_serving_bit_identical_and_covered(setup, tmp_path, mode):
    cfg, params = setup
    serving = {} if mode == "dense" else dict(
        kv_paged=True, page_size=4, prefill_segment=4,
        admit_chunks_per_tick=1)
    base, _ = _serve(cfg, params, recorder=None, **serving)
    rec = TraceRecorder()
    traced, stats = _serve(cfg, params, recorder=rec, **serving)

    assert sorted(traced) == sorted(base)
    for rid in base:
        np.testing.assert_array_equal(traced[rid], base[rid])

    doc = chrome_trace(rec)
    assert validate_chrome_trace(doc) == []
    cover = lifecycle_coverage(doc)
    assert len(cover) == 3
    for track, spans in cover.items():
        assert {"queued", "prefill", "decode"} <= spans, (track, spans)

    # the host's leaves between two decode steps never overlap, start
    # where the device finished and end where the next step left
    for a, b, inside in _between_steps(rec):
        assert inside[0].name == "drain" and inside[0].ts_ns == a
        assert inside[-1].name == "dispatch" and _end(inside[-1]) == b
        assert {"select", "emit", "admission", "plan"} <= \
            {ev.name for ev in inside}
        for prev, nxt in zip(inside, inside[1:]):
            assert _end(prev) <= nxt.ts_ns, (prev.name, nxt.name)

    # percentile channel populated on RunStats
    assert stats.ttft_ms_p50 > 0.0
    assert stats.tpot_ms_p50 > 0.0
    assert stats.ttft_ms_p50 <= stats.ttft_ms_p99

    # JSON artifact round-trips and passes the CLI validator
    path = tmp_path / "trace.json"
    write_chrome_trace(rec, str(path))
    assert json.loads(path.read_text()) == json.loads(json.dumps(doc))
    assert validate_main([str(path), "--require-lifecycle"]) == 0


def test_trace_orders_step_phases_within_tick(setup):
    cfg, params = setup
    rec = TraceRecorder()
    _serve(cfg, params, recorder=rec)
    by_track = {}
    for ev in rec.events():
        by_track.setdefault(ev.track, []).append(ev)
    ticks = [ev for ev in by_track["sched"] if ev.name == "tick"]
    assert ticks
    # every admission/decode+drain span nests inside some tick span
    for ev in by_track["sched"]:
        if ev.name in ("admission", "decode+drain"):
            assert any(t.ts_ns <= ev.ts_ns
                       and ev.ts_ns + ev.dur_ns <= t.ts_ns + t.dur_ns + 1
                       for t in ticks), ev.name
    # engine decode steps carry their lane attribution as args
    eng = [ev for ev in by_track.get("engine", []) if ev.name == "decode_step"]
    assert eng and all(ev.kind == "X" for ev in eng)
    for ev in eng:
        assert {"hit_experts", "fetched_experts", "cpu_expert_calls"} \
            <= set(ev.args)
    assert sum(ev.args["hit_experts"] for ev in eng) > 0
    assert sum(ev.args["tokens"] for ev in eng) == 3 * 4   # 5 - first


def test_leaf_spans_tile_the_host_time_between_steps(setup, monkeypatch):
    """On a clock that advances one microsecond per reading, consecutive
    leaves between two decode steps are one reading apart: every phase
    of the loop that reads the clock lies inside a leaf."""
    from repro.serving import engine as engine_mod
    from repro.serving import scheduler as scheduler_mod
    cfg, params = setup
    ticks = iter(range(10 ** 9))
    clock = lambda: 1000 * next(ticks)                    # noqa: E731
    monkeypatch.setattr(engine_mod, "now_ns", clock)
    monkeypatch.setattr(scheduler_mod, "now_ns", clock)
    rec = TraceRecorder()
    _serve(cfg, params, recorder=rec, kv_paged=True, page_size=4,
           prefill_segment=4, admit_chunks_per_tick=1)
    for a, b, inside in _between_steps(rec):
        assert inside[0].ts_ns == a and _end(inside[-1]) == b
        holes = [nxt.ts_ns - _end(prev)
                 for prev, nxt in zip(inside, inside[1:])]
        assert all(0 <= h <= 1000 for h in holes), \
            [(ev.name, ev.ts_ns, _end(ev)) for ev in inside]


def test_trace_validator_flags_malformed_documents():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"traceEvents": "nope"}) != []
    bad = {"traceEvents": [
        {"ph": "Z", "name": "x", "pid": 1, "tid": 1, "ts": 0},
        {"ph": "X", "name": "y", "pid": 1, "tid": 2, "ts": -5},
    ]}
    problems = validate_chrome_trace(bad)
    assert any("unknown phase" in p for p in problems)
    assert any("bad ts" in p for p in problems)
    assert any("no thread_name" in p for p in problems)
    # a complete span without dur, and a counter without value
    bad2 = {"traceEvents": [
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 1,
         "args": {"name": "t"}},
        {"ph": "X", "name": "y", "pid": 1, "tid": 1, "ts": 0},
        {"ph": "C", "name": "g", "pid": 1, "tid": 1, "ts": 0, "args": {}},
    ]}
    problems = validate_chrome_trace(bad2)
    assert any("without non-negative dur" in p for p in problems)
    assert any("without args.value" in p for p in problems)


def test_cancelled_request_gets_terminal_instant(setup):
    cfg, params = setup
    rec = TraceRecorder()
    _, sched = build(cfg, cache=dict(num_ways=4),
                     serving=dict(capacity=64, max_batch=1,
                                  prefill_chunk=4),
                     params=params, seed=0, recorder=rec)
    rng = np.random.default_rng(3)
    keep = sched.submit(rng.integers(0, cfg.vocab_size, 6),
                        max_new_tokens=4)
    gone = sched.submit(rng.integers(0, cfg.vocab_size, 6),
                        max_new_tokens=4)
    assert sched.cancel(gone.rid)
    sched.run()
    doc = chrome_trace(rec)
    assert validate_chrome_trace(doc) == []
    names_by_tid = {ev["tid"]: ev["args"]["name"]
                    for ev in doc["traceEvents"]
                    if ev.get("ph") == "M"}
    instants = {(names_by_tid[ev["tid"]], ev["name"])
                for ev in doc["traceEvents"] if ev.get("ph") == "i"}
    assert (f"req:{gone.rid}", "cancelled") in instants
    assert (f"req:{keep.rid}", "done") in instants
    # cancelled-in-queue lifecycles cover queued only; finished cover all
    cover = lifecycle_coverage(doc)
    assert "queued" in cover[f"req:{gone.rid}"]
    assert "decode" not in cover[f"req:{gone.rid}"]
    assert {"queued", "prefill", "decode"} <= cover[f"req:{keep.rid}"]
