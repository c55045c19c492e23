"""The trace reduction and the per-layer readers, on a small synthetic
trace."""
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import harness, spec, trace

ROOT = Path(__file__).resolve().parents[3]
K = 'custom_call_target="tpu_custom_call"'


def _op(name, s, e, kernel=False):
    return (f"%{name} = bf16[2] custom-call(), {K}" if kernel
            else f"%{name} = bf16[2] fusion()", s, e)


def _span(track, name, s, e, args=None):
    return trace.Span(track, name, s, e, args)


def test_union_and_self_times():
    assert trace.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    ops = [("while", 0, 100), ("a", 10, 30), ("b", 40, 50),
           ("c", 42, 45), ("d", 120, 130)]
    assert trace.self_times(ops) == {"while": 70, "a": 20, "b": 7, "c": 3,
                                     "d": 10}


def test_names():
    assert trace.instruction("%moe_ffn.36 = bf16[2] custom-call()") == \
        "moe_ffn.36"
    assert trace.base("moe_ffn.36") == "moe_ffn"
    assert trace.base("jit__decode_step(7605441706620457738)") == \
        "jit__decode_step"


def _reduction():
    ops = [_op("while.1", 100, 400), _op("moe_ffn.3", 150, 200, True),
           _op("moe_ffn.4", 210, 240, True), _op("fusion.9", 600, 700),
           _op("moe_ffn.5", 620, 660, True),
           _op("_paged_prefill_attention.2", 800, 880, True)]
    modules = [("jit__decode_step(1)", 90, 410),
               ("jit__segment_step(2)", 590, 900)]
    spans = [_span("sched", "tick", 0, 1000),
             _span("engine", "decode_step", 80, 450),
             _span("engine", "segment_stream", 550, 950,
                   {"units": 1, "cursor": 2, "of": 2}),
             _span("sched", "admission", 550, 960)]
    return trace.Reduction(a=0, b=1000, ops=ops, modules=modules,
                           spans=spans)


def test_busy_kernels_and_breakdown():
    r = _reduction()
    assert r.window_s == pytest.approx(1e-6)
    assert r.busy_s == pytest.approx((300 + 100 + 80) / 1e9)
    assert r.kernel_seconds("moe_ffn") == pytest.approx(120 / 1e9)
    assert r.kernel_seconds("moe_ffn", module="jit__decode_step") == \
        pytest.approx(80 / 1e9)
    assert r.kernel_seconds("fusion") == 0.0      # not a Mosaic kernel
    bd = r.breakdown()
    assert bd["device_ops"][0] == ["while.1", pytest.approx(220 / 1e9)]
    # gaps 400-600, 880-1000, 0-100, 700-800, each named by the
    # shortest program span open at its middle
    gaps = [(n, round(s * 1e9)) for n, s in bd["idle_gaps"]]
    assert gaps[:2] == [("sched/tick", 200), ("engine/segment_stream", 120)]
    assert sorted(gaps[2:]) == [("engine/segment_stream", 100),
                                ("sched/tick", 100)]


def test_busy_time_is_cut_to_the_traced_window():
    """A step still running when the profiler stops, or begun before it
    started, counts only inside [a, b]; one wholly outside not at all."""
    ops = [_op("fusion.1", -50, 30), _op("fusion.2", 100, 200),
           _op("while.3", 900, 1400), _op("fusion.4", 1500, 1600)]
    r = trace.Reduction(a=0, b=1000, ops=ops, modules=[], spans=[])
    assert r.busy_s == pytest.approx((30 + 100 + 100) / 1e9)
    assert r.busy_s <= r.window_s
    idle = spec.load_reader(ROOT, "device_idle")
    ctx = trace.Context(cell=None, model=None, served=[], t0=0, t1=1000,
                        stats0=None, stats1=None, peak={}, trace=r)
    assert idle(ctx) == pytest.approx(77.0)
    gaps = sorted(round(s * 1e9) for _, s in r.idle_gaps())
    assert gaps == [70, 700]


def test_readers_on_a_synthetic_context():
    cell = spec.load_cell(ROOT, "mixtral.single-decode")
    model = cell.model
    req = harness.Served(0, np.zeros(200, np.int32), 8)
    req.times = [960, 990]                        # first token after span
    req.tokens = [1, 2]
    stats = type("S", (), {"hits": 10, "accesses": 40})
    stats1 = type("S", (), {"hits": 40, "accesses": 100})
    ctx = trace.Context(cell=cell, model=model, served=[req], t0=0, t1=1000,
                        stats0=stats, stats1=stats1,
                        peak=spec.peaks(ROOT, "TPU v5 lite"),
                        trace=_reduction())
    read = {m: spec.load_reader(ROOT, m) for m in
            ("decode_step_ms", "expert_hit_rate", "device_idle",
             "decode_mfu", "gmm_roofline")}
    assert read["decode_step_ms"](ctx) == pytest.approx(370 / 1e6)
    assert read["expert_hit_rate"](ctx) == pytest.approx(50.0)
    assert read["device_idle"](ctx) == pytest.approx(52.0)
    assert read["decode_mfu"](ctx) > 0
    assert read["gmm_roofline"](ctx) > 0


def test_a_reader_that_finds_nothing_returns_none():
    r = trace.Reduction(a=0, b=1000, ops=[], modules=[], spans=[])
    cell = spec.load_cell(ROOT, "mixtral.single-decode")
    ctx = trace.Context(cell=cell, model=cell.model,
                        served=[], t0=0, t1=1000, stats0=None, stats1=None,
                        peak=spec.peaks(ROOT, "TPU v5 lite"), trace=r)
    for m in ("decode_step_ms", "device_idle", "decode_mfu",
              "gmm_roofline"):
        assert spec.load_reader(ROOT, m)(ctx) is None, m
