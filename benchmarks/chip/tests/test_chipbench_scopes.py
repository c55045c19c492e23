"""The decode step's device time by scope, and the host gap between steps,
on small synthetic traces; the readers that were there before, on theirs."""
import dataclasses
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import harness, scopes, spec, trace, work
from test_chipbench_trace import _reduction

ROOT = Path(__file__).resolve().parents[3]

HLO = """\
HloModule jit__decode_step, is_scheduled=true

%fused_computation.1 (param_0: bf16[4]) -> bf16[4] {
  %param_0 = bf16[4]{0} parameter(0)
  ROOT %add.2 = bf16[4]{0} add(%param_0, %param_0), metadata={op_name="jit(_decode_step)/while/body/closed_call/moe_gather/add"}
}

%scatter_body.3 (p.4: (s32[], bf16[4])) -> (s32[], bf16[4]) {
  %p.4 = (s32[], bf16[4]{0}) parameter(0)
  %gte.5 = bf16[4]{0} get-tuple-element(%p.4), index=1
  ROOT %tuple.6 = (s32[], bf16[4]{0}) tuple(%gte.5, %gte.5)
}

%region_0.7 (arg.8: (bf16[4])) -> (bf16[4]) {
  %arg.8 = (bf16[4]{0}) parameter(0)
  %constant.9 = bf16[] constant(0), metadata={op_name="jit(_decode_step)/while/body/closed_call/moe_dispatch/broadcast"}
  %broadcast.10 = bf16[4]{0} broadcast(%constant.9), dimensions={}
  %gather.11 = bf16[4]{0} fusion(%broadcast.10), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_decode_step)/while/body/closed_call/moe_gather/gather"}
  %copy.12 = bf16[4]{0} copy(%gather.11)
  %while.13 = (s32[], bf16[4]{0}) while(%copy.12), condition=%cond.20, body=%scatter_body.3, metadata={op_name="jit(_decode_step)/while/body/closed_call/moe_commit/scatter"}
  %moe_ffn.14 = bf16[4]{0} custom-call(%copy.12), custom_call_target="tpu_custom_call", metadata={op_name="jit(_decode_step)/while/body/closed_call/moe_experts/jit(moe_ffn)/pallas_call"}
  %fusion.15 = bf16[4]{0} fusion(%moe_ffn.14), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_decode_step)/while/body/closed_call/attn/dot_general"}
  %iota.16 = s32[4]{0} iota(), iota_dimension=0
  ROOT %tuple.17 = (bf16[4]{0}) tuple(%fusion.15)
}

ENTRY %main.18 (a.19: bf16[4]) -> (bf16[4]) {
  %a.19 = bf16[4]{0} parameter(0)
  ROOT %while.21 = (bf16[4]{0}) while(%a.19), condition=%cond.20, body=%region_0.7, metadata={op_name="jit(_decode_step)/while"}
}
"""


def test_scope_of_names_the_stage():
    assert scopes.scope_of(
        "jit(_decode_step)/while/body/closed_call/moe_gather/gather") \
        == "moe_gather"
    assert scopes.scope_of("jit(_decode_step)/while") == scopes.NO_SCOPE
    assert scopes.scope_of("jit(f)/attn/jit(moe_ffn)/moe_experts") == "attn"


def test_parse_hlo_inherits_scopes_where_xla_left_none():
    table = scopes.parse_hlo(HLO)
    assert table["gather.11"] == ("fusion", "moe_gather", True)
    assert table["moe_ffn.14"] == ("custom-call", "moe_experts", True)
    # no metadata: the producer's scope, not the shared constant's
    assert table["copy.12"] == ("copy", "moe_gather", False)
    assert table["broadcast.10"] == ("broadcast", "moe_gather", False)
    # inside a loop XLA built for a scatter: the loop's scope
    assert table["gte.5"] == ("get-tuple-element", "moe_commit", False)
    assert table["add.2"][1] == "moe_gather"
    # the layer loop itself names no stage
    assert table["while.21"] == ("while", scopes.NO_SCOPE, True)


def _op(name, opcode, s, e):
    return (f"%{name} = bf16[4]{{0}} {opcode}(bf16[4]{{0}} %x)", s, e)


def _ctx(tr, cell=None):
    return trace.Context(cell=cell, model=None, served=[], t0=0, t1=10 ** 4,
                         stats0=None, stats1=None, peak={}, trace=tr)


def _decode_trace():
    """Two decode runs inside the slice [0, 1000], one crossing its end;
    self times: layer loop 80 + 40, gather 200 + 100, ffn 100, attn
    60 + 60, the commit's scatter loop 60."""
    ops = [_op("while.21", "while", 100, 600),
           _op("gather.11", "fusion", 110, 310),
           _op("moe_ffn.14", "custom-call", 320, 420),
           _op("fusion.15", "fusion", 430, 490),
           _op("while.13", "while", 500, 560),
           _op("while.21", "while", 700, 900),
           _op("gather.11", "fusion", 710, 810),
           _op("fusion.15", "fusion", 820, 880),
           _op("while.21", "while", 950, 1100),
           _op("gather.11", "fusion", 960, 1090)]
    modules = [("jit__decode_step(1)", 90, 610),
               ("jit__decode_step(1)", 690, 910),
               ("jit__decode_step(1)", 940, 1110)]
    return trace.Reduction(a=0, b=1000, ops=ops, modules=modules, spans=[])


def test_device_readers_count_whole_decode_runs(monkeypatch, capsys):
    monkeypatch.setattr(scopes, "decode_scopes",
                        lambda ctx: scopes.parse_hlo(HLO))
    ctx = _ctx(_decode_trace())
    r = scopes.reading(ctx)
    assert r.steps == 2                        # the third crosses b
    # gather 200 + 100, the commit loop 60, over two steps
    assert spec.load_reader(ROOT, "expert_cache_ms")(ctx) == \
        pytest.approx((200 + 100 + 60) / 2 / 1e6)
    assert spec.load_reader(ROOT, "attention_ms")(ctx) == \
        pytest.approx((60 + 60) / 2 / 1e6)
    assert r.ms["moe_experts"] == pytest.approx(100 / 2 / 1e6)
    # the layer loop's own time: 500 - 200 - 100 - 60 - 60 and 200 - 160
    assert r.ms[scopes.NO_SCOPE] == pytest.approx((80 + 40) / 2 / 1e6)
    assert r.unscoped_share == pytest.approx(120 / 700)
    err = capsys.readouterr().err
    assert "2 decode step(s)" in err and "under no scope" in err


def test_device_readers_read_nothing_they_cannot_place(monkeypatch):
    table = scopes.parse_hlo(HLO)
    # a program without scopes (the same step before they were added)
    monkeypatch.setattr(scopes, "decode_scopes", lambda ctx: {
        n: (op, scopes.NO_SCOPE, own) for n, (op, _, own) in table.items()})
    assert spec.load_reader(ROOT, "expert_cache_ms")(
        _ctx(_decode_trace())) is None
    # a trace instruction the compiled step does not hold
    monkeypatch.setattr(scopes, "decode_scopes", lambda ctx: {
        n: v for n, v in table.items() if n != "fusion.15"})
    assert spec.load_reader(ROOT, "attention_ms")(
        _ctx(_decode_trace())) is None
    # no compiler for this cell
    def refuse(ctx):
        raise RuntimeError("no backend")
    monkeypatch.setattr(scopes, "decode_scopes", refuse)
    assert spec.load_reader(ROOT, "attention_ms")(
        _ctx(_decode_trace())) is None
    # no whole decode run in the slice
    tr = dataclasses.replace(_decode_trace(), b=500)
    assert spec.load_reader(ROOT, "expert_cache_ms")(_ctx(tr)) is None


def _span(track, name, s, e, args=None):
    return trace.Span(track, name, s, e, args)


def _step(t, admitted=0, warming=0):
    """One tick at t with a decode step: dispatch ends at t + 20, the
    wait at t + 120; the tick's admission args as given."""
    args = {"admitted": admitted, "warming": warming, "decoded": 1}
    return [_span("sched", "tick", t, t + 200, args),
            _span("sched", "admission", t, t + 5, args),
            _span("engine", "dispatch", t + 10, t + 20),
            _span("engine", "wait", t + 20, t + 120),
            _span("engine", "drain", t + 120, t + 130)]


def test_host_gap_leaves_out_ticks_with_admission_work():
    spans = (_step(0) + _step(200) + _step(400, admitted=1)
             + _step(600, warming=1) + _step(800))
    tr = trace.Reduction(a=0, b=10 ** 4, ops=[], modules=[], spans=spans)
    # pairs (0, 200) and (600, 800): wait end -> next dispatch end
    assert spec.load_reader(ROOT, "host_gap_ms")(_ctx(tr)) == \
        pytest.approx((220 - 120) / 1e6)
    only_admission = trace.Reduction(a=0, b=10 ** 4, ops=[], modules=[],
                                     spans=_step(0) + _step(200, 1))
    assert spec.load_reader(ROOT, "host_gap_ms")(_ctx(only_admission)) \
        is None


def test_every_reader_on_the_existing_synthetic_trace(monkeypatch):
    """The five readers that came before give their numbers on the
    trace their own tests use; the new ones find nothing there."""
    cell = spec.load_cell(ROOT, "mixtral.single-decode")
    req = harness.Served(0, np.zeros(200, np.int32), 8)
    req.times, req.tokens = [960, 990], [1, 2]
    ctx = trace.Context(
        cell=cell, model=cell.model, served=[req],
        t0=0, t1=1000, stats0=type("S", (), {"hits": 10, "accesses": 40}),
        stats1=type("S", (), {"hits": 40, "accesses": 100}),
        peak=spec.peaks(ROOT, "TPU v5 lite"), trace=_reduction())
    monkeypatch.setattr(scopes, "decode_scopes",
                        lambda ctx: scopes.parse_hlo(HLO))
    read = {m["name"]: spec.load_reader(ROOT, m["name"])(ctx)
            for m in cell.per_layer}
    assert read["decode_step_ms"] == pytest.approx(370 / 1e6)
    assert read["expert_hit_rate"] == pytest.approx(50.0)
    assert read["device_idle"] == pytest.approx(52.0)
    # one token decoded in the 1 us slice, after a 200-token prompt
    assert read["decode_mfu"] == pytest.approx(
        100.0 * work.decode_token_flops(ctx.model, 201)
        / (1e-6 * ctx.peak["bf16_flops_per_s"]))
    # one decode run, its two kernels 50 + 30 ns
    k = ctx.model.moe.top_k
    need = ctx.model.num_layers * work.least_seconds(
        *work.decode_gmm(ctx.model, unique_experts=k, rows=k), ctx.peak)
    assert read["gmm_roofline"] == pytest.approx(100.0 * need / 80e-9)
    # the synthetic trace's instructions are not the compiled step's
    for name in ("expert_cache_ms", "attention_ms", "host_gap_ms"):
        assert read[name] is None, name
