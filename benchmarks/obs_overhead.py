"""Tracing-overhead benchmark: the instrumented serving stack with a live
``TraceRecorder`` vs the default no-op recorder.

The drain-point design (repro.obs: plain ``perf_counter_ns`` reads in the
hot path, emission only at the ``_obs_*`` drain helpers, never a device
sync) claims the trace is close to free. This benchmark pins that claim:
it serves the SAME workload (same seed, same prompts) twice per repeat —
once untraced, once with a recorder — and checks

  * tokens are BIT-identical traced vs untraced (observability never
    touches numerics), and
  * the median tokens/s delta across repeats stays under 5%, and
  * the captured trace validates as Chrome trace-event JSON with every
    request's full lifecycle covered.

Both arms build a fresh stack, so compile/tracing costs are symmetric;
the arms interleave within each repeat so drift hits both equally.

    PYTHONPATH=src python -m benchmarks.obs_overhead [--json PATH]
        [--repeats 3]
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.obs import TraceRecorder, chrome_trace, validate_chrome_trace
from repro.obs.export import lifecycle_coverage

from .common import check, dump_json, emit, record_run, run_live_scheduler

SLOTS = 3
REQUESTS = 5
NEW_TOKENS = 16
OVERHEAD_TOL = 0.05


def serve(recorder=None):
    return run_live_scheduler(slots=SLOTS, requests=REQUESTS,
                              new_tokens=NEW_TOKENS, recorder=recorder)


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="also write the results to this BENCH_*.json path")
    ap.add_argument("--repeats", type=int, default=5)
    args, _ = ap.parse_known_args()

    print(f"=== observability overhead: traced vs no-op recorder, "
          f"{REQUESTS} requests x {NEW_TOKENS} tokens, "
          f"median of {args.repeats} repeats ===")
    # throwaway run warms the XLA executable cache so the measured pairs
    # compare steady-state serving, not first-compile
    serve()

    tok_off, tok_on = [], []
    outs_off = outs_on = stats_on = rec = None
    for rep in range(args.repeats):
        # alternate arm order so slow drift (thermal, background load)
        # hits both arms symmetrically across the repeat set
        if rep % 2 == 0:
            outs_off, _, dt_off = serve()
            rec = TraceRecorder()
            outs_on, stats_on, dt_on = serve(rec)
        else:
            rec = TraceRecorder()
            outs_on, stats_on, dt_on = serve(rec)
            outs_off, _, dt_off = serve()
        total = sum(len(o) for o in outs_off.values())
        tok_off.append(total / dt_off)
        tok_on.append(sum(len(o) for o in outs_on.values()) / dt_on)

    # self-check 1: tracing never touches numerics
    assert sorted(outs_on) == sorted(outs_off)
    for rid in outs_off:
        np.testing.assert_array_equal(outs_on[rid], outs_off[rid])
    print("[self-check OK] tokens bit-identical traced vs untraced")

    # self-check 2: the trace itself is well-formed and complete
    doc = chrome_trace(rec)
    problems = validate_chrome_trace(doc)
    assert not problems, problems
    cover = lifecycle_coverage(doc)
    assert len(cover) == REQUESTS, sorted(cover)
    for track, spans in cover.items():
        assert {"queued", "prefill", "decode"} <= spans, (track, spans)
    print(f"[self-check OK] trace valid, {len(rec)} events, "
          f"{len(cover)} request lifecycles covered")

    r_off = float(np.median(tok_off))
    r_on = float(np.median(tok_on))
    delta = abs(r_on - r_off) / max(r_off, 1e-12)
    emit("obs_overhead.tok_s.untraced", r_off * 1e6,
         "median wall tok/s, no-op recorder")
    emit("obs_overhead.tok_s.traced", r_on * 1e6,
         "median wall tok/s, live TraceRecorder")
    emit("obs_overhead.overhead_pct", delta * 100,
         f"|traced - untraced| / untraced (bound {OVERHEAD_TOL:.0%})")
    record_run("obs_overhead.traced", stats_on)
    print(check("obs_overhead.tok_s_ratio", r_on / r_off, 1.0,
                OVERHEAD_TOL))

    # self-check 3: the overhead bound the drain-point design promises
    assert delta <= OVERHEAD_TOL, \
        ("tracing overhead above bound", delta, r_off, r_on)
    print(f"[self-check OK] tracing overhead {delta:.1%} "
          f"(bound {OVERHEAD_TOL:.0%})")

    if args.json:
        dump_json(args.json)


if __name__ == "__main__":
    main()
