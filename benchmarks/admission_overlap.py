"""Admission head-of-line-blocking microbenchmark: synchronous vs
overlapped chunk-interleaved prefill vs segment-streamed prefill.

Serves a small batch of *established* short-prompt requests through the
continuous-batching scheduler, then admits a LONG-prompt newcomer
mid-stream and measures the established requests' inter-token latency
around the admission — the paper-regime pathology this repo's PR 5 fixes.
With synchronous admission (``admit_chunks_per_tick=0``) the newcomer's
whole cache-warming replay runs on the admission tick, stalling every
in-flight decode for the full prompt; with overlapped admission the slot
sits in the PREFILLING phase and replays at most one chunk per tick
between decode steps — but the full-prompt prefill FORWARD still runs on
the admission tick. Segment-streamed prefill (``prefill_segment``)
removes that last O(prompt) step too: the admission tick only allocates,
and each tick forwards ONE segment (KV append + cache warm fused), so
the worst established-request gap is bounded by a segment.

Reported per mode (off / on / seg): TTFT/TPOT/stall p50/p99 from the
scheduler's streaming log-bucket histograms (``RunStats`` carries them —
no ad-hoc percentile math over collected gap lists) and the *stall* (max
established inter-token gap, i.e. the admission tick, which the
self-checks gate on). A second episode measures prefix-skip TTFT: under paged
KV + retention, a repeat admission of an identical prompt skips the
shared span's forward outright — time-to-first-token and forwarded
tokens both drop, tokens stay identical.
Self-checks:
  * established requests' decode tokens are BIT-identical across all
    three modes (prefill pacing never touches numerics) — and so are
    the newcomer's;
  * the median-over-repeats stall is strictly lower with overlap on
    than off, and strictly lower again with segment streaming;
  * the prefix-hit admission forwards fewer tokens than the cold one
    and produces the identical output tokens.

    PYTHONPATH=src python -m benchmarks.admission_overlap [--json PATH]
        [--repeats 2] [--long-prompt 48] [--chunk 4]
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.launch.compile_cache import enable_compile_cache

from .common import dump_json, emit, record_run

SLOTS = 3
ESTABLISHED = 2
EST_PROMPT = 6
EST_TOKENS = 24
NEW_TOKENS = 4
MODES = (("off", 0, 0), ("on", 1, 0), ("seg", 1, 1))
PREFIX_PROMPT = 32
PREFIX_TOKENS = 6


def serve_once(admit_chunks: int, long_prompt: int, chunk: int, seed: int,
               segment: int = 0):
    """One admission episode. Returns (established outputs {rid: tokens},
    newcomer tokens, established inter-token gaps [s] from the admission
    window, RunStats)."""
    from repro.config import get_config, reduced
    from repro.serving import build

    cfg = reduced(get_config("mixtral-8x7b"))
    _, sched = build(cfg,
                     serving=dict(max_batch=SLOTS,
                                  capacity=long_prompt + NEW_TOKENS + 8,
                                  prefill_chunk=chunk,
                                  prefill_segment=segment,
                                  admit_chunks_per_tick=admit_chunks),
                     seed=seed)
    rng = np.random.default_rng(seed)
    stamps = {}

    def stamp(rid):
        return lambda tok, done: stamps[rid].append(time.perf_counter())

    est = []
    for _ in range(ESTABLISHED):
        r = sched.submit(rng.integers(0, cfg.vocab_size, EST_PROMPT),
                         max_new_tokens=EST_TOKENS)
        stamps[r.rid] = []
        r.on_token = stamp(r.rid)
        est.append(r)

    # establish + warm the compile caches (prefill trace, warm chunk,
    # decode step) before any timing: the first ticks pay tracing/lowering
    for _ in range(6):
        sched.step()
    t_submit = time.perf_counter()
    newcomer = sched.submit(rng.integers(0, cfg.vocab_size, long_prompt),
                            max_new_tokens=NEW_TOKENS)
    outs = sched.run()
    stats = sched.stats

    gaps = []
    for r in est:
        # anchor the window at the submit instant: the first gap is then
        # exactly the established request's wait across the admission
        # tick (prefill trace + however much warm replay the mode runs)
        ts = [t_submit] + [t for t in stamps[r.rid] if t >= t_submit]
        gaps += list(np.diff(ts))
    return ({r.rid: outs[r.rid] for r in est}, outs[newcomer.rid],
            np.asarray(gaps), stats)


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="also write the results to this BENCH_*.json path")
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--long-prompt", type=int, default=48)
    ap.add_argument("--chunk", type=int, default=4)
    args, _ = ap.parse_known_args()
    n_chunks = -(-args.long_prompt // args.chunk)

    print(f"=== admission overlap: {ESTABLISHED} established requests, "
          f"{args.long_prompt}-token prompt admits mid-stream "
          f"({n_chunks} warm chunks / segments) ===")
    stalls = {name: [] for name, _, _ in MODES}
    last = {}
    for rep in range(args.repeats):
        for name, admit, seg in MODES:
            est, new, gaps, stats = serve_once(
                admit, args.long_prompt, args.chunk, seed=rep,
                segment=seg * args.chunk)
            stalls[name].append(float(gaps.max()))
            last[name] = (est, new, stats)

    for name, _, _ in MODES:
        stall = float(np.median(stalls[name]))
        stats = last[name][2]
        # percentiles from the scheduler's streaming log-bucket
        # histograms (last repeat) — RunStats carries them, replacing
        # the np.percentile math over hand-collected gap lists
        emit(f"admission_overlap.ttft_p50.{name}",
             stats.ttft_ms_p50 * 1e3,
             f"TTFT p50 (streaming histogram, mode {name}, "
             f"p99={stats.ttft_ms_p99 * 1e3:.0f}us)")
        emit(f"admission_overlap.tpot_p50.{name}",
             stats.tpot_ms_p50 * 1e3,
             f"inter-token p50 (streaming histogram, mode {name}, "
             f"p99={stats.tpot_ms_p99 * 1e3:.0f}us)")
        emit(f"admission_overlap.stall_p99.{name}",
             stats.stall_ms_p99 * 1e3,
             f"admission-work stall p99 absorbed by the decode loop "
             f"(streaming histogram, mode {name})")
        emit(f"admission_overlap.stall.{name}", stall * 1e6,
             f"max established inter-token gap during admission "
             f"(median of {args.repeats} repeats)")
        record_run(f"admission_overlap.{name}", stats)

    # self-check 1: prefill pacing never changes tokens — established
    # AND newcomer decode bit-identical across all three modes
    est_off, new_off, _ = last["off"]
    for name in ("on", "seg"):
        est_m, new_m, _ = last[name]
        assert sorted(est_m) == sorted(est_off)
        for rid in est_off:
            np.testing.assert_array_equal(est_m[rid], est_off[rid])
        np.testing.assert_array_equal(new_m, new_off)
    print("[self-check OK] established + newcomer tokens bit-identical "
          "(off vs on vs seg)")

    # self-check 2: the head-of-line stall really shrank — overlap moves
    # the warm replay off the admission tick, segment streaming moves
    # the prefill forward itself off it too
    stall_off = float(np.median(stalls["off"]))
    stall_on = float(np.median(stalls["on"]))
    stall_seg = float(np.median(stalls["seg"]))
    assert stall_on < stall_off, \
        ("overlapped admission must lower the established-request stall",
         stall_on, stall_off)
    assert stall_seg < stall_on, \
        ("segment-streamed prefill must lower the stall below the "
         "overlapped replay (the full-prompt forward left the admission "
         "tick)", stall_seg, stall_on)
    print(f"[self-check OK] admission stall {stall_off * 1e3:.1f} -> "
          f"{stall_on * 1e3:.1f} -> {stall_seg * 1e3:.1f} ms "
          f"(seg {(1 - stall_seg / max(stall_off, 1e-12)) * 100:.0f}% "
          f"below sync)")

    prefix_ttft(args)
    if args.json:
        dump_json(args.json)


def prefix_ttft(args) -> None:
    """Prefix-skip episode: paged KV + retention + segment streaming.

    Admits a PREFIX_PROMPT-token request cold, retires it, then admits
    the IDENTICAL prompt again — the prefix index serves the repeat from
    retained pages and the segment stream starts past the shared span,
    so only the last prompt token forwards. Measures time-to-first-token
    for both and self-checks: fewer forwarded prompt tokens, skipped
    tokens counted, identical output tokens."""
    from repro.config import get_config, reduced
    from repro.serving import build

    cfg = reduced(get_config("mixtral-8x7b"))
    cap = -(-(PREFIX_PROMPT + PREFIX_TOKENS + 8) // 4) * 4
    _, sched = build(cfg,
                     serving=dict(max_batch=2,
                                  capacity=cap,
                                  prefill_chunk=args.chunk,
                                  prefill_segment=args.chunk,
                                  admit_chunks_per_tick=1,
                                  kv_paged=True, page_size=4,
                                  prefix_keep_pages=64),
                     seed=0)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, PREFIX_PROMPT)
    warmup = rng.integers(0, cfg.vocab_size, PREFIX_PROMPT)
    engine = sched.engine

    def admit_once(p):
        first = []
        t0 = time.perf_counter()
        req = sched.submit(p, max_new_tokens=PREFIX_TOKENS,
                           on_token=lambda tok, done:
                           first.append(time.perf_counter())
                           if not first else None)
        before = engine.stats.prefill_tokens
        outs = sched.run()
        return (first[0] - t0, outs[req.rid],
                engine.stats.prefill_tokens - before)

    # one throwaway admission (DIFFERENT prompt — it must not seed the
    # prefix index for the measured pair) warms the compile caches so
    # the cold/hit TTFT contrast measures work, not tracing
    admit_once(warmup)
    ttft_cold, out_cold, fwd_cold = admit_once(prompt)
    ttft_hit, out_hit, fwd_hit = admit_once(prompt)
    stats = engine.stats
    emit("admission_overlap.prefix_ttft.cold", ttft_cold * 1e6,
         f"TTFT, cold {PREFIX_PROMPT}-token prompt (segmented, paged)")
    emit("admission_overlap.prefix_ttft.hit", ttft_hit * 1e6,
         f"TTFT, identical prompt re-admitted (prefix pages retained)")
    record_run("admission_overlap.prefix", sched.stats)

    np.testing.assert_array_equal(out_cold, out_hit)
    assert fwd_hit < fwd_cold, \
        ("prefix hit must forward fewer prompt tokens", fwd_hit, fwd_cold)
    assert stats.prefix_tokens_skipped > 0
    print(f"[self-check OK] prefix skip: {fwd_cold} -> {fwd_hit} forwarded "
          f"prompt tokens, {stats.prefix_tokens_skipped} skipped, tokens "
          f"identical; TTFT {ttft_cold * 1e3:.1f} -> {ttft_hit * 1e3:.1f} ms")


if __name__ == "__main__":
    main()
