"""Serving driver: collaborative two-tier MoE engine (the paper) with
continuous batching, or the plain generic path for non-MoE archs.

    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b \
        --tokens 64 [--ways 4 --indexes 8 --policy lru] \
        [--concurrency 4 --requests 8] [--temperature 0.8 --top-p 0.95] \
        [--prefetch --prefetch-min-prob 0.2] \
        [--host-compute --host-threads 8 --host-backend callback] \
        [--kv-paged --page-size 16 --kv-pages 64] \
        [--prefill-segment 8 --prefix-keep-pages 16] [--layers 2]

Without ``--layers`` the model is ``reduced()`` (tiny widths, for the CPU);
``--layers N`` serves the published config at every published width with
only its depth cut to N layers (for the chip). Prints tokens/s and the
paper's cache counters.
``--temperature > 0`` turns on per-request sampling (seeded per request:
request r uses seed ``--seed + r``); the default is greedy decoding.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import get_config, reduced, with_layers
from repro.launch.compile_cache import enable_compile_cache
from repro.models import decode_step, init_params, prefill
from repro.obs import TraceRecorder, write_chrome_trace
from repro.serving import SamplingParams, build


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--layers", type=int, default=None,
                    help="serve the published config at its published "
                         "widths, cut to this many layers (default: the "
                         "tiny reduced() geometry)")
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--prompt", type=int, default=32)
    ap.add_argument("--indexes", type=int, default=None)
    ap.add_argument("--ways", type=int, default=2)
    ap.add_argument("--policy", default="lru",
                    choices=["lru", "fifo", "random"])
    ap.add_argument("--concurrency", type=int, default=4,
                    help="scheduler slots (padded decode batch T)")
    ap.add_argument("--requests", type=int, default=None,
                    help="total requests to serve (default: concurrency*2)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="> 0: per-request temperature sampling "
                         "(0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="cache-warming chunked-prefill chunk "
                         "(0 = bypass prefill, cold cache)")
    ap.add_argument("--prefill-segment", type=int, default=0,
                    help="segment-streamed prefill: forward the prompt in "
                         "this-many-token segments between decode ticks, "
                         "fusing KV append and cache warm per segment "
                         "(0 = one full-prompt forward at admission)")
    ap.add_argument("--prefix-keep-pages", type=int, default=0,
                    help="with --kv-paged: park up to this many zero-ref "
                         "prefix-indexed pages in an eviction LRU at "
                         "request retirement so same-prefix admissions "
                         "can adopt them (0 = free eagerly)")
    ap.add_argument("--admit-chunks-per-tick", type=int, default=0,
                    help="overlapped admission: advance a newly admitted "
                         "request's cache-warming replay by at most this "
                         "many chunks per tick between decode steps, so "
                         "established requests keep decoding while it "
                         "warms (0 = synchronous admission)")
    ap.add_argument("--admit-slots-per-tick", type=int, default=0,
                    help="overlapped admission: at most this many "
                         "admitting slots advance per tick, the earliest "
                         "admitted first, so a decode step waits behind "
                         "at most this many slots' prefill (0 = all)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bound the scheduler queue; a full queue blocks "
                         "submit() (backpressure) instead of growing "
                         "without limit")
    ap.add_argument("--prefetch", action="store_true",
                    help="cross-layer speculative expert prefetch")
    ap.add_argument("--prefetch-min-prob", type=float, default=0.0,
                    help="confidence gate: only reserve predicted experts "
                         "whose router probability clears this threshold "
                         "(implies --prefetch when > 0)")
    ap.add_argument("--host-compute", action="store_true",
                    help="compute cache-miss experts on the CPU when the "
                         "cost model favors it over the weight fetch "
                         "(repro.hostexec)")
    ap.add_argument("--host-threads", type=int, default=8,
                    help="host executor threads (also the cost model's "
                         "OMP thread count)")
    ap.add_argument("--host-fuse-small", type=int, default=4,
                    help="batch same-step CPU-miss groups with at most "
                         "this many valid tokens into one stacked numpy "
                         "matmul instead of one pool task each (0 = "
                         "never fuse)")
    ap.add_argument("--no-prefetch-rank-votes", action="store_false",
                    dest="prefetch_rank_votes",
                    help="disable vote-count ranking of speculative "
                         "prefetch reservations (default: experts many "
                         "rows predict claim cache ways first)")
    ap.add_argument("--host-backend", default="callback",
                    choices=["callback", "jax"],
                    help="host lane: real numpy thread pool (callback) or "
                         "the bit-exact in-graph fallback (jax)")
    ap.add_argument("--kv-paged", action="store_true",
                    help="paged KV pool with prefix sharing (per-request "
                         "page tables over one global page pool; "
                         "bit-identical tokens to the dense cache)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV tokens per page (with --kv-paged)")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="page pool size (default: dense-equivalent "
                         "slots*capacity/page_size)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON of the run "
                         "(request lifecycles, step phases, lane "
                         "counters; open in ui.perfetto.dev or "
                         "chrome://tracing; collaborative path only)")
    ap.add_argument("--metrics-every", type=int, default=0, metavar="N",
                    help="print a periodic latency summary every N "
                         "scheduler ticks (p50/p99 TTFT/TPOT/stall from "
                         "the streaming histograms; 0 = off)")
    args = ap.parse_args()
    if not 0.0 < args.top_p <= 1.0:
        ap.error(f"--top-p must be in (0, 1], got {args.top_p}")
    if args.top_k < 0:
        ap.error(f"--top-k must be >= 0, got {args.top_k}")
    if args.temperature < 0:
        ap.error(f"--temperature must be >= 0, got {args.temperature}")

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.layers is None:
        cfg = reduced(cfg)
    else:
        try:
            cfg = with_layers(cfg, args.layers)
        except ValueError as e:
            ap.error(str(e))
    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, key)
    prompt = np.asarray(
        jax.random.randint(key, (args.batch, args.prompt), 0, cfg.vocab_size),
        np.int32)

    # any sampling knob enables sampling (top-k/top-p without an explicit
    # temperature sample at T=1.0 rather than being silently ignored)
    sample_on = args.temperature > 0 or args.top_k > 0 or args.top_p < 1.0
    temp = args.temperature if args.temperature > 0 else 1.0

    if cfg.moe is not None and cfg.moe_every == 1 and not cfg.is_encdec:
        n = args.indexes if args.indexes is not None else cfg.num_layers // 2
        R = args.requests or args.concurrency * 2
        prefetch = args.prefetch or args.prefetch_min_prob > 0
        capacity = args.prompt + args.tokens + 1
        if args.kv_paged:
            # paged KV slices the per-request capacity into whole pages
            capacity = -(-capacity // args.page_size) * args.page_size
        print(f"[serve] collaborative engine: {cfg.name} cache=(N={n}, "
              f"M={args.ways}, {args.policy}) slots={args.concurrency} "
              f"requests={R} "
              f"sampling={f'T={temp}' if sample_on else 'greedy'}"
              + (f" prefetch(min_prob={args.prefetch_min_prob})"
                 if prefetch else "")
              + (f" overlap_admit({args.admit_chunks_per_tick} chunks/tick)"
                 if args.admit_chunks_per_tick else "")
              + (f" segmented_prefill({args.prefill_segment} tok/seg)"
                 if args.prefill_segment else "")
              + (f" max_queue={args.max_queue}"
                 if args.max_queue is not None else "")
              + (f" host_compute({args.host_backend}, "
                 f"{args.host_threads}t)" if args.host_compute else "")
              + (f" kv_paged(page_size={args.page_size})"
                 if args.kv_paged else ""))
        recorder = TraceRecorder() if args.trace_out else None
        _, sched = build(
            cfg,
            cache=dict(num_indexes=n, num_ways=args.ways,
                       policy=args.policy),
            serving=dict(max_batch=args.concurrency,
                         capacity=capacity,
                         prefill_chunk=args.prefill_chunk,
                         prefill_segment=args.prefill_segment,
                         admit_chunks_per_tick=args.admit_chunks_per_tick,
                         admit_slots_per_tick=args.admit_slots_per_tick,
                         prefetch=prefetch,
                         prefetch_min_prob=args.prefetch_min_prob,
                         prefetch_rank_votes=args.prefetch_rank_votes,
                         host_compute=args.host_compute,
                         host_threads=args.host_threads,
                         host_backend=args.host_backend,
                         host_fuse_small=args.host_fuse_small,
                         kv_paged=args.kv_paged,
                         page_size=args.page_size,
                         kv_pages=args.kv_pages,
                         prefix_keep_pages=args.prefix_keep_pages),
            seed=args.seed, params=params, max_queue=args.max_queue,
            recorder=recorder)
        rng = np.random.default_rng(args.seed)
        for r in range(R):
            plen = int(rng.integers(max(args.prompt // 2, 1),
                                    args.prompt + 1))
            sp = SamplingParams(greedy=False, temperature=temp,
                                top_k=args.top_k, top_p=args.top_p,
                                seed=args.seed + r) if sample_on \
                else SamplingParams()
            sched.submit(rng.integers(0, cfg.vocab_size, plen),
                         max_new_tokens=args.tokens, sampling=sp)
        t0 = time.time()
        if args.metrics_every > 0:
            # step-driven drain so the periodic summary can fire between
            # ticks; sched.run() is the one-shot equivalent
            done, tick = 0, 0
            while done < R:
                done += len(sched.step())
                tick += 1
                if tick % args.metrics_every == 0:
                    s = sched.stats
                    print(f"  [metrics] tick={tick} "
                          f"finished={s.requests_finished} "
                          f"active={s.requests_active} "
                          f"queued={s.requests_queued} | "
                          f"ttft_ms {s.ttft_ms_p50:.1f}/"
                          f"{s.ttft_ms_p99:.1f} "
                          f"tpot_ms {s.tpot_ms_p50:.2f}/"
                          f"{s.tpot_ms_p99:.2f} "
                          f"stall_ms {s.stall_ms_p50:.2f}/"
                          f"{s.stall_ms_p99:.2f} (p50/p99)")
            outs = {req.rid: req.output for req in sched.finished}
        else:
            outs = sched.run()
        dt = time.time() - t0
        stats = sched.stats
        total = sum(len(o) for o in outs.values())
        assert total == stats.generated_tokens, (total, stats.generated_tokens)
        print(f"  served {stats.requests_finished} requests / {total} tokens "
              f"in {dt:.2f}s ({total / dt:.1f} tok/s wall, "
              f"{stats.steps} decode steps, "
              f"{stats.admission_stalls} admission stalls)")
        print(f"  cache hit rate: {stats.hit_rate:.3f} "
              f"(hits={stats.hits} accesses={stats.accesses} "
              f"fetches={stats.fetched_experts})")
        if stats.prefill_accesses:
            print(f"  prefill warming: {stats.prefill_tokens} tokens / "
                  f"{stats.prefill_chunks} chunks, hit rate "
                  f"{stats.prefill_hit_rate:.3f} "
                  f"({stats.prefill_fetched} fetches)")
        if prefetch:
            print(f"  prefetch: issued={stats.prefetch_issued} "
                  f"spec_hits={stats.prefetch_hits} "
                  f"wasted={stats.prefetch_wasted} "
                  f"pred_acc={stats.prediction_accuracy:.3f}")
        if args.host_compute:
            print(f"  host execution: {stats.cpu_expert_calls} expert "
                  f"groups / {stats.cpu_tokens} assignments on CPU "
                  f"({stats.fused_groups} fused, offload rate "
                  f"{stats.cpu_offload_rate:.3f}, "
                  f"backend={args.host_backend})")
        if args.prefill_segment:
            print(f"  segmented prefill: {stats.prefill_segments} segments "
                  f"({args.prefill_segment} tok/seg), "
                  f"{stats.prefix_tokens_skipped} prefix tokens skipped")
        if args.kv_paged:
            print(f"  paged KV: page_size={args.page_size} "
                  f"pages_in_use={stats.kv_pages_in_use} "
                  f"prefix_hits={stats.prefix_hits} "
                  f"cow_forks={stats.cow_forks} "
                  f"prefix_pages_retained={stats.prefix_pages_retained}")
        print(f"  latency: ttft_ms p50={stats.ttft_ms_p50:.1f} "
              f"p99={stats.ttft_ms_p99:.1f}, "
              f"tpot_ms p50={stats.tpot_ms_p50:.2f} "
              f"p99={stats.tpot_ms_p99:.2f}, "
              f"stall_ms p50={stats.stall_ms_p50:.2f} "
              f"p99={stats.stall_ms_p99:.2f}")
        if args.trace_out:
            write_chrome_trace(recorder, args.trace_out)
            print(f"  trace: {len(recorder)} events "
                  f"({recorder.dropped} dropped) -> {args.trace_out}")
    else:
        print(f"[serve] generic path: {cfg.name}")
        batch = {"tokens": jnp.asarray(prompt)}
        if cfg.family == "audio":
            batch["frames"] = jax.random.normal(
                key, (args.batch, args.prompt, cfg.frontend_embed_dim),
                jnp.bfloat16)
        logits, state = jax.jit(lambda p, b: prefill(p, b, cfg))(params, batch)
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        dstep = jax.jit(lambda p, s, b: decode_step(p, s, b, cfg),
                        donate_argnums=(1,))
        outs = [np.asarray(tok)]
        t0 = time.time()
        for _ in range(args.tokens - 1):
            logits, state = dstep(params, state, {"tokens": tok})
            tok = jnp.argmax(logits[:, 0], -1)[:, None].astype(jnp.int32)
            outs.append(np.asarray(tok))
        dt = time.time() - t0
        print(f"  generated {np.concatenate(outs,1).shape} in {dt:.2f}s "
              f"({(args.tokens-1)*args.batch/max(dt,1e-9):.1f} tok/s wall)")


if __name__ == "__main__":
    main()
