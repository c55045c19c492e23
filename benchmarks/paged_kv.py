"""Paged-KV microbenchmark: memory footprint + admission-latency wins of
the paged KV pool with copy-on-write prefix sharing.

Three probes, all on the reduced-Mixtral serving stack:

1. **A/B bit-identity** — the same shared-prefix request fleet served
   through the continuous-batching scheduler with dense per-slot KV and
   with the paged pool; the generated tokens must match bitwise (paging
   changes memory layout and residency, never logits).
2. **Footprint** — the paged run's peak page occupancy versus the
   dense-equivalent page count (every resident request paying
   ``capacity / page_size`` pages); prefix sharing must hold strictly
   fewer pages.
3. **TTFT** — cold admission versus prefix-hit admission of the same
   prompt length through the engine's request primitives: the prefix hit
   must replay strictly fewer warm chunks and land strictly lower
   wall-clock (the shared span's routing already warmed the cache when
   the prefix holder was admitted).

Interpret-mode wall time is not the paper metric, but the chunk counts
and page accounting are exact, and the TTFT ordering tracks on real
hardware (the win is skipped work, not kernel speed).

    PYTHONPATH=src python -m benchmarks.paged_kv [--json PATH]
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache

from .common import dump_json, emit, record_run

SLOTS = 4
CAP = 64            # per-request KV capacity (tokens)
PS = 8              # page size (tokens)
PREFIX = 40         # shared prompt prefix (5 full pages)
SUFFIX = 8          # unique per-request tail
NEW = 12            # decode budget per request
REQUESTS = 6


def _prompts(vocab: int):
    rng = np.random.default_rng(11)
    prefix = rng.integers(0, vocab, PREFIX)
    return [np.concatenate([prefix, rng.integers(0, vocab, SUFFIX)])
            .astype(np.int32) for _ in range(REQUESTS)], prefix


def serve_fleet(kv_paged: bool):
    """One scheduler run over the shared-prefix fleet; returns
    (engine, outputs, RunStats)."""
    from repro.config import get_config, reduced
    from repro.serving import build

    cfg = reduced(get_config("mixtral-8x7b"))
    eng, sched = build(cfg, cache=dict(policy="lru"),
                       serving=dict(max_batch=SLOTS, capacity=CAP,
                                    prefill_chunk=PS, kv_paged=kv_paged,
                                    page_size=PS),
                       seed=0)
    prompts, _ = _prompts(cfg.vocab_size)
    for p in prompts:
        sched.submit(p, max_new_tokens=NEW)
    outs = sched.run()
    return eng, outs, sched.stats


def ttft_probe():
    """Cold vs prefix-hit admission latency through the engine
    primitives. Returns (cold_s, hit_s, cold_chunks, hit_chunks)."""
    from repro.config import get_config, reduced
    from repro.serving import build

    cfg = reduced(get_config("mixtral-8x7b"))
    eng, _ = build(cfg, serving=dict(max_batch=2, capacity=CAP,
                                     prefill_chunk=PS, kv_paged=True,
                                     page_size=PS),
                   seed=0)
    state = eng.init_slots()
    rng = np.random.default_rng(7)
    prefix = rng.integers(0, cfg.vocab_size, PREFIX)
    holder = np.concatenate(
        [prefix, rng.integers(0, cfg.vocab_size, SUFFIX)]).astype(np.int32)
    # admit the prefix holder: bind_slot registers its full-page prompt
    # prefixes in the pool's index, making later admissions shareable
    tkt = eng.start_prefill(holder, max_total_tokens=holder.size + 1)
    eng.advance_prefill(tkt, tkt.n_chunks)
    eng.bind_slot(state, tkt, 0)

    hit_p = np.concatenate(
        [prefix, rng.integers(0, cfg.vocab_size, SUFFIX)]).astype(np.int32)
    cold_p = rng.integers(0, cfg.vocab_size, holder.size).astype(np.int32)

    def probe(p):
        t0 = time.perf_counter()
        t = eng.start_prefill(p, max_total_tokens=p.size + 1)
        replayed = t.n_chunks - t.cursor
        eng.advance_prefill(t, t.n_chunks)
        jax.block_until_ready(t.logits)
        dt = time.perf_counter() - t0
        eng.kv_pool.free(t.table)   # probe only: never bound to a slot
        return dt, replayed

    probe(cold_p), probe(hit_p)               # compile both paths
    cold = [probe(cold_p) for _ in range(5)]
    hit = [probe(hit_p) for _ in range(5)]
    return (min(d for d, _ in cold), min(d for d, _ in hit),
            cold[0][1], hit[0][1])


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="also write the results to this BENCH_*.json path")
    args, _ = ap.parse_known_args()

    print("=== paged KV pool: prefix sharing vs dense per-slot cache ===")
    eng_d, outs_d, s_d = serve_fleet(kv_paged=False)
    eng_p, outs_p, s_p = serve_fleet(kv_paged=True)
    record_run("paged_kv.dense", s_d)
    record_run("paged_kv.paged", s_p)

    # 1) bit-identity: paging must never change the generated tokens
    assert sorted(outs_d) == sorted(outs_p)
    for rid in outs_d:
        np.testing.assert_array_equal(outs_d[rid], outs_p[rid])

    # 2) footprint: a fleet sharing a 5-page prefix must peak strictly
    #    below the dense-equivalent (every resident slot paying CAP/PS
    #    pages of private storage)
    pool = eng_p.kv_pool
    dense_eq = SLOTS * (CAP // PS)
    emit("paged_kv.peak_pages", float(pool.peak_pages_in_use),
         f"dense_equivalent={dense_eq} "
         f"prefix_hits={s_p.prefix_hits} "
         f"shared_tokens={pool.prefix_tokens_shared} "
         f"cow_forks={s_p.cow_forks}")
    assert pool.peak_pages_in_use < dense_eq, \
        ("prefix sharing must beat dense-equivalent page count",
         pool.peak_pages_in_use, dense_eq)
    assert s_p.prefix_hits >= 1, "shared-prefix fleet saw no prefix hits"
    assert pool.pages_in_use == 0, \
        ("drained fleet must return every page", pool.pages_in_use)
    pool.check_invariants()

    # 3) TTFT: a prefix-hit admission skips the shared span's warm replay
    cold_s, hit_s, cold_chunks, hit_chunks = ttft_probe()
    emit("paged_kv.ttft_cold_us", cold_s * 1e6,
         f"warm_chunks={cold_chunks}")
    emit("paged_kv.ttft_prefix_hit_us", hit_s * 1e6,
         f"warm_chunks={hit_chunks} "
         f"speedup={cold_s / max(hit_s, 1e-12):.2f}x")
    assert hit_chunks < cold_chunks, \
        ("prefix hit must skip shared-span warm chunks",
         hit_chunks, cold_chunks)
    assert hit_s < cold_s, \
        ("prefix-hit admission must be strictly faster", hit_s, cold_s)

    if args.json:
        dump_json(args.json)


if __name__ == "__main__":
    main()
