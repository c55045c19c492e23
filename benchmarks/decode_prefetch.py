"""Decode-step microbenchmark: cross-layer speculative prefetch on vs off.

Times one jitted decode step of the batched collaborative engine (reduced
Mixtral geometry, 4-slot batch, shared LRU expert cache) with
``EngineConfig.prefetch`` disabled and enabled, and reports the measured
demand hit rates and prefetch counters over a short greedy generation.

Interpret-mode wall time on this container is NOT the paper metric (the
calibrated simulator is — see fig5/fig6); what this harness pins down is
(a) the per-step cost of the prediction + reservation stages and (b) the
live hit-rate uplift, both of which should track on real hardware.

    PYTHONPATH=src python -m benchmarks.decode_prefetch [--json PATH]
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache

from .common import dump_json, emit, record_run, timeit

SLOTS = 4
STEPS = 24


def bench(prefetch: bool, rank_votes: bool = True):
    from repro.serving import build

    eng, _ = build("mixtral-8x7b",
                   serving=dict(max_batch=SLOTS, capacity=64,
                                prefetch=prefetch,
                                prefetch_rank_votes=rank_votes),
                   seed=0)
    cfg = eng.cfg

    # hit-rate probe: short greedy generation through the decode path
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(0),
                                           (SLOTS, 8), 0,
                                           cfg.vocab_size), np.int32)
    out, stats = eng.generate(prompt, steps=STEPS)

    # step-latency probe: one jitted decode step, steady state
    state = eng.init_slots()
    state["pos"] = jnp.full((SLOTS,), 8, jnp.int32)
    tok = np.zeros((SLOTS, 1), np.int32)
    active = jnp.ones((SLOTS,), bool)

    def step():
        nonlocal state
        logits, state = eng.decode_batch(tok, state, active)
        jax.block_until_ready(logits)

    us = timeit(step, iters=10, warmup=3)
    return us, stats, out


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="also write the results to this BENCH_*.json path")
    args, _ = ap.parse_known_args()

    print("=== decode step: cross-layer speculative prefetch on/off ===")
    us_off, s_off, _ = bench(prefetch=False)
    us_on, s_on, out_rv = bench(prefetch=True)
    record_run("decode_prefetch.off", s_off)
    record_run("decode_prefetch.on", s_on)
    # batch-aware reservation ranking self-check: vote-ranked claims must
    # not lose speculative hits vs insertion order, and never touch tokens
    _, s_nrv, out_nrv = bench(prefetch=True, rank_votes=False)
    assert np.array_equal(out_rv, out_nrv), \
        "rank_votes changed generated tokens (must be residency-only)"
    assert s_on.prefetch_hits >= s_nrv.prefetch_hits, \
        (s_on.prefetch_hits, s_nrv.prefetch_hits)
    emit("decode_step.rank_votes_spec_hits",
         float(s_on.prefetch_hits - s_nrv.prefetch_hits),
         f"spec hits {s_nrv.prefetch_hits} -> {s_on.prefetch_hits} with "
         f"vote-ranked reservations (tokens bit-identical)")
    hr_off = s_off.hit_rate
    hr_on = s_on.hit_rate
    emit("decode_step.prefetch_off", us_off,
         f"hit_rate={hr_off:.3f} ({SLOTS}-slot batch, lru 2-way)")
    emit("decode_step.prefetch_on", us_on,
         f"hit_rate={hr_on:.3f} overhead={us_on / us_off:.2f}x "
         f"pred_acc={s_on.prediction_accuracy:.3f} "
         f"issued={s_on.prefetch_issued} "
         f"spec_hits={s_on.prefetch_hits} "
         f"wasted={s_on.prefetch_wasted}")
    emit("decode_step.prefetch_hit_uplift", (hr_on - hr_off) * 1e6,
         f"demand hit rate {hr_off:.3f} -> {hr_on:.3f} on the same "
         f"prompts/weights (prefetch changes residency, never logits)")
    if args.json:
        dump_json(args.json)


if __name__ == "__main__":
    main()
