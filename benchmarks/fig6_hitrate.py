"""Paper Fig. 6: expert-cache hit rates by configuration and eviction
policy (LRU vs FIFO vs static-random + its closed form), for both models.

Two modes: calibrated synthetic traces (default, matches the paper's
measured router statistics) and --live, which captures real router
decisions from a reduced repro model.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.config import CacheConfig
from repro.core import (NumpyCache, TraceConfig, random_policy_hit_probs,
                        synthetic_trace)
from repro.core.costmodel import PAPER_TIMINGS
from repro.core.simulator import best_cache_config
from repro.launch.compile_cache import enable_compile_cache
from .common import emit

TRACES = {
    "mixtral-8x7b": TraceConfig(num_tokens=1500, num_layers=32, num_experts=8),
    "phi35-moe": TraceConfig(num_tokens=1500, num_layers=32, num_experts=16,
                             stickiness=0.50),
}


def run_policy(trace, ccfg: CacheConfig, num_experts: int):
    c = NumpyCache(ccfg, num_experts=num_experts, seed=3)
    anyh = both = 0
    T, L, K = trace.shape
    for t in range(T):
        for l in range(L):
            h = c.access(l, trace[t, l])
            anyh += any(h)
            both += all(h)
    return anyh / (T * L), both / (T * L)


def live_trace(steps: int = 200):
    import jax
    from repro.config import get_config, reduced
    from repro.core.router_trace import capture_trace
    from repro.models import init_params
    cfg = reduced(get_config("mixtral-8x7b"))
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    toks = jax.random.randint(key, (1, steps), 0, cfg.vocab_size)
    return capture_trace(cfg, params, toks), cfg.moe.num_experts


def live_serving(policy: str, prefetch: bool = False,
                 prefetch_min_prob: float = 0.0, rank_votes: bool = True):
    """Measured stats of the real serving path: the batched engine +
    continuous-batching scheduler, 4 concurrent requests sharing one
    expert cache (grouped gmm execution, per-slot KV positions, optional
    cross-layer speculative prefetch, optionally confidence-gated).
    Returns (outputs {rid: tokens}, RunStats)."""
    from .common import record_run, run_live_scheduler
    outs, stats, _ = run_live_scheduler(policy=policy, prefetch=prefetch,
                                        prefetch_min_prob=prefetch_min_prob,
                                        prefetch_rank_votes=rank_votes)
    gate = f".gate{prefetch_min_prob}" if prefetch_min_prob else ""
    rv = ".norank" if not rank_votes else ""
    record_run(f"fig6.live.{policy}{'.pf' if prefetch else ''}{gate}{rv}",
               stats)
    return outs, stats


def prefetch_uplift_sim() -> None:
    """Cross-layer speculative prefetch in the calibrated simulator: the
    window-gated speculative fetches convert next-layer misses into hits
    where the CPU expert compute leaves transfer bubbles (low thread
    counts); at saturated-link configurations the gate keeps prefetch
    out of the demand path's way (no regression by construction)."""
    from repro.core.simulator import simulate
    print("=== prefetch uplift (calibrated simulator, ours vs "
          "ours_prefetch) ===")
    for name, tm in PAPER_TIMINGS.items():
        trace = synthetic_trace(TRACES[name])
        for threads in (1, 8):
            for m, ccfg in best_cache_config(tm).items():
                base = simulate(trace, tm, threads, "ours", ccfg=ccfg)
                pf = simulate(trace, tm, threads, "ours_prefetch", ccfg=ccfg)
                emit(f"{name}.t{threads}.M{m}.prefetch_hit_rate",
                     pf.hit_rate * 1e6,
                     f"ours={base.hit_rate:.3f} tok_s={pf.tokens_per_s:.2f} "
                     f"vs {base.tokens_per_s:.2f} "
                     f"issued={pf.extra.get('prefetch_issued', 0)} "
                     f"wasted={pf.extra.get('prefetch_wasted', 0)}")
                # the window gate makes prefetch best-effort: it may be
                # neutral (gate closed) but must never lose throughput
                assert pf.tokens_per_s >= base.tokens_per_s * 0.995, \
                    (name, threads, m, pf.tokens_per_s, base.tokens_per_s)


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--live", action="store_true",
                    help="capture router trace from a live reduced model")
    args, _ = ap.parse_known_args()

    print("=== Fig. 6: hit rates by cache config x policy ===")
    for name, tm in PAPER_TIMINGS.items():
        trace = synthetic_trace(TRACES[name])
        E = tm.num_experts
        for m, ccfg in best_cache_config(tm).items():
            tag = f"{name}.(N={ccfg.num_indexes},M={m})"
            lru_any, lru_both = run_policy(
                trace, CacheConfig(ccfg.num_indexes, m, "lru"), E)
            fifo_any, _ = run_policy(
                trace, CacheConfig(ccfg.num_indexes, m, "fifo"), E)
            rnd_any, rnd_both = run_policy(
                trace, CacheConfig(ccfg.num_indexes, m, "random"), E)
            cf_any, cf_both = random_policy_hit_probs(E, m)
            # coverage-weighted closed form (layers >= N always miss)
            cov = min(ccfg.num_indexes, 32) / 32
            emit(f"{tag}.lru_any", lru_any * 1e6,
                 f"fifo={fifo_any:.3f} random={rnd_any:.3f} "
                 f"closed_form={cf_any*cov:.3f} both_lru={lru_both:.3f}")
            assert lru_any >= fifo_any - 0.02, "paper: LRU >= FIFO"
            assert lru_any >= rnd_any - 0.02, "paper: LRU beats random"

    prefetch_uplift_sim()

    if args.live:
        trace, E = live_trace()
        lru_any, _ = run_policy(
            trace, CacheConfig(trace.shape[1], 2, "lru"), E)
        rnd_any, _ = run_policy(
            trace, CacheConfig(trace.shape[1], 2, "random"), E)
        emit("live.mixtral_reduced.lru_any", lru_any * 1e6,
             f"random={rnd_any:.3f} (untrained router: near-chance reuse)")
        _, s_lru = live_serving("lru")
        served_lru = s_lru.hit_rate
        served_rnd = live_serving("random")[1].hit_rate
        emit("live.mixtral_reduced.served_lru_hit_rate", served_lru * 1e6,
             f"random={served_rnd:.3f} (batched scheduler, 4 slots sharing "
             f"one cache; per-assignment hit rate of the serving engine)")
        # cross-layer speculative prefetch on the SAME trace/engine/policy:
        # the demand hit rate must strictly improve (the pre-gating
        # predictor runs layer l+1's router one layer early; its accuracy
        # is near-perfect on the slowly-moving residual stream)
        outs_pf, pf = live_serving("lru", prefetch=True)
        emit("live.mixtral_reduced.served_lru_prefetch_hit_rate",
             pf.hit_rate * 1e6,
             f"baseline={served_lru:.3f} "
             f"pred_acc={pf.prediction_accuracy:.3f} "
             f"issued={pf.prefetch_issued} "
             f"spec_hits={pf.prefetch_hits} "
             f"wasted={pf.prefetch_wasted}")
        assert pf.hit_rate > served_lru, \
            ("prefetch must beat the no-prefetch baseline",
             pf.hit_rate, served_lru)
        # confidence-gated prefetch: thresholding reservations on router
        # probability cuts the speculative transfer volume — and with it
        # prefetch_wasted, the only source of cache pollution — while the
        # generated tokens stay IDENTICAL (gating changes residency,
        # never logits). The untrained reduced router's one-layer-ahead
        # predictions are near-perfect (pred_acc above), so the ungated
        # baseline often has zero waste to begin with; the waste assert
        # is strict exactly when there is waste to cut.
        GATE = 0.35                      # ~p75 pick prob, untrained
        outs_g, pfg = live_serving("lru", prefetch=True,
                                   prefetch_min_prob=GATE)
        emit("live.mixtral_reduced.served_lru_prefetch_gated_wasted",
             pfg.prefetch_wasted * 1e6,
             f"ungated_wasted={pf.prefetch_wasted} gate={GATE} "
             f"issued={pfg.prefetch_issued} vs {pf.prefetch_issued} "
             f"predicted={pfg.predicted} vs {pf.predicted} "
             f"hit_rate={pfg.hit_rate:.3f}")
        assert sorted(outs_g) == sorted(outs_pf)
        for rid in outs_pf:
            np.testing.assert_array_equal(outs_g[rid], outs_pf[rid])
        assert pfg.predicted < pf.predicted, \
            ("the gate must suppress low-confidence predictions",
             pfg.predicted, pf.predicted)
        assert pfg.prefetch_issued < pf.prefetch_issued, \
            ("the gate must cut the speculative transfer volume",
             pfg.prefetch_issued, pf.prefetch_issued)
        assert pfg.prefetch_wasted <= pf.prefetch_wasted, \
            ("gating must never add waste",
             pfg.prefetch_wasted, pf.prefetch_wasted)
        if pf.prefetch_wasted:
            assert pfg.prefetch_wasted < pf.prefetch_wasted, \
                ("confidence gating must cut wasted prefetches",
                 pfg.prefetch_wasted, pf.prefetch_wasted)
        # batch-aware reservation ranking: vote-ranked way claims must
        # never lose speculative hits vs insertion order, and (like every
        # prefetch knob) never change the generated tokens
        outs_nr, pf_nr = live_serving("lru", prefetch=True,
                                      rank_votes=False)
        emit("live.mixtral_reduced.served_lru_prefetch_rank_votes",
             pf.prefetch_hits * 1e6,
             f"spec_hits ranked={pf.prefetch_hits} "
             f"unranked={pf_nr.prefetch_hits} "
             f"hit_rate {pf_nr.hit_rate:.3f} -> {pf.hit_rate:.3f}")
        assert sorted(outs_nr) == sorted(outs_pf)
        for rid in outs_pf:
            np.testing.assert_array_equal(outs_nr[rid], outs_pf[rid])
        assert pf.prefetch_hits >= pf_nr.prefetch_hits, \
            ("vote ranking must not lose speculative hits",
             pf.prefetch_hits, pf_nr.prefetch_hits)
        assert pf.hit_rate >= pf_nr.hit_rate, \
            ("vote ranking must keep the demand hit rate non-decreasing",
             pf.hit_rate, pf_nr.hit_rate)


if __name__ == "__main__":
    main()
