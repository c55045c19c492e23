#!/usr/bin/env python3
"""Chip smoke test: the collaborative MoE serving path on one TPU chip.

    python chip_smoke.py

Serves Mixtral-8x7B at its published widths (d_model 4096, 32 query / 8
KV heads of 128, 8 experts of d_ff 14336, top-2, vocabulary 32000) with
only the depth cut to 2 layers, so that the whole expert table fits one
16 GB chip next to the cache. Weights are random, made from a seed. Each
phase goes through the user's entry points, ``build()`` ->
``ContinuousBatchingScheduler.run()`` -> ``engine.decode_batch``:

  dense          dense KV cache, cache-warming replay prefill
  paged_segment  paged KV, segment-streamed prefill (the Pallas paged
                 prefill kernel)
  host_lane      dense KV, cache-miss experts computed on the host through
                 the ``pure_callback`` executor

Every phase checks that each request got all its tokens, that cache hits
never exceed accesses, that no logit is NaN or Inf, that the engine's
first-token logits agree with ``repro.models.prefill`` on the same
params, and that the compiled decode step holds Mosaic kernels
(``tpu_custom_call``): an interpreted kernel cannot pass. The script
exits non-zero before any phase when JAX finds no TPU. Its last line, on
success only, is ``{"ok": true, "device": {...}}``. Times printed are
wall times of this smoke run, compilation included: not benchmarks.
"""
from __future__ import annotations

import gc
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.config import get_config, with_layers  # noqa: E402
from repro.core.collaborative import memory_kinds  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import init_params, prefill  # noqa: E402
from repro.serving import build  # noqa: E402

ARCH = "mixtral-8x7b"
LAYERS = 2
SLOTS = 4
REQUESTS = 8
PROMPT_LEN = (32, 64)               # inclusive range of prompt lengths
NEW_TOKENS = 16
CAPACITY = 96                       # >= 64 + 16 + 1, a whole number of pages
CACHE = dict(num_indexes=1, num_ways=2, policy="lru")
PHASES = {
    "dense": dict(),
    "paged_segment": dict(kv_paged=True, page_size=16, prefill_segment=16),
    "host_lane": dict(host_compute=True, host_backend="callback",
                      host_threads=8),
}
# First-token logits, engine vs repro.models.prefill, as max |diff| over
# max |reference|. Both run bf16 weights and activations, but not the same
# programs: the engine pads the prompt to CAPACITY (or streams it in
# 16-token segments through the Pallas paged prefill kernel) where the
# reference runs the unpadded prompt through XLA's attention. Each bf16
# rounding is 2**-8 relative; a few of them compound through two layers
# and the vocabulary projection. A wrong mask, page or position moves the
# logits by O(1) of their scale.
LOGITS_RTOL = 3e-2


# the reference forward: the same prompt in every phase compiles once
_reference = jax.jit(prefill, static_argnums=(2,))


def _nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _peak_bytes():
    """The device's high-water mark of HBM in use since the process began."""
    mem = jax.devices()[0].memory_stats() or {}
    return mem.get("peak_bytes_in_use", "n/a")


def _say(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def _prompts(vocab: int, seed: int):
    rng = np.random.default_rng(seed)
    lo, hi = PROMPT_LEN
    return [rng.integers(0, vocab, int(rng.integers(lo, hi + 1)))
            .astype(np.int32) for _ in range(REQUESTS)]


def decode_step_text(engine, sched) -> str:
    """Compiled text of the engine's decode step at the live batch
    geometry (a persistent-cache hit after the run compiled it)."""
    T = engine.ecfg.max_batch
    pages = (jnp.asarray(engine._slot_pages) if engine.ecfg.kv_paged
             else None)
    lowered = engine._decode.lower(
        engine.params, jnp.zeros((T, 1), jnp.int32), sched.state,
        engine.fast, jnp.ones((T,), bool), pages)
    return lowered.compile().as_text()


def run_phase(cfg, params, serving: dict, *, seed: int = 0,
              require_kernels: bool = True) -> dict:
    """Serve REQUESTS prompts through build() and the scheduler and check
    the results. Raises AssertionError on a failed check; returns the
    phase's counters."""
    t0 = time.perf_counter()
    engine, sched = build(
        cfg, cache=CACHE,
        serving=dict(max_batch=SLOTS, capacity=CAPACITY, **serving),
        seed=seed, params=params)
    firsts, finite = [], []
    sample_first, decode_batch = engine.sample_first, engine.decode_batch

    def record_first(ticket, *args, **kwargs):
        firsts.append((ticket.prompt, ticket.logits))
        return sample_first(ticket, *args, **kwargs)

    def record_decode(tokens, state, active):
        logits, state = decode_batch(tokens, state, active)
        finite.append(jnp.isfinite(logits).all())
        return logits, state

    engine.sample_first, engine.decode_batch = record_first, record_decode
    prompts = _prompts(cfg.vocab_size, seed)
    for p in prompts:
        sched.submit(p, max_new_tokens=NEW_TOKENS)
    outs = sched.run()
    wall = time.perf_counter() - t0
    st = sched.stats

    assert len(outs) == REQUESTS, f"{len(outs)} of {REQUESTS} served"
    for rid, out in outs.items():
        assert len(out) == NEW_TOKENS, f"request {rid}: {len(out)} tokens"
        assert ((out >= 0) & (out < cfg.vocab_size)).all(), rid
    assert 0 < st.accesses and st.hits <= st.accesses, (st.hits, st.accesses)
    assert len(firsts) == REQUESTS and finite, (len(firsts), len(finite))
    assert all(bool(f) for f in finite), "NaN/Inf in decode logits"
    for _, lg in firsts:
        assert bool(jnp.isfinite(lg).all()), "NaN/Inf in first-token logits"

    prompt, got = firsts[0]
    want, _ = _reference(params, {"tokens": jnp.asarray(prompt)[None]}, cfg)
    got = np.asarray(got, np.float32).reshape(-1)
    want = np.asarray(want, np.float32).reshape(-1)
    err = float(np.abs(got - want).max() / np.abs(want).max())
    assert err <= LOGITS_RTOL, f"first-token logits off by {err:.3g} rel"
    if serving.get("host_compute"):
        assert st.cpu_expert_calls > 0, "host lane never ran"
    kernels = "tpu_custom_call" in decode_step_text(engine, sched)
    if require_kernels:
        assert kernels, "decode step holds no Mosaic kernel (interpreted?)"

    result = dict(
        device=jax.devices()[0].device_kind,
        requests=st.requests_finished, tokens=st.generated_tokens,
        hits=st.hits, accesses=st.accesses, fetches=st.fetched_experts,
        cpu_expert_calls=st.cpu_expert_calls,
        prefill_segments=st.prefill_segments,
        first_logits_rel_err=err,
        first_token_agrees=bool(got.argmax() == want.argmax()),
        tpu_custom_call=kernels,
        cache_slot_bytes=_nbytes(engine.fast[:3]),
        wall_s_chip_smoke=round(wall, 3),
        peak_bytes_in_use=_peak_bytes())
    return result


def smoke_config():
    return with_layers(get_config(ARCH), LAYERS)


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}", flush=True)
    print(f"memory_kinds (host, device): {memory_kinds()}", flush=True)

    cfg = smoke_config()
    t0 = time.perf_counter()
    params = init_params(cfg, jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    _say("config", arch=cfg.name, layers=cfg.num_layers, d_model=cfg.d_model,
         heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
         head_dim=cfg.head_dim, experts=cfg.moe.num_experts,
         top_k=cfg.moe.top_k, expert_d_ff=cfg.moe.d_ff,
         vocab=cfg.vocab_size, param_bytes=_nbytes(params),
         init_s_chip_smoke=round(time.perf_counter() - t0, 3),
         peak_bytes_in_use=_peak_bytes())

    failed = []
    for name, serving in PHASES.items():
        try:
            _say(name, **run_phase(cfg, params, serving))
        except Exception as e:  # noqa: BLE001 — report every phase
            failed.append(name)
            traceback.print_exc()
            _say(name, FAILED=f"{type(e).__name__}: {e}")
        gc.collect()
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
