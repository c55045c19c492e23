"""Kernel microbenchmarks (interpret-mode wall time is NOT a TPU metric;
reported for harness completeness plus the analytic VMEM/roofline numbers
that ARE the TPU-relevant quantities)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.costmodel import TPU_HBM_BW, TPU_PEAK_FLOPS_BF16
from repro.kernels.moe_gmm import grouped_matmul, moe_ffn
from repro.kernels.decode_attention import decode_attention
from repro.launch.compile_cache import enable_compile_cache
from .common import emit, timeit


def bench_cache_access() -> None:
    """Expert-cache access: seed per-pick scan vs vectorized row update.

    The paper-scale geometry (N=32 layers, M=8 ways) at decode assignment
    counts from a single request (T*K = 4) up to a full continuous batch
    (T*K = 64). The vectorized path gathers the set row once and services
    picks with O(M) vector ops; the seed path re-slices the full [N, M]
    arrays per pick inside a lax.scan.
    """
    import jax.numpy as jnp
    from repro.config import CacheConfig
    from repro.core.cache import access, access_scan_reference, \
        init_cache_state

    print("=== expert-cache access: seed scan vs vectorized row update ===")
    ccfg = CacheConfig(num_indexes=32, num_ways=8, policy="lru")
    state = init_cache_state(ccfg)
    layer = jnp.int32(3)
    for A in (4, 16, 64):
        experts = jax.random.randint(jax.random.PRNGKey(A), (A,), 0, 16,
                                     jnp.int32)
        new = jax.jit(lambda s, e: access(s, layer, e, "lru"))
        old = jax.jit(lambda s, e: access_scan_reference(s, layer, e, "lru"))
        t_new = timeit(lambda: jax.block_until_ready(new(state, experts)),
                       iters=50, warmup=5)
        t_old = timeit(lambda: jax.block_until_ready(old(state, experts)),
                       iters=50, warmup=5)
        emit(f"cache_access.A{A}.vectorized", t_new,
             f"seed_scan={t_old:.1f}us speedup={t_old / t_new:.2f}x "
             f"(N=32 M=8 lru, {A} assignments/step)")


def main() -> None:
    enable_compile_cache()
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="also write the results to this BENCH_*.json path")
    args, _ = ap.parse_known_args()

    bench_cache_access()
    print("=== kernels: analytic roofline + interpret-mode correctness ===")
    # mixtral-shaped expert pair on one device
    E, C, D, F = 2, 128, 512, 1792        # scaled-down for interpret mode
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (E, C, D), jnp.bfloat16)
    w1 = jax.random.normal(ks[1], (E, D, F), jnp.bfloat16) * 0.05
    w3 = jax.random.normal(ks[2], (E, D, F), jnp.bfloat16) * 0.05
    w2 = jax.random.normal(ks[3], (E, F, D), jnp.bfloat16) * 0.05

    us = timeit(lambda: jax.block_until_ready(moe_ffn(x, w1, w3, w2)),
                iters=2, warmup=1)
    flops = 2 * E * C * D * F * 3
    weight_bytes = 3 * E * D * F * 2
    t_compute = flops / TPU_PEAK_FLOPS_BF16
    t_memory = weight_bytes / TPU_HBM_BW
    emit("moe_ffn.interpret", us,
         f"tpu_roofline: compute={t_compute*1e6:.1f}us "
         f"memory={t_memory*1e6:.1f}us "
         f"bound={'memory' if t_memory > t_compute else 'compute'} "
         f"(C={C}: decode-like, weight-streaming bound)")

    B, H, Hk, hd, S = 2, 8, 2, 128, 4096
    q = jax.random.normal(ks[0], (B, H, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, Hk, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, Hk, hd), jnp.bfloat16)
    us = timeit(lambda: jax.block_until_ready(
        decode_attention(q, k, v, jnp.int32(S - 1))), iters=2, warmup=1)
    kv_bytes = 2 * B * S * Hk * hd * 2
    emit("flash_decode.interpret", us,
         f"tpu_roofline: kv_stream={kv_bytes/TPU_HBM_BW*1e6:.1f}us "
         f"(pure HBM-bandwidth bound at decode)")

    from repro.kernels.ssd_scan import ssd_chunked_kernel
    Bb, S2, nh, hp, ds = 1, 512, 4, 64, 128
    x = jax.random.normal(ks[0], (Bb, S2, nh, hp), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bb, S2, nh)))
    A_log = jax.random.normal(ks[2], (nh,)) * 0.3
    Bm = jax.random.normal(ks[3], (Bb, S2, ds)) * 0.3
    Cm = jax.random.normal(ks[0], (Bb, S2, ds)) * 0.3
    us = timeit(lambda: jax.block_until_ready(
        ssd_chunked_kernel(x, dt, A_log, Bm, Cm)), iters=2, warmup=1)
    # the win: state [ds,hp] stays in VMEM across chunks instead of
    # round-tripping HBM every lax.scan step
    state_traffic = (S2 // 128) * Bb * nh * ds * hp * 4 * 2
    emit("ssd_scan.interpret", us,
         f"tpu: saved state HBM round-trips={state_traffic/1e6:.2f}MB/layer "
         f"({(S2 // 128)} chunks x {Bb*nh} heads, kept in VMEM scratch)")

    if args.json:
        from .common import dump_json
        dump_json(args.json)


if __name__ == "__main__":
    main()
