"""The hybrid dispatcher stage: partition a step's expert groups into
GPU-hit and CPU-miss sets and merge their outputs.

Drop-in twin of :func:`repro.core.collaborative.execute` (stage 2 of the
probe → execute → commit pipeline) that the serving engine slots in when
``EngineConfig.host_compute`` is on:

  * **GPU-hit set** — groups resident in the fast-tier slot buffer run
    through the grouped Pallas gmm kernels exactly as before.
  * **CPU-miss set** — non-resident groups whose cost-model decision
    (:class:`repro.hostexec.policy.HostDispatchPolicy`) favors host
    execution ship their rows of the ``[G, A, D]`` activation dispatch
    buffer to the host executor and get the ``[tokens, D]`` outputs
    scatter-added back into the residual by the shared combine —
    *activations move, weights never do*.
  * **fetch set** — the remaining misses (cost model favors the weight
    transfer) keep the old path: gather from the host tier, compute on
    device.

Cache semantics are IDENTICAL in all three sets: the probe's bookkeeping
and the commit's post-fetch are untouched, so misses the policy admits
still warm the cache (the async weight copy is off the critical path —
the cost model charges the *critical-path* choice, the warming copy rides
the commit's overlap slot exactly as before). Host execution therefore
changes where FLOPs run and the stats channel — never residency, never
tokens.

Two backends:
  * ``"callback"`` — the real multithreaded numpy executor, bridged via
    ``jax.pure_callback``. float32 host math: numerically close, not
    bitwise-identical to the device lane.
  * ``"jax"`` — pure-JAX fallback: the CPU-miss groups run the same
    grouped kernel against the host-tier weight gather, entirely
    in-graph. On single-device CI both lanes are literally the same
    computation, so tokens stay BIT-identical to the all-GPU path while
    the dispatcher's partition/counters exercise for real. This is the
    default and the parity contract the tests pin.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import CacheConfig
from repro.core import collaborative as collab

from .executor import HostExpertExecutor

__all__ = ["dispatch_execute", "dispatch_plan"]


def dispatch_plan(pr: collab.ProbeResult, cpu_table: jax.Array,
                  ) -> Tuple[jax.Array, jax.Array]:
    """Partition the probe's groups: (to_cpu [G] bool, counts [G] int32).

    counts — valid assignments per group; to_cpu — non-resident groups the
    cost model sends to the host (``cpu_table[c]`` = run a c-token miss
    group on the CPU; index 0 is False so padded/empty groups never
    dispatch). Resident groups always stay on the device — a hit costs
    ``gpu_expert_ms`` with no transfer on either lane, so the CPU can
    never win one."""
    G = pr.rep_e.shape[0]
    counts = jnp.zeros((G,), jnp.int32).at[pr.gid].add(
        pr.valid.astype(jnp.int32))
    miss = (~pr.resident) & (pr.rep_e >= 0)
    to_cpu = miss & cpu_table[jnp.minimum(counts, cpu_table.shape[0] - 1)]
    return to_cpu, counts


def dispatch_execute(tiers: collab.ExpertTiers, layer: jax.Array,
                     x: jax.Array, top_w: jax.Array,
                     pr: collab.ProbeResult, ccfg: CacheConfig,
                     cpu_table: jax.Array,
                     executor: Optional[HostExpertExecutor] = None,
                     fuse_small: int = 0,
                     ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array,
                                                 jax.Array],
                                Dict[str, jax.Array]]:
    """Stage 2' — hybrid grouped execution with host-computed misses.

    Same signature contract as :func:`repro.core.collaborative.execute`
    plus the split table and (for the callback backend) the executor;
    ``fuse_small`` is the executor's small-group fusion threshold (the
    stat mirrors it for both backends); returns (y [T, D], the step's
    expert weights for commit()'s post-fetch, dispatch stats
    {cpu_expert_calls, cpu_tokens, miss_expert_groups, fused_groups})."""
    T, K = top_w.shape
    tok, xbuf = collab._stage_dispatch(x, K, pr)
    w = collab._gather_group_weights(tiers, layer, pr, ccfg)
    to_cpu, counts = dispatch_plan(pr, cpu_table)

    # device lane: grouped gmm over the tiered gather (hit groups read the
    # slot buffer, fetch-set misses the host tier)
    ybuf_dev = collab.experts(xbuf, w)                     # [G, A, D]

    if executor is not None:
        # host lane: the activation buffer crosses to the CPU executor
        # (thread-pool numpy FFN over the host expert table) and the
        # outputs cross back — the paper's activation round-trip
        ybuf_host = jax.pure_callback(
            executor.compute_groups,
            jax.ShapeDtypeStruct(xbuf.shape, xbuf.dtype),
            layer, pr.rep_e, to_cpu, xbuf, counts)
        ybuf = jnp.where(to_cpu[:, None, None], ybuf_host, ybuf_dev)
    else:
        # pure-JAX fallback: the CPU-miss groups' rows of ybuf_dev were
        # already computed from the host-tier gather (non-resident groups
        # never read the slot buffer), which is exactly what the host
        # lane would produce — so the device buffer IS the merged result,
        # bit for bit, and only the partition/counters differ from the
        # all-GPU path. No second FFN.
        ybuf = ybuf_dev

    y = collab._combine(ybuf, pr.gid, pr.pos, tok, top_w, pr.valid, T,
                        x.dtype)
    executed_miss = (~pr.resident) & (pr.rep_e >= 0) & (counts > 0)
    dstats = {
        "cpu_expert_calls": to_cpu.sum().astype(jnp.int32),
        "cpu_tokens": jnp.where(to_cpu, counts, 0).sum().astype(jnp.int32),
        # every executed non-resident group reads the host tier whatever
        # lane it takes — the denominator of the miss-handling cost model
        # (fetched_experts undercounts it: an expert evicted within the
        # step still paid its read)
        "miss_expert_groups": executed_miss.sum().astype(jnp.int32),
        # groups the executor's fusion lane batches (to_cpu already
        # excludes empty groups via cpu_table[0]=False); mirrored for
        # the jax backend so the stat channel is backend-invariant
        "fused_groups": (
            (to_cpu & (counts <= fuse_small)).sum().astype(jnp.int32)
            if fuse_small > 0 else jnp.int32(0)),
    }
    return y, w, dstats
