"""One-call construction of the collaborative serving stack.

Every driver used to hand-assemble ``CacheConfig`` + ``EngineConfig`` +
``init_params`` + engine + scheduler slightly differently; :func:`build`
is the single front door: resolve the (reduced) architecture, derive
sensible cache defaults from it, initialize parameters, and return the
``(engine, scheduler)`` pair ready to ``submit()`` / ``stream()`` /
``run()``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple, Union

import jax

from repro.config import CacheConfig, ModelConfig, get_config, reduced

from .engine import CollaborativeEngine, EngineConfig
from .scheduler import ContinuousBatchingScheduler

__all__ = ["build"]


def build(arch: Union[str, ModelConfig], *,
          cache: Union[None, CacheConfig, Dict] = None,
          serving: Union[None, EngineConfig, Dict] = None,
          seed: int = 0,
          params=None,
          reduce: bool = True,
          max_queue: Optional[int] = None,
          recorder=None
          ) -> Tuple[CollaborativeEngine, ContinuousBatchingScheduler]:
    """Build the collaborative engine + continuous-batching scheduler.

    arch    — architecture id (``"mixtral-8x7b"``) or a ModelConfig. A
              ModelConfig is used AS-IS (the caller already chose its
              geometry — and its ``params`` must match it); ``reduce``
              only applies when resolving an arch id.
    cache   — CacheConfig, or a dict of overrides on the default
              ``CacheConfig(num_indexes=num_layers, num_ways=2, "lru")``.
    serving — EngineConfig (its ``cache`` is replaced when ``cache`` is
              also given), or a dict of EngineConfig overrides
              (``max_batch`` / ``capacity`` / ``prefetch`` /
              ``prefill_chunk`` / ``admit_chunks_per_tick``).
    seed    — seeds parameter init, static cache placement and the
              scheduler's fallback sampling chains.
    params  — pre-initialized parameters; without them the engine draws
              them (``init_params``) once it has accepted the config.
    reduce  — apply :func:`repro.config.reduced` (the CPU-container
              geometry) to arch-id lookups; pass False to serve the full
              config.
    max_queue — bound the scheduler's waiting line (None = unbounded);
              a full queue makes ``submit(..., block=False)`` raise
              :class:`~repro.serving.scheduler.QueueFull`.
    recorder — a :class:`repro.obs.TraceRecorder` to wire through the
              engine AND scheduler (request-lifecycle + step-phase
              tracing); None serves untraced with the no-op recorder.

    Returns ``(engine, scheduler)``.
    """
    if isinstance(arch, str):
        cfg = get_config(arch)
        if reduce:
            cfg = reduced(cfg)
    else:
        cfg = arch
    if cfg.moe is None or cfg.moe_every != 1 or cfg.is_encdec:
        raise ValueError(
            f"{cfg.name}: collaborative serving needs a homogeneous "
            f"decoder-only MoE stack (every layer MoE); use the generic "
            f"path in repro.launch.serve for other archs")

    if isinstance(cache, CacheConfig):
        ccfg = cache
    else:
        opts = dict(num_indexes=cfg.num_layers, num_ways=2, policy="lru")
        opts.update(cache or {})
        ccfg = CacheConfig(**opts)

    if isinstance(serving, EngineConfig):
        ecfg = dataclasses.replace(serving, cache=ccfg) if cache is not None \
            else serving
    else:
        ecfg = EngineConfig(cache=ccfg, **(serving or {}))

    key = jax.random.PRNGKey(seed)
    engine = CollaborativeEngine(cfg, params, ecfg, key=key,
                                 recorder=recorder)
    scheduler = ContinuousBatchingScheduler(
        engine, key=jax.random.fold_in(key, 1), max_queue=max_queue,
        recorder=recorder)
    return engine, scheduler
