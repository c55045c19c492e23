"""Pallas TPU kernels for the perf-critical compute layers, each with an
ops.py jit wrapper and a ref.py pure-jnp oracle (validated in interpret
mode on CPU; see tests/test_kernels_*.py):

  moe_gmm/           grouped expert matmul + fused SwiGLU gate
  decode_attention/  flash-decode over dense and paged KV caches
  prefill_attention/ chunked-prefill attention over paged KV
  ssd_scan/          Mamba2 SSD chunked scan (state held in VMEM)
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Whether the ops.py wrappers run their kernels in Pallas interpret
    mode. Called when a wrapper is traced, never at import.

    On a TPU backend the kernels compile with Mosaic. The CPU backend runs
    them interpreted only when the CPU was chosen explicitly
    (``JAX_PLATFORMS=cpu`` or ``jax.config.update("jax_platforms",
    "cpu")``). A process that meant to reach a TPU and fell back to the
    CPU because the TPU did not start fails here instead of serving on
    interpreted kernels."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    platforms = [p.strip() for p in (jax.config.jax_platforms or "").split(",")]
    if backend == "cpu" and platforms[0] == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels need a TPU, but JAX runs on {backend!r} "
        f"(jax_platforms={jax.config.jax_platforms!r}); set "
        f"JAX_PLATFORMS=cpu to run them in interpret mode on purpose")
