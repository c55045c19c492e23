"""AOT compiles of the serving kernels for a described TPU v5e chip.

Interpret mode never checks Mosaic's rules (block shapes whose last two
dims are neither (8, 128)-divisible nor whole, unprovable alignment, VMEM
budget), so every other kernel test passes on kernels the chip would
refuse. Here each kernel is compiled at Mixtral-8x7B widths (D=4096,
F=14336, H=32, Hk=8, hd=128, page 16) for a v5e chip that is described,
not attached, and the decode step of ``phi35.batch-decode`` whole, at
Phi-3.5-MoE's published widths. The kernel functions are called with ``interpret=False``
directly: the ops.py wrappers see the CPU backend here.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU compiler library,
and under xdist only the worker that runs this file does.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.decode_attention import flash_decode
from repro.kernels.decode_attention.paged import paged_flash_decode
from repro.kernels.moe_gmm.moe_gmm import gmm, swiglu_gmm
from repro.kernels.prefill_attention.paged import paged_flash_prefill

B, C, H, HK, HD, PAGE, MAX_PAGES = 4, 16, 32, 8, 128, 16, 6
NUM_PAGES = B * MAX_PAGES
E, ROWS, D, F = 8, 128, 4096, 14336


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")      # else the compiler logs to /tmp
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(one_chip, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]


bf16, i32 = jnp.bfloat16, jnp.int32
KV = ((NUM_PAGES, PAGE, HK, HD), bf16)
CSR = [((B + 1,), i32), ((NUM_PAGES,), i32), ((B,), i32)]

CASES = {
    "swiglu_gmm": (lambda x, w1, w3: swiglu_gmm(x, w1, w3),
                   [((E, ROWS, D), bf16), ((E, D, F), bf16),
                    ((E, D, F), bf16)]),
    "gmm": (lambda x, w: gmm(x, w),
            [((E, ROWS, D), bf16), ((E, D, F), bf16)]),
    "paged_flash_prefill": (
        lambda q, k, v, ip, ix, ll, p0: paged_flash_prefill(
            q, k, v, ip, ix, ll, p0, max_pages=MAX_PAGES),
        [((B, C, H, HD), bf16), KV, KV, *CSR, ((B,), i32)]),
    "paged_flash_decode": (
        lambda q, k, v, ip, ix, ll: paged_flash_decode(
            q, k, v, ip, ix, ll, max_pages=MAX_PAGES),
        [((B, H, HD), bf16), KV, KV, *CSR]),
    "flash_decode": (
        lambda q, k, v, pos: flash_decode(q, k, v, pos),
        [((B, H, HD), bf16), ((B, 2048, HK, HD), bf16),
         ((B, 2048, HK, HD), bf16), ((B,), i32)]),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, specs = CASES[name]
    compiled = jax.jit(fn).lower(*_shapes(one_chip, *specs)).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


# one v5e chip's HBM, and the share of it one cell's decode step may
# hold (its arguments, its scratch and its outputs): the rest is the
# runtime's own
HBM_BYTES = 16 * 2 ** 30
HBM_SHARE = 0.95


def _decode_step_compiled(one_chip, monkeypatch, workload, published):
    """The engine's decode step compiled for the chip with its Pallas
    kernels: the ``workload`` cell's engine with prefetch on, at the
    cell's own widths and depth (``published``), or Mixtral's at reduced
    widths."""
    import dataclasses
    from pathlib import Path

    from benchmarks.chip import scopes, spec
    from repro.config import get_config, reduced
    from repro.kernels.decode_attention import ops as attn_ops
    from repro.kernels.moe_gmm import ops as gmm_ops
    from repro.kernels.prefill_attention import ops as prefill_ops

    for mod in (attn_ops, gmm_ops, prefill_ops):
        monkeypatch.setattr(mod, "interpret_mode", lambda: False)
    jax.clear_caches()          # no interpret-mode trace of moe_ffn reused
    cell = spec.load_cell(Path(__file__).resolve().parents[1], workload)
    engine = dict(cell.config["engine"], prefetch=True)
    model = cell.model if published else reduced(get_config("mixtral-8x7b"))
    cell = dataclasses.replace(cell, config={**cell.config,
                                             "engine": engine})
    compiled = scopes.decode_lowered(model, cell, one_chip).compile()
    jax.clear_caches()
    return compiled


def _decode_step_text(one_chip, monkeypatch,
                      workload="mixtral.single-decode",
                      published=False) -> str:
    return _decode_step_compiled(one_chip, monkeypatch, workload,
                                 published).as_text()


def test_decode_step_names_its_stages_for_v5e(one_chip, monkeypatch):
    """The engine's decode step at reduced widths, compiled for the chip
    with its Pallas kernels: every stage scope reaches some instruction's
    ``op_name``, and the grouped expert kernels keep their instruction
    name ``moe_ffn`` (the benchmark's ``gmm_roofline`` reads it)."""
    from benchmarks.chip import scopes

    text = _decode_step_text(one_chip, monkeypatch)
    table = scopes.parse_hlo(text)
    own = {scopes.scope_of(op)
           for op in re.findall(r'op_name="([^"]*)"', text)}
    assert set(scopes.SCOPES) <= own
    kernels = {name for name, (opcode, scope, _) in table.items()
               if opcode == "custom-call" and scope == "moe_experts"}
    assert kernels and {re.sub(r"\.\d+$", "", n) for n in kernels} \
        == {"moe_ffn"}
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("workload,published", [
    ("mixtral.single-decode", False), ("phi35.batch-decode", True)],
    ids=["cell", "batch8_experts16"])
def test_decode_step_moves_expert_weights_as_slices_for_v5e(
        one_chip, monkeypatch, workload, published):
    """The expert cache moves weights as whole contiguous slices: no gather
    or scatter of floating-point values (expert weights) resolves to
    ``moe_gather``, ``moe_commit`` or ``moe_prefetch``, at Mixtral's G = 2
    groups and at ``phi35.batch-decode``'s 8 slots over 16 experts (G =
    16), the latter at Phi-3.5-MoE's published widths and the cell's 4
    layers, where the step must also fit the chip's memory. The cache's
    integer bookkeeping (tags, ways, votes) may still gather and scatter;
    the stage scopes are all still found."""
    from benchmarks.chip import scopes

    compiled = _decode_step_compiled(one_chip, monkeypatch, workload,
                                     published)
    text = compiled.as_text()
    if published:
        m = compiled.memory_analysis()
        held = (m.argument_size_in_bytes + m.temp_size_in_bytes
                + m.output_size_in_bytes - m.alias_size_in_bytes)
        assert held <= HBM_SHARE * HBM_BYTES, held
    dtype = dict(re.findall(
        r"^\s+(?:ROOT )?%(\S+) = \(?([a-z]+[0-9]*)\[", text, re.M))
    table = scopes.parse_hlo(text)
    assert set(scopes.SCOPES) <= {scope for _, scope, _ in table.values()}
    moves = {name: (opcode, scope, dtype.get(name))
             for name, (opcode, scope, _) in table.items()
             if opcode in ("gather", "scatter")
             and scope in ("moe_gather", "moe_commit", "moe_prefetch")
             and dtype.get(name, "")[:1] in ("f", "b")}
    assert not moves, moves
    slices = {scope for _, (opcode, scope, _) in table.items()
              if opcode == "dynamic-update-slice"}
    assert {"moe_gather", "moe_commit", "moe_prefetch"} <= slices
