"""Phi-3.5-MoE's published keys (``hf/phimoe.py``): every key of the
configuration file read or skipped, any other refused by name, a value the
program does not serve refused before any weights are made; the reference
(``references/phimoe.py``) checks the tree against the keys."""
import json
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import spec

ROOT = Path(__file__).resolve().parents[3]
CHIP = ROOT / "benchmarks/chip"
FILE = CHIP / "configs/phi-3.5-moe-l4.json"


def _phi(**changes):
    conf = json.loads(FILE.read_text())
    conf.update(changes)
    return conf


def _hf():
    return spec._module(ROOT, "hf", "phimoe", "program mapping")


def test_every_key_of_the_file_is_read_or_skipped():
    hf = _hf()
    keys = set(spec.published(_phi()))
    assert keys <= hf.READ | set(hf.SKIPPED)
    assert not hf.READ & set(hf.SKIPPED)
    assert not spec.HARNESS_KEYS & (hf.READ | set(hf.SKIPPED))
    assert all(r and "\n" not in r for r in hf.SKIPPED.values())
    assert set(hf.SKIPPED) == {
        "input_jitter_noise", "router_aux_loss_coef", "attention_dropout",
        "hidden_dropout", "output_router_logits", "initializer_range",
        "bos_token_id", "eos_token_id"}


def test_the_file_maps_to_the_published_block():
    m = spec.model_config(ROOT, _phi())
    assert (m.d_model, m.num_heads, m.num_kv_heads, m.head_dim) == \
        (4096, 32, 8, 128)
    assert (m.moe.num_experts, m.moe.top_k, m.moe.d_ff) == (16, 2, 6400)
    assert m.moe.router == "sparsemixer" and m.moe.router_jitter == 0.01
    assert m.norm == "layer" and m.norm_eps == 1e-5
    assert m.qkv_bias and m.attn_out_bias and m.lm_head_bias
    assert m.rope_scaling.original_max_position_embeddings == 4096
    assert len(m.rope_scaling.short_factor) == 64
    assert m.num_layers == 4 and m.vocab_size == 32064


def test_an_unknown_key_is_refused_by_name():
    with pytest.raises(ValueError, match="partial_rotary_factor.*hf/phimoe"):
        spec.model_config(ROOT, _phi(partial_rotary_factor=0.5))


@pytest.mark.parametrize("key", ["rope_scaling", "router_jitter_noise"])
def test_a_missing_needed_key_is_refused_by_name(key):
    conf = _phi()
    del conf[key]
    with pytest.raises(ValueError,
                       match=f"model_type 'phimoe'.*{key}.*hf/phimoe"):
        spec.model_config(ROOT, conf)


def _rope(**changes):
    rs = dict(_phi()["rope_scaling"])
    rs.update(changes)
    return rs


@pytest.mark.parametrize("key,value", [
    ("hidden_act", "gelu"),
    ("sliding_window", 2048),
    ("num_experts_per_tok", 4),
    ("rope_scaling", _rope(type="yarn")),
    ("rope_scaling", _rope(short_factor=[1.0] * 32)),
    ("rope_scaling", _rope(long_factor=[1.0] * 32)),
    ("rope_scaling", _rope(long_mscale=None)),
    ("original_max_position_embeddings", 8192),
])
def test_a_value_the_program_does_not_serve_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=f"'phimoe'.*{key}"):
        spec.model_config(ROOT, _phi(**{key: value}))


def test_a_capacity_past_longrope_original_positions_is_refused_first(
        monkeypatch):
    """``build()`` refuses a KV capacity past LongRoPE's original 4096
    positions, naming the reason, before any weight is made."""
    from repro.models import transformer
    from repro.serving import api

    def no_weights(*args, **kwargs):
        raise AssertionError("weights made before the refusal")

    monkeypatch.setattr(transformer, "init_params", no_weights)
    model = spec.model_config(ROOT, _phi())
    with pytest.raises(ValueError,
                       match="original_max_position_embeddings 4096.*re-rotate"):
        api.build(model, serving=dict(max_batch=8, capacity=4112,
                                      kv_paged=True, page_size=16))


def test_the_cell_capacity_is_within_the_short_factors():
    cell = spec.load_cell(ROOT, "phi35.batch-decode")
    assert cell.capacity == 3424
    cell.model.check_capacity(cell.capacity)
    assert cell.config["engine"]["max_batch"] == cell.traffic["clients"] == 8


def test_the_reference_refuses_a_tree_without_the_biases():
    import jax.numpy as jnp
    conf = _phi(hidden_size=64, intermediate_size=32, num_attention_heads=4,
                num_key_value_heads=2, num_local_experts=4,
                num_hidden_layers=1, vocab_size=64,
                rope_scaling=_rope(short_factor=[1.0] * 8,
                                   long_factor=[1.0] * 8))
    ref = spec.reference(ROOT, conf)
    shapes = ref.expected_shapes(spec.published(conf))
    params = {}
    for name, shape in shapes.items():
        node = params
        *path, last = name.split("/")
        for p in path:
            node = node.setdefault(p, {})
        node[last] = jnp.zeros(shape, jnp.float32)
    out = ref.logits(params, spec.published(conf), np.zeros(4, np.int32), 8)
    assert out.shape == (4, 64)
    del params["scan"]["s0"]["attn"]["bo"]
    with pytest.raises(ValueError, match="scan/s0/attn/bo"):
        ref.logits(params, spec.published(conf), np.zeros(4, np.int32), 8)


def _ctx(stats0, stats1, layers=4, max_batch=8):
    from types import SimpleNamespace
    return SimpleNamespace(
        stats0=stats0, stats1=stats1,
        model=SimpleNamespace(num_layers=layers),
        cell=SimpleNamespace(config={"engine": {"max_batch": max_batch}}))


def test_the_batch_readers_read_the_window_counters():
    from repro.serving import EngineStats
    a = EngineStats(steps=10, tokens=60, unique_experts=400)
    b = EngineStats(steps=30, tokens=210, unique_experts=1240)
    unique = spec.load_reader(ROOT, "unique_experts")
    occupancy = spec.load_reader(ROOT, "batch_occupancy")
    assert unique(_ctx(a, b)) == pytest.approx(840 / (20 * 4))
    assert occupancy(_ctx(a, b)) == pytest.approx(100 * 150 / (20 * 8))
    assert unique(_ctx(a, a)) is None and occupancy(_ctx(a, a)) is None


def test_the_unique_experts_reader_reads_nothing_without_the_counter():
    """A program that does not count distinct experts (the tree before
    this counter) gives no reading, and no error."""
    from types import SimpleNamespace
    old = SimpleNamespace(steps=10, tokens=60)
    new = SimpleNamespace(steps=30, tokens=210)
    assert spec.load_reader(ROOT, "unique_experts")(_ctx(old, new)) is None
