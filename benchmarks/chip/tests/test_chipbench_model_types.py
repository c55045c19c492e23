"""A configuration's published keys: every one read or skipped by its
``model_type``'s mapping, or the file refused before any weights are made;
the reference found by ``model_type`` and fed those keys; the weights drawn
by each leaf's role. The pinned numbers are the ones the harness gave
before it read configurations this way (the same tree, the same seed)."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import spec, weights

ROOT = Path(__file__).resolve().parents[3]
CHIP = ROOT / "benchmarks/chip"
SEED = 2 ** 40 + 11
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

TINY = dict(name="tiny-moe", hidden_size=256, intermediate_size=128,
            num_attention_heads=4, num_key_value_heads=2,
            num_local_experts=4)
# sha256 over the tiny tree's leaves in flattening order, and of the
# float32 reference logits (plain, then float8) of 20 tokens padded to 32
TINY_WEIGHTS = \
    "bfa423c6bd74e0687e52a069178c13c769c29aa0bbe36228b97c04ee1ed6e8e0"
TINY_LOGITS = {False: "7d9399a4c7597469", True: "bfe54c2a157974c0"}
TINY_ARGMAX = {
    False: [21986, 27752, 27752, 30943, 18113, 24742, 18863, 24742, 21175,
            24742, 17626, 17942, 28043, 10370, 15904, 15904, 30550, 20491,
            15904, 28229],
    True: [23935, 13399, 27752, 30943, 18113, 9910, 18863, 24742, 15854,
           24742, 17626, 395, 28043, 10370, 15904, 15904, 29395, 20491,
           15904, 17791]}


def _mixtral(**changes):
    conf = json.loads((CHIP / "configs/mixtral-8x7b-l3.json").read_text())
    conf.update(changes)
    return conf


def _mapping_before(conf):
    """The fixed mapping the harness had before: the ModelConfig a
    Mixtral file must still give, field by field."""
    from repro.config import ModelConfig, MoEConfig
    heads = conf["num_attention_heads"]
    return ModelConfig(
        name=conf["name"], family="moe",
        num_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], num_heads=heads,
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim") or conf["hidden_size"] // heads,
        d_ff=0, vocab_size=conf["vocab_size"],
        moe=MoEConfig(num_experts=conf["num_local_experts"],
                      top_k=conf["num_experts_per_tok"],
                      d_ff=conf["intermediate_size"]),
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf.get("tie_word_embeddings", False)),
        max_seq_len=conf["max_position_embeddings"],
        dtype=conf["torch_dtype"])


@pytest.fixture(scope="module")
def tiny_params():
    model = spec.model_config(ROOT, _mixtral(**TINY))
    return weights.make_params(model, SEED)


@pytest.mark.parametrize("conf", [_mixtral(), _mixtral(**TINY)],
                         ids=["published", "tiny"])
def test_mixtral_maps_as_before(conf):
    model = spec.model_config(ROOT, conf)
    before = _mapping_before(conf)
    for f in before.__dataclass_fields__:
        assert getattr(model, f) == getattr(before, f), f


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_every_configuration_maps_and_has_its_reference(config):
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    conf = json.loads((ROOT / entry["file"]).read_text())
    assert spec.model_config(ROOT, conf).name == config
    assert callable(spec.reference(ROOT, conf).logits)


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True),
    ("lm_head_bias", True),
    ("rope_scaling", {"type": "longrope", "short_factor": [1.0],
                      "long_factor": [1.0]}),
    ("hidden_act", "gelu"),
    ("sliding_window", 4096),
])
def test_a_key_the_program_does_not_do_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=f"'?{key}") as e:
        spec.model_config(ROOT, _mixtral(**{key: value}))
    assert "mixtral" in str(e.value)


def test_a_model_type_with_no_mapping_is_refused():
    with pytest.raises(ValueError, match="model_type 'phimoe'.*hf/phimoe"):
        spec.model_config(ROOT, _mixtral(model_type="phimoe"))
    with pytest.raises(ValueError, match="references/phimoe.py"):
        spec.reference(ROOT, _mixtral(model_type="phimoe"))


def test_skipped_keys_give_one_line_reasons_and_are_not_read():
    hf = spec._module(ROOT, "hf", "mixtral", "program mapping")
    assert not hf.READ & set(hf.SKIPPED)
    assert all(r and "\n" not in r for r in hf.SKIPPED.values())
    assert not spec.HARNESS_KEYS & (hf.READ | set(hf.SKIPPED))


def _tiny_root(root: Path, conf: dict, with_reference: bool = True) -> None:
    """A checkout holding only BENCHMARK.json and the benchmark's files,
    with one cell on ``conf``; its ``model_type``'s files are copies of
    Mixtral's, the reference left out unless ``with_reference``."""
    chip = root / "benchmarks/chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    kind = conf["model_type"]
    shutil.copy(CHIP / "hf/mixtral.py", chip / "hf" / f"{kind}.py")
    if with_reference:
        shutil.copy(CHIP / "references/mixtral.py",
                    chip / "references" / f"{kind}.py")
    (chip / "configs/tiny-moe.json").write_text(json.dumps(conf))
    bench = dict(BENCH)
    bench["configs"] = [{"name": "tiny-moe", "source": "test",
                         "file": "benchmarks/chip/configs/tiny-moe.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": "tiny.cell", "config": "tiny-moe",
                           "traffic": "single-decode", "chips": 1,
                           "why": "test"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def _run_py(root: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    return subprocess.run(
        [sys.executable, str(root / "benchmarks/chip/run.py"), "--workload",
         "tiny.cell", "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("change,names", [
    ({"attention_bias": True}, "attention_bias"),
    ({"model_type": "toy_moe"}, "references/toy_moe.py"),
])
def test_run_py_refuses_before_any_weights(tmp_path, change, names):
    """run.py exits non-zero, naming the key or the missing reference,
    before it looks for a chip or makes a weight."""
    _tiny_root(tmp_path, _mixtral(**TINY, **change), with_reference=False)
    proc = _run_py(tmp_path)
    assert proc.returncode not in (0, 2), proc.stderr[-2000:]
    assert proc.stdout.strip() == ""
    assert names in proc.stderr
    assert "setup:" not in proc.stderr
    assert "needs 1 TPU chip" not in proc.stderr


def test_the_reference_is_found_by_model_type(tmp_path):
    _tiny_root(tmp_path, _mixtral(**TINY, model_type="toy_moe"))
    cell = spec.load_cell(tmp_path, "tiny.cell")
    assert Path(cell.reference.__file__) == \
        tmp_path / "benchmarks/chip/references/toy_moe.py"
    assert cell.model == spec.model_config(ROOT, _mixtral(**TINY))


def test_tiny_weights_are_the_bytes_drawn_before(tiny_params):
    import jax
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tiny_params):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == TINY_WEIGHTS


@pytest.mark.parametrize("fp8", [False, True], ids=["float32", "float8"])
def test_mixtral_reference_logits_are_the_ones_before(tiny_params, fp8):
    conf = _mixtral(**TINY)
    ref = spec.reference(ROOT, conf)
    toks = np.random.default_rng(7).integers(0, 32000, 20).astype(np.int32)
    out = np.asarray(ref.logits(tiny_params, spec.published(conf), toks, 32,
                                fp8=fp8))
    assert out.shape == (20, 32000) and out.dtype == np.float32
    assert out.argmax(-1).tolist() == TINY_ARGMAX[fp8]
    assert hashlib.sha256(out.tobytes()).hexdigest()[:16] == TINY_LOGITS[fp8]


@pytest.mark.parametrize("keys,leaf", [
    ({"num_key_value_heads": 1}, "scan/s0/attn/wk"),
    ({"intermediate_size": 64}, "scan/s0/moe/w1"),
    ({"tie_word_embeddings": True}, "lm_head"),
])
def test_the_reference_refuses_a_tree_its_keys_do_not_give(tiny_params, keys,
                                                          leaf):
    conf = _mixtral(**TINY)
    ref = spec.reference(ROOT, conf)
    with pytest.raises(ValueError, match=leaf):
        ref.logits(tiny_params, {**spec.published(conf), **keys},
                   np.zeros(4, np.int32), 8)


def test_the_reference_refuses_a_leaf_it_does_not_know(tiny_params):
    conf = _mixtral(**TINY)
    ref = spec.reference(ROOT, conf)
    params = dict(tiny_params, lm_head_bias=np.zeros(32000, np.float32))
    with pytest.raises(ValueError, match="lm_head_bias"):
        ref.logits(params, spec.published(conf), np.zeros(4, np.int32), 8)


@pytest.mark.parametrize("name,shape", [
    ("scan/s0/attn/bq", (3, 4096)),
    ("scan/s0/attn/bo", (3, 4096)),
    ("scan/s0/mlp/bias", (3, 4096)),
    ("scan/s0/moe/experts/bias", (3, 16, 6400)),
    ("lm_head/bias", (32000,)),
])
def test_a_bias_is_drawn_at_std_0_02(name, shape):
    import jax
    import jax.numpy as jnp
    x = np.asarray(weights._draw(jax.random.PRNGKey(3), shape, jnp.float32,
                                 name))
    assert x.shape == shape
    assert abs(x.mean()) < 0.002 and x.std() == pytest.approx(0.02, rel=0.05)


@pytest.mark.parametrize("name,shape", [
    ("scan/s0/ln1", (3, 64)), ("final_norm", (64,)),
    ("scan/s0/attn/q_norm/scale", (3, 64)),
    ("scan/s0/moe/experts/ln", (3, 4, 64)),
])
def test_a_norm_scale_is_drawn_as_ones(name, shape):
    import jax
    import jax.numpy as jnp
    x = weights._draw(jax.random.PRNGKey(3), shape, jnp.float32, name)
    assert np.array_equal(np.asarray(x), np.ones(shape, np.float32))


@pytest.mark.parametrize("name,shape", [
    ("scan/s0/attn/offset", (3, 64)), ("position_table", (64,)),
])
def test_an_unknown_vector_leaf_is_refused(name, shape):
    import jax
    import jax.numpy as jnp
    with pytest.raises(ValueError, match=name):
        weights._draw(jax.random.PRNGKey(3), shape, jnp.float32, name)
