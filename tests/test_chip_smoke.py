"""chip_smoke.py's control flow on the CPU, at reduced() widths.

The script's phase functions run here without its platform check, so a
broken phase, check or entry point fails on the CPU and not on the
chip. The script itself must refuse a CPU backend. The kernel-mode
helper behind it must never pick interpret mode unasked.
"""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

import repro.kernels as kernels
from repro.config import get_config, reduced, with_layers
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params

ROOT = Path(__file__).resolve().parents[1]


def _load_script():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    cs = _load_script()
    cfg = reduced(get_config(cs.ARCH))
    return cs, cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.mark.parametrize("phase", ["dense", "paged_segment", "host_lane"])
def test_phase_serves_and_checks_on_cpu(smoke, phase):
    cs, cfg, params = smoke
    r = cs.run_phase(cfg, params, cs.PHASES[phase],
                     require_kernels=False)
    assert r["requests"] == cs.REQUESTS
    assert r["tokens"] == cs.REQUESTS * cs.NEW_TOKENS
    assert 0 < r["accesses"] and r["hits"] <= r["accesses"]
    assert r["first_logits_rel_err"] <= cs.LOGITS_RTOL
    # interpreted kernels leave no Mosaic call in the compiled step: the
    # chip run's tpu_custom_call check cannot pass on them
    assert r["tpu_custom_call"] is False
    if phase == "host_lane":
        assert r["cpu_expert_calls"] > 0
    if phase == "paged_segment":
        assert r["prefill_segments"] > 0


def test_smoke_config_keeps_published_widths():
    cs = _load_script()
    full, cut = get_config(cs.ARCH), cs.smoke_config()
    assert cut.num_layers == cs.LAYERS < full.num_layers
    assert cut == full.__class__(**{**full.__dict__,
                                    "num_layers": cs.LAYERS})


@pytest.mark.parametrize("layers", [0, 33])
def test_with_layers_refuses_depth_outside_published(layers):
    with pytest.raises(ValueError, match="num_layers"):
        with_layers(get_config("mixtral-8x7b"), layers)


@pytest.mark.parametrize("env", [None, "elsewhere"])
def test_compile_cache_dir(monkeypatch, tmp_path, env):
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / env))
    before = jax.config.jax_compilation_cache_dir
    try:
        got = enable_compile_cache()
        if env is None:
            assert got == str(ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            # left to JAX, which reads the variable itself
            assert got == str(tmp_path / env)
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_script_refuses_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    for line in out.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def _fake_jax(backend, platforms):
    return SimpleNamespace(default_backend=lambda: backend,
                           config=SimpleNamespace(jax_platforms=platforms))


@pytest.mark.parametrize("backend,platforms,want", [
    ("tpu", None, False),
    ("cpu", "cpu", True),
    ("cpu", "cpu,tpu", True),
    ("cpu", None, RuntimeError),         # a TPU that failed to start
    ("cpu", "tpu,cpu", RuntimeError),
    ("gpu", None, RuntimeError),
])
def test_interpret_mode_only_when_asked(monkeypatch, backend, platforms,
                                        want):
    monkeypatch.setattr(kernels, "jax", _fake_jax(backend, platforms))
    if want is RuntimeError:
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
            kernels.interpret_mode()
    else:
        assert kernels.interpret_mode() is want
