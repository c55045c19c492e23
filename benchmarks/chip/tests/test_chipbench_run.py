"""The whole run at a tiny size on the CPU (interpret mode), from a
throwaway checkout that adds a cell, a traffic mix and a per-layer metric
with files and entries alone; the refusal of a CPU backend; the control
and a planted fault, both of which the check must call not correct."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
CHIP = ROOT / "benchmarks/chip"
SEED = 2 ** 40 + 11                  # a seed wider than 32 bits


def _throwaway(root: Path, model_type: str = "mixtral") -> None:
    """A checkout-shaped tree whose BENCHMARK.json holds one new cell, on
    a new configuration file and a new mix file, with one new metric
    reader beside copies of the real ones. A ``model_type`` other than
    Mixtral's comes with files of its own alone: its program mapping and
    its reference, here copies of Mixtral's."""
    chip = root / "benchmarks/chip"
    for kind in ("metrics", "hf", "references"):
        shutil.copytree(CHIP / kind, chip / kind)
    for kind in ("hf", "references"):
        shutil.copy(CHIP / kind / "mixtral.py",
                    chip / kind / f"{model_type}.py")
    (chip / "metrics/tokens_seen.py").write_text(
        "def read(ctx):\n"
        "    return float(sum(len(r.tokens) for r in ctx.served))\n")
    conf = json.loads((CHIP / "configs/mixtral-8x7b-l3.json").read_text())
    # every width small but the vocabulary's: the margins between the
    # best logits, which rounding has to cross, are then the real ones
    conf.update(name="tiny-moe", hidden_size=256, intermediate_size=128,
                num_attention_heads=4, num_key_value_heads=2,
                num_local_experts=4, model_type=model_type)
    conf["engine"].update(max_batch=2, prefill_segment=16)
    (chip / "configs").mkdir()
    (chip / "configs/tiny-moe.json").write_text(json.dumps(conf))
    (chip / "traffic").mkdir()
    (chip / "traffic/tiny-mix.json").write_text(json.dumps(
        {"loop": "closed", "clients": 2,
         "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.5},
         "output": {"dist": "lognormal", "median": 20, "sigma": 0.3},
         "check_requests": 4}))
    (chip / "peaks.json").write_text(json.dumps(
        {"source": "test only", "devices": {"cpu": {
            "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny-moe", "source": "test",
                         "file": "benchmarks/chip/configs/tiny-moe.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": "tiny.cell", "config": "tiny-moe",
                           "traffic": "tiny-mix", "chips": 1,
                           "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    bench["per_layer"].append(
        {"name": "tokens_seen", "unit": "tokens", "better": "higher",
         "source": "program_counter", "layer": "test", "moves": "itl_ms_p95"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


@pytest.fixture(scope="module")
def harness():
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from benchmarks.chip import harness
    return harness


@pytest.fixture
def tiny(tmp_path):
    _throwaway(tmp_path)
    return tmp_path


def _run(harness, root, trace=False):
    return harness.run_cell(root, "tiny.cell", SEED, 2.0, trace,
                            t_start=time.perf_counter(), require_chip=False)


@pytest.mark.parametrize("model_type", ["mixtral", "toy_moe"])
def test_throwaway_cell_runs_end_to_end(harness, tmp_path, model_type):
    _throwaway(tmp_path, model_type)
    cell, _ = harness.prepare(tmp_path, "tiny.cell", require_chip=False)
    assert Path(cell.reference.__file__).name == f"{model_type}.py"
    out = _run(harness, tmp_path)
    assert out["correct"] is True, out
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) >= {"itl_ms_p95", "setup_s"}
    assert list(out)[-1] == "compared"
    assert out["compared"]["mismatch_share"]["value"] <= \
        out["compared"]["mismatch_share"]["limit"]
    assert out["device"]["platform"] == "cpu"


def test_a_metric_added_by_a_file_is_read(harness, tiny):
    cell, _ = harness.prepare(tiny, "tiny.cell", require_chip=False)
    reader = harness.spec.load_reader(tiny, "tokens_seen")
    assert [m["name"] for m in cell.per_layer][-1] == "tokens_seen"
    ctx = harness.trace_mod.Context(cell=cell, model=None, served=[],
                                    t0=0, t1=1, stats0=None, stats1=None,
                                    peak={})
    assert reader(ctx) == 0.0


def test_a_token_altered_where_produced_is_not_correct(harness, tiny,
                                                       monkeypatch):
    from repro.serving.engine import CollaborativeEngine
    select = CollaborativeEngine.select_tokens

    def altered(self, logits, *args, **kwargs):
        return (select(self, logits, *args, **kwargs) + 1) % logits.shape[-1]

    monkeypatch.setattr(CollaborativeEngine, "select_tokens", altered)
    out = _run(harness, tiny)
    assert out["correct"] is False
    assert out["compared"]["mismatch_share"]["value"] > \
        out["compared"]["mismatch_share"]["limit"]


def test_a_decode_step_that_returns_its_state_unchanged_is_not_correct(
        harness, tiny, monkeypatch):
    from repro.serving.engine import CollaborativeEngine
    step = CollaborativeEngine._decode_step

    def stale(self, params, tokens, state, *args, **kwargs):
        logits, _, *rest = step(self, params, tokens, state, *args, **kwargs)
        return (logits, state, *rest)

    monkeypatch.setattr(CollaborativeEngine, "_decode_step", stale)
    out = _run(harness, tiny)
    assert out["correct"] is False
    assert out["compared"]["mismatch_share"]["value"] > \
        out["compared"]["mismatch_share"]["limit"]


def test_a_refused_request_fails_the_run(harness, tiny, monkeypatch):
    from repro.serving.scheduler import ContinuousBatchingScheduler
    submit = ContinuousBatchingScheduler.submit
    calls = []

    def refuse_third(self, prompt, *args, **kwargs):
        calls.append(len(prompt))
        if len(calls) == 3:
            raise ValueError("refused")
        return submit(self, prompt, *args, **kwargs)

    monkeypatch.setattr(ContinuousBatchingScheduler, "submit", refuse_third)
    out = _run(harness, tiny)
    assert out["failed"] == 1 and out["correct"] is False


def test_the_float8_control_is_not_correct(harness, tiny):
    """The control, the reference with float8 operands in the program's
    place, fails every number the configuration compares, where the
    program passes them: the same requests served to completion through
    ``build()`` and the scheduler, so no clock decides what is compared."""
    import itertools

    import numpy as np
    from repro.serving import build
    cell, _ = harness.prepare(tiny, "tiny.cell", require_chip=False)
    model = cell.model
    params = harness.weights.make_params(model, SEED)
    eng = harness.spec.engine_settings(cell)
    _, sched = build(model, cache=eng["cache"], serving=eng["serving"],
                     params=params)
    plan = itertools.islice(harness.traffic.requests(
        cell.traffic, model.vocab_size, np.random.default_rng(SEED)), 4)
    served = [harness.Served(p.index, p.prompt, p.max_new) for p in plan]
    for rec in served:
        harness._submit(sched, rec, lambda r: None)
    while harness._busy(sched):
        sched.step()
    readings = harness.check.compare(
        params, cell.reference, harness.spec.published(cell.config), served,
        cell.capacity, control=True)
    assert readings["tokens_compared"] == sum(r.max_new for r in served)
    for name, limit in cell.config["limits"].items():
        assert readings[name] <= limit < readings[name + "_control"], name


def test_the_runner_refuses_a_cpu_backend(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(CHIP / "run.py"), "--workload",
         "mixtral.single-decode", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""
    assert "needs 1 TPU chip" in proc.stderr
