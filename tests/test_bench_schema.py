"""Benchmark JSON artifact schema: the ``--json`` outputs are validated
against ``RunStats.to_json()`` / ``EngineStats.to_json()``.

Pins two contracts: (a) typed stats export only JSON-native types and
round-trip through ``json.dumps``/``json.loads`` exactly (the old
string-keyed dict mixed a numpy array into the scalar channel and made
``json.dumps`` raise), and (b) ``benchmarks.common.dump_json`` writes the
``{"results": [...], "runs": [...]}`` schema CI archives, with every run
entry shaped like a typed-stats export.
"""
import copy
import importlib
import json
import pathlib
import pickle
import sys

import pytest

from repro.serving import EngineStats, RunStats

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
common = importlib.import_module("benchmarks.common")

SAMPLE = EngineStats(hits=7, accesses=12, host_assignments=5,
                     fetched_experts=3, tokens=6, steps=3,
                     prefetch_issued=4, prefetch_hits=2, prefetch_wasted=1,
                     predicted=8, predicted_correct=6,
                     prefill_hits=9, prefill_accesses=20, prefill_fetched=4,
                     prefill_tokens=10, prefill_chunks=2, first_tokens=2,
                     prefill_segments=3, prefix_tokens_skipped=4,
                     cpu_expert_calls=2, cpu_tokens=3, miss_expert_groups=3,
                     fused_groups=2, census_calls=2, census_threads=7,
                     affinity_hits=1, host_busy_us=150, host_queue_peak=2,
                     kv_pages_in_use=5, prefix_hits=1,
                     cow_forks=1, prefix_pages_retained=2,
                     per_layer_hits=(3, 4), per_layer_accesses=(6, 6))

ENGINE_KEYS = {
    "hits", "accesses", "host_assignments", "fetched_experts", "tokens",
    "steps", "prefetch_issued", "prefetch_hits", "prefetch_wasted",
    "predicted", "predicted_correct", "prefill_hits", "prefill_accesses",
    "prefill_fetched", "prefill_tokens", "prefill_chunks", "first_tokens",
    "prefill_segments", "prefix_tokens_skipped", "generated_tokens",
    "cpu_expert_calls", "cpu_tokens", "miss_expert_groups",
    "fused_groups", "census_calls", "census_threads", "affinity_hits",
    "host_busy_us", "host_queue_peak",
    "kv_pages_in_use", "prefix_hits", "cow_forks",
    "prefix_pages_retained",
    "hit_rate", "prefetch_hit_rate", "prefetch_waste_rate",
    "prediction_accuracy", "prefill_hit_rate", "cpu_offload_rate",
    "per_layer_hits", "per_layer_accesses", "per_layer_hit_rates",
}
RUN_KEYS = {"requests_submitted", "requests_finished", "requests_active",
            "requests_queued", "prefill_pending", "admission_stalls",
            "queue_rejected",
            "ttft_ms_p50", "ttft_ms_p95", "ttft_ms_p99",
            "tpot_ms_p50", "tpot_ms_p95", "tpot_ms_p99",
            "stall_ms_p50", "stall_ms_p95", "stall_ms_p99",
            "engine"}


def test_engine_stats_json_round_trips():
    d = SAMPLE.to_json()
    assert set(d) == ENGINE_KEYS
    assert json.loads(json.dumps(d)) == d        # exact round-trip
    for k, v in d.items():
        assert isinstance(v, (int, float, list)), (k, type(v))
    assert d["hit_rate"] == pytest.approx(7 / 12)
    assert d["per_layer_hit_rates"] == [0.5, 4 / 6]
    assert d["prefill_hit_rate"] == pytest.approx(9 / 20)
    assert d["cpu_offload_rate"] == pytest.approx(3 / 5)
    # first tokens fold into reported totals (tokens stays decode-only)
    assert d["generated_tokens"] == d["tokens"] + d["first_tokens"] == 8


def test_run_stats_delegate_and_round_trip():
    rs = RunStats(engine=SAMPLE, requests_submitted=3, requests_finished=2,
                  requests_active=1, requests_queued=0)
    # engine counters and rates reachable without the .engine hop
    assert rs.hits == 7 and rs.hit_rate == pytest.approx(7 / 12)
    d = rs.to_json()
    assert set(d) == RUN_KEYS
    assert set(d["engine"]) == ENGINE_KEYS
    assert json.loads(json.dumps(d)) == d


def test_run_stats_survive_copy_and_pickle():
    """Regression: the delegating __getattr__ used to recurse infinitely
    on instances whose fields are not set yet (copy.copy / pickle
    reconstruct via __new__ before filling the dict, then probe
    attributes) — "engine" itself must raise a plain AttributeError
    instead of delegating to self.engine."""
    rs = RunStats(engine=SAMPLE, requests_submitted=3, requests_finished=2,
                  prefill_pending=1, admission_stalls=4, queue_rejected=1)
    for clone in (copy.copy(rs), copy.deepcopy(rs),
                  pickle.loads(pickle.dumps(rs))):
        assert clone.requests_submitted == 3
        assert clone.engine == SAMPLE
        assert clone.hits == 7                     # delegation still works
        assert clone.hit_rate == pytest.approx(7 / 12)
        assert clone.admission_stalls == 4
        assert clone.to_json() == rs.to_json()
    # a half-built instance raises AttributeError (not RecursionError)
    empty = object.__new__(RunStats)
    with pytest.raises(AttributeError):
        empty.engine
    with pytest.raises(AttributeError):
        empty.hits


def test_zero_guarded_rates_on_empty_stats():
    """A run that never decoded reports 0.0 rates, not ZeroDivisionError."""
    s = EngineStats()
    assert s.hit_rate == s.prefetch_hit_rate == 0.0
    assert s.prediction_accuracy == s.prefetch_waste_rate == 0.0
    assert s.prefill_hit_rate == 0.0
    assert s.cpu_offload_rate == 0.0
    assert s.per_layer_hit_rates.shape == (0,)
    json.dumps(RunStats().to_json())


def test_dump_json_schema(tmp_path, monkeypatch):
    """dump_json writes {"results", "runs"} with run entries validating
    against the RunStats.to_json() schema."""
    monkeypatch.setattr(common, "_RESULTS", [])
    monkeypatch.setattr(common, "_RUNS", [])
    common.emit("bench.micro", 12.5, "derived=1")
    common.record_run("bench.run",
                      RunStats(engine=SAMPLE, requests_submitted=2,
                               requests_finished=2))
    path = tmp_path / "BENCH_test.json"
    common.dump_json(str(path))
    doc = json.loads(path.read_text())

    assert set(doc) == {"results", "runs"}
    assert doc["results"] == [
        {"name": "bench.micro", "us": 12.5, "derived": "derived=1"}]
    (run,) = doc["runs"]
    assert run["name"] == "bench.run"
    assert set(run["stats"]) == RUN_KEYS
    assert set(run["stats"]["engine"]) == ENGINE_KEYS
    # EngineStats exports (decode_prefetch's generate() path) validate too
    common.record_run("bench.engine_only", SAMPLE)
    common.dump_json(str(path))
    doc = json.loads(path.read_text())
    assert set(doc["runs"][1]["stats"]) == ENGINE_KEYS


def test_live_fleet_artifact_shapes(tmp_path, monkeypatch):
    """BENCH_fig5_throughput.json / BENCH_fig6_hitrate.json: the live-mode
    sweeps (fig5_throughput's concurrency scaling, fig6_hitrate's policy/
    prefetch matrix) record RunStats payloads that validate against the
    pinned schema like every other benchmark artifact."""
    importlib.import_module("benchmarks.fig5_throughput")
    importlib.import_module("benchmarks.fig6_hitrate")
    monkeypatch.setattr(common, "_RESULTS", [])
    monkeypatch.setattr(common, "_RUNS", [])
    names = ["fig5.live.slots1", "fig5.live.slots4",
             "fig6.live.lru.pf", "fig6.live.lfu"]
    for name in names:
        common.record_run(name, RunStats(engine=SAMPLE,
                                         requests_submitted=4,
                                         requests_finished=4))
    path = tmp_path / "BENCH_fig5_throughput.json"
    common.dump_json(str(path))
    doc = json.loads(path.read_text())
    assert [r["name"] for r in doc["runs"]] == names
    for run in doc["runs"]:
        assert set(run["stats"]) == RUN_KEYS
        assert set(run["stats"]["engine"]) == ENGINE_KEYS


def test_admission_overlap_artifact_shape(tmp_path, monkeypatch):
    """BENCH_admission_overlap.json: the CI smoke artifact triples an
    off/on/seg run whose stats carry the overlapped-admission channel
    (prefill_pending / admission_stalls / queue_rejected on the run,
    first_tokens / generated_tokens / prefill_segments /
    prefix_tokens_skipped on the engine) next to the established-latency
    and prefix-TTFT results."""
    bench = importlib.import_module("benchmarks.admission_overlap")
    assert [m[0] for m in bench.MODES] == ["off", "on", "seg"]
    monkeypatch.setattr(common, "_RESULTS", [])
    monkeypatch.setattr(common, "_RUNS", [])
    names = ["admission_overlap.off", "admission_overlap.on",
             "admission_overlap.seg", "admission_overlap.prefix"]
    for name in names:
        common.emit(f"{name}.stall", 1234.5, "max established gap")
        common.record_run(name, RunStats(engine=SAMPLE,
                                         requests_submitted=3,
                                         requests_finished=3,
                                         admission_stalls=2))
    common.emit("admission_overlap.prefix_ttft.cold", 9000.0, "cold TTFT")
    common.emit("admission_overlap.prefix_ttft.hit", 4000.0, "hit TTFT")
    path = tmp_path / "BENCH_admission_overlap.json"
    common.dump_json(str(path))
    doc = json.loads(path.read_text())
    assert [r["name"] for r in doc["runs"]] == names
    for run in doc["runs"]:
        stats = run["stats"]
        assert set(stats) == RUN_KEYS
        assert {"prefill_pending", "admission_stalls",
                "queue_rejected"} <= set(stats)
        assert set(stats["engine"]) == ENGINE_KEYS
        assert {"prefill_segments", "prefix_tokens_skipped",
                "prefix_pages_retained"} <= set(stats["engine"])
        assert stats["engine"]["generated_tokens"] == \
            stats["engine"]["tokens"] + stats["engine"]["first_tokens"]


def test_paged_kv_artifact_shape(tmp_path, monkeypatch):
    """BENCH_paged_kv.json: the CI smoke artifact pairs a dense/paged run
    whose engine stats carry the paged-KV channel (kv_pages_in_use /
    prefix_hits / cow_forks) next to the page-occupancy and TTFT
    results."""
    importlib.import_module("benchmarks.paged_kv")          # importable
    monkeypatch.setattr(common, "_RESULTS", [])
    monkeypatch.setattr(common, "_RUNS", [])
    common.emit("paged_kv.peak_pages", 17.0, "paged fleet peak occupancy")
    common.emit("paged_kv.ttft_prefix_hit_us", 11400.0, "warm-skip TTFT")
    for name in ("paged_kv.dense", "paged_kv.paged"):
        common.record_run(name, RunStats(engine=SAMPLE,
                                         requests_submitted=6,
                                         requests_finished=6))
    path = tmp_path / "BENCH_paged_kv.json"
    common.dump_json(str(path))
    doc = json.loads(path.read_text())
    assert [r["name"] for r in doc["runs"]] == ["paged_kv.dense",
                                                "paged_kv.paged"]
    for run in doc["runs"]:
        eng = run["stats"]["engine"]
        assert set(eng) == ENGINE_KEYS
        assert {"kv_pages_in_use", "prefix_hits",
                "cow_forks", "fused_groups"} <= set(eng)


def test_host_compute_artifact_shape_and_cost_model(tmp_path, monkeypatch):
    """BENCH_host_compute.json: the CI smoke artifact carries the
    host-execution channel in every run entry, and the benchmark's
    miss-handling cost model obeys the dispatcher's decision rule (the
    self-check's foundation): per-group savings are positive exactly when
    the policy prefers the CPU."""
    host_compute = importlib.import_module("benchmarks.host_compute")
    from repro.core.costmodel import MIXTRAL_TIMINGS
    from repro.hostexec import HostDispatchPolicy

    monkeypatch.setattr(common, "_RESULTS", [])
    monkeypatch.setattr(common, "_RUNS", [])
    common.record_run("host_compute.off", SAMPLE)
    common.record_run("host_compute.on", SAMPLE)
    path = tmp_path / "BENCH_host_compute.json"
    common.dump_json(str(path))
    doc = json.loads(path.read_text())
    assert [r["name"] for r in doc["runs"]] == ["host_compute.off",
                                                "host_compute.on"]
    for run in doc["runs"]:
        stats = run["stats"]
        assert set(stats) == ENGINE_KEYS
        assert {"cpu_expert_calls", "cpu_tokens",
                "cpu_offload_rate"} <= set(stats)

    # SAMPLE dispatched 2 one-plus-token groups at 8 threads (CPU-favored
    # on the paper's Mixtral timings): the modeled miss handling drops
    pol = HostDispatchPolicy(MIXTRAL_TIMINGS, threads=8)
    assert pol.prefers_cpu(1)
    ms_off, ms_on = host_compute.miss_handling_ms(SAMPLE, pol)
    assert ms_on < ms_off
    # one thread: the cost model prefers the fetch, and a run that
    # dispatched nothing to the CPU models no reduction
    none = EngineStats(hits=7, accesses=12, host_assignments=5,
                       fetched_experts=3, steps=3)
    ms_off0, ms_on0 = host_compute.miss_handling_ms(
        none, HostDispatchPolicy(MIXTRAL_TIMINGS, threads=1))
    assert ms_on0 == ms_off0


# -- reprolint CI artifacts: REPROLINT.json / REPROLINT.sarif ----------------

REPROLINT_FIXTURE = (pathlib.Path(__file__).resolve().parent
                     / "analysis_fixtures" / "rl011_bad")
FINDING_KEYS = {"rule", "file", "line", "message", "symbol", "severity"}


def test_reprolint_json_artifact_schema(tmp_path, capsys):
    """REPROLINT.json: {"new", "grandfathered", "stale_baseline"} with each
    finding dict carrying location, identity, and severity — the shape the
    CI failure annotations parse."""
    from repro.analysis.cli import main as reprolint

    out = tmp_path / "REPROLINT.json"
    assert reprolint(["--root", str(REPROLINT_FIXTURE), "--rules", "RL011",
                      "--json", str(out)]) == 1
    capsys.readouterr()
    doc = json.loads(out.read_text())
    assert set(doc) == {"new", "grandfathered", "stale_baseline"}
    assert doc["grandfathered"] == [] and doc["stale_baseline"] == []
    assert len(doc["new"]) == 2
    for f in doc["new"]:
        assert set(f) == FINDING_KEYS
        assert f["rule"] == "RL011" and f["severity"] == "warning"
        assert isinstance(f["line"], int) and f["line"] > 0
        assert f["file"].startswith("src/repro/")
    assert json.loads(json.dumps(doc)) == doc


def test_reprolint_sarif_artifact_schema(tmp_path, capsys):
    """REPROLINT.sarif: minimal valid SARIF 2.1.0 — versioned log, one run,
    a rule descriptor per registered rule, results indexing into them with
    the baseline's line-number-free key as the fingerprint."""
    from repro.analysis.cli import main as reprolint
    from repro.analysis.core import RULES
    from repro.analysis.sarif import SARIF_SCHEMA

    out = tmp_path / "REPROLINT.sarif"
    assert reprolint(["--root", str(REPROLINT_FIXTURE), "--rules", "RL011",
                      "--sarif", str(out)]) == 1
    capsys.readouterr()
    log = json.loads(out.read_text())
    assert set(log) == {"$schema", "version", "runs"}
    assert log["$schema"] == SARIF_SCHEMA
    assert log["version"] == "2.1.0"
    (run,) = log["runs"]
    driver = run["tool"]["driver"]
    assert driver["name"] == "reprolint"
    assert [r["id"] for r in driver["rules"]] == sorted(RULES)
    for r in driver["rules"]:
        assert set(r) == {"id", "shortDescription", "defaultConfiguration"}
        assert r["defaultConfiguration"]["level"] in ("error", "warning",
                                                      "note")
    assert len(run["results"]) == 2
    for res in run["results"]:
        assert set(res) == {"ruleId", "ruleIndex", "level", "message",
                            "locations", "partialFingerprints"}
        assert driver["rules"][res["ruleIndex"]]["id"] == res["ruleId"]
        (loc,) = res["locations"]
        region = loc["physicalLocation"]["region"]
        assert region["startLine"] > 0
        key = res["partialFingerprints"]["reprolintKey/v1"].split("\t")
        assert key[0] == res["ruleId"]
        assert key[1] == loc["physicalLocation"]["artifactLocation"]["uri"]


def test_reprolint_baseline_is_byte_stable(tmp_path):
    """--update-baseline determinism: shuffled, duplicated findings with
    control characters in messages serialize to identical bytes, and the
    sanitized keys still match on re-read."""
    from repro.analysis.baseline import (load_baseline, save_baseline,
                                         split_findings)
    from repro.analysis.core import Finding

    def mk(rule, file, line, msg, sym):
        return Finding(rule=rule, file=file, line=line, message=msg,
                       symbol=sym)

    findings = [
        mk("RL008", "src/repro/a.py", 10, "leak\ton a\npath", "A.f"),
        mk("RL009", "src/repro/b.py", 20, "unlocked write", "B"),
        mk("RL008", "src/repro/a.py", 99, "leak\ton a\npath", "A.f"),
    ]  # third is a line-moved duplicate of the first: same identity
    p1, p2 = tmp_path / "b1", tmp_path / "b2"
    save_baseline(p1, findings)
    save_baseline(p2, list(reversed(findings)))
    assert p1.read_bytes() == p2.read_bytes()

    baseline = load_baseline(p1)
    assert len(baseline) == 2                    # deduped, sanitized
    assert all("\t" not in part and "\n" not in part
               for key in baseline for part in key)
    new, old, stale = split_findings(findings, baseline)
    assert new == [] and stale == []             # control chars still match
    assert len(old) == 3
