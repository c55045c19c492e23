"""One run of one cell: set-up, the measured window, the check.

``run_cell`` is what ``run.py`` calls. In order it

1. makes the weights on the device from the seed (``weights.py``);
2. builds the engine through ``repro.serving.build`` and serves a few
   warm-up requests of the cell's own shapes, so every program is
   compiled (or read from the persistent cache) and the KV pool and the
   expert cache are in use;
3. starts the cell's closed loop and steps it until every slot is
   decoding: the end of set-up;
4. drives the loop through ``submit`` / ``step`` for the given seconds —
   with ``trace`` under the profiler and the program's recorder — and
   reads the peak of HBM in use right after;
5. frees the program's state and compares what it served with the
   reference (``check.py``).

Every timestamp is the benchmark's own host clock
(``time.perf_counter_ns``): a token's time is when its callback ran,
after the scheduler's tick brought it to the host.
"""
from __future__ import annotations

import dataclasses
import gc
import logging
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import check, spec, traffic, weights
from . import trace as trace_mod

WARMUP_NEW_TOKENS = 4
TRACE_SECONDS = 3.0         # the profiler's slice, at the window's end
GIB = 2 ** 30


@dataclasses.dataclass
class Served:
    index: int
    prompt: np.ndarray
    max_new: int
    client: int = 0
    refused: bool = False
    tokens: List[int] = dataclasses.field(default_factory=list)
    times: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


def _say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class _CompileCounter(logging.Handler):
    """Counts ``jax_log_compiles`` records (the method of
    ``tools/compile_gate.py``)."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names: List[str] = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling ") and " with global shapes" in msg:
            self.names.append(msg[len("Compiling "):].split(" ", 1)[0])


class _Counting:
    """Compiles inside a ``with`` block."""

    def __init__(self, jax):
        self.jax, self.counter = jax, _CompileCounter()
        self.logger = logging.getLogger("jax._src.interpreters.pxla")
        # the same flag logs timings and cache hits: keep them quiet
        self.quiet = [logging.getLogger(n) for n in
                      ("jax._src.dispatch", "jax._src.compiler")]

    def __enter__(self):
        self.saved = self.logger.propagate, [q.level for q in self.quiet]
        self.logger.propagate = False
        for q in self.quiet:
            q.setLevel(logging.ERROR)
        self.logger.addHandler(self.counter)
        self.jax.config.update("jax_log_compiles", True)
        return self.counter

    def __exit__(self, *exc):
        self.jax.config.update("jax_log_compiles", False)
        self.logger.removeHandler(self.counter)
        self.logger.propagate, levels = self.saved
        for q, level in zip(self.quiet, levels):
            q.setLevel(level)


def percentile(values, q: float) -> Optional[float]:
    """Exact percentile over every sample (linear between order
    statistics); None without samples."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def inter_token_ms(reqs, t0: int, t1: int) -> List[float]:
    """Every gap between consecutive tokens of every request, both
    inside [t0, t1]."""
    out = []
    for r in reqs:
        ts = np.asarray(r.times, np.int64)
        if len(ts) < 2:
            continue
        a, b = ts[:-1], ts[1:]
        keep = (a >= t0) & (b <= t1)
        out.extend(((b - a)[keep] / 1e6).tolist())
    return out


def tokens_in(reqs, t0: int, t1: int) -> int:
    return sum(int(((np.asarray(r.times) >= t0)
                    & (np.asarray(r.times) <= t1)).sum())
               for r in reqs if r.times)


def end_to_end(name: str, served, t0: int, t1: int, setup_s: float,
               peak: int) -> Optional[float]:
    """An end-to-end metric by its name: ``out_tok_s``, ``setup_s``,
    ``hbm_peak_gib``, and ``itl_ms_p<q>`` for any percentile q. None
    where the window has no sample of it."""
    if name == "out_tok_s":
        return tokens_in(served, t0, t1) / ((t1 - t0) / 1e9)
    if name == "setup_s":
        return setup_s
    if name == "hbm_peak_gib":
        return peak / GIB if peak else None
    kind, _, q = name.rpartition("_ms_p")
    if kind != "itl" or not q.isdigit():
        raise KeyError(f"no end-to-end metric {name!r}")
    return percentile(inter_token_ms(served, t0, t1), float(q))


def _device_info(jax) -> Dict[str, Any]:
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def _peak_bytes(jax) -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def _submit(sched, rec: Served, on_done: Callable[[Served], None]) -> None:
    """Sends one request; one the scheduler refuses is marked
    ``refused`` and never finishes."""
    def on_token(tok: int, done: bool) -> None:
        rec.times.append(time.perf_counter_ns())
        rec.tokens.append(int(tok))
        if done:
            rec.done = True
            on_done(rec)

    try:
        sched.submit(rec.prompt, max_new_tokens=rec.max_new,
                     on_token=on_token)
    except ValueError as e:
        _say(f"window: request {rec.index} refused: {e}")
        rec.refused = True


def _busy(sched) -> bool:
    return bool(sched.queue) or any(s is not None for s in sched.slots)


def warm_up(sched, cell: spec.Cell, vocab: int, seed: int) -> None:
    """Serves one request per slot, of one prompt segment, to
    completion: every program the window drives (segment step, decode
    step, slot binding, token selection) has the same shapes for every
    prompt length, so all compile here, and the pool and the expert
    cache leave their empty state."""
    rng = np.random.default_rng(weights.seed32(seed, "warm-up"))
    plen = int(cell.config["engine"]["prefill_segment"])
    for i in range(cell.config["engine"]["max_batch"]):
        rec = Served(-1 - i, rng.integers(0, vocab, plen, dtype=np.int32),
                     WARMUP_NEW_TOKENS)
        _submit(sched, rec, lambda r: None)
    while _busy(sched):
        sched.step()


class Profiler:
    """The JAX profiler over a slice of the window, with the anchor that
    puts the program's spans on the trace's clock."""

    def __init__(self, jax, start: int, stop: int):
        self.jax, self.start, self.stop = jax, start, stop
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.a = self.b = self.anchor = None

    def poll(self, now: int) -> None:
        if self.a is None and now >= self.start:
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            self.jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.a = time.perf_counter_ns()
        elif self.a is not None and self.b is None and now >= self.stop:
            self.close()

    def close(self) -> None:
        if self.a is None or self.b is not None:
            return
        self.b = time.perf_counter_ns()
        with self.jax.profiler.TraceAnnotation(trace_mod.ANCHOR):
            self.anchor = time.perf_counter_ns()
        self.jax.profiler.stop_trace()


class ClosedLoop:
    """The cell's closed loop: each client sends its next request when
    its last one finishes."""

    def __init__(self, sched, cell: spec.Cell, vocab: int, seed: int):
        self.sched = sched
        self.stream = traffic.requests(
            cell.traffic, vocab,
            np.random.default_rng(weights.seed32(seed, "traffic")))
        self.clients = int(cell.traffic["clients"])
        self.ready = list(range(self.clients))
        self.served: List[Served] = []

    def _send(self) -> None:
        while self.ready:
            p = next(self.stream)
            rec = Served(p.index, p.prompt, p.max_new, self.ready.pop(0))
            self.served.append(rec)
            _submit(self.sched, rec, lambda r: self.ready.append(r.client))

    def fill(self) -> None:
        """Steps until every client's first request has its first token:
        every slot is then decoding, so the window opens on the steady
        state and not on the slots' first admissions."""
        self._send()
        while any(not r.times and not r.refused
                  for r in self.served[:self.clients]):
            self.sched.step()
            self._send()

    def run(self, seconds: float, poll: Callable[[int], None]) -> int:
        """The measured window, from now for ``seconds``; returns its
        end (``perf_counter_ns``)."""
        end = time.perf_counter_ns() + int(seconds * 1e9)
        while True:
            self._send()
            now = time.perf_counter_ns()
            poll(now)
            if now >= end:
                return time.perf_counter_ns()
            self.sched.step()


def prepare(root: Path, workload: str, require_chip: bool = True):
    """Loads the cell, points JAX's persistent compilation cache at
    ``<checkout>/.jax_cache`` and checks the devices. Exits with code 2,
    before any work, where JAX finds no TPU or fewer chips than the cell
    asks for."""
    cell = spec.load_cell(root, workload)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(Path(root) / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        _say(f"chipbench: cell {workload} needs {cell.chips} TPU chip(s); "
             f"JAX found {len(devices)} {devices[0].platform!r} device(s)")
        raise SystemExit(2)
    return cell, jax


@dataclasses.dataclass
class Window:
    """What one measured window left behind; no device state."""
    served: List[Served]
    t0: int                          # the window, perf_counter_ns
    t1: int
    metrics: Dict[str, Any]
    device: Dict[str, Any]
    breakdown: Optional[Dict[str, Any]]


def serve_window(cell: spec.Cell, jax, seed: int, seconds: float,
                 trace: bool, t_start: float) -> Window:
    """Set-up, then the measured window; end-to-end metrics without
    ``trace``, per-layer metrics with it. Returns once the program's
    state is out of scope."""
    from repro.serving import build
    device = _device_info(jax)
    model = spec.model_config(cell.config)
    params = weights.make_params(model, seed)
    _say(f"setup: weights {weights.nbytes(params)} B, "
         f"peak_bytes_in_use {_peak_bytes(jax)}")
    recorder = None
    if trace:
        from repro.obs import TraceRecorder
        recorder = TraceRecorder(capacity=1 << 20)
    eng = spec.engine_settings(cell)
    engine, sched = build(model, cache=eng["cache"], serving=eng["serving"],
                          seed=weights.seed32(seed, "engine"),
                          params=params, recorder=recorder)
    peak_built = _peak_bytes(jax)
    _say(f"setup: engine built, peak_bytes_in_use {peak_built}")
    with _Counting(jax) as warm_compiles:
        warm_up(sched, cell, model.vocab_size, seed)
    _say(f"setup: warm-up compiled {len(warm_compiles.names)} program(s), "
         f"peak_bytes_in_use {_peak_bytes(jax)}")

    loop = ClosedLoop(sched, cell, model.vocab_size, seed)
    with _Counting(jax) as fill_compiles:
        loop.fill()
    _say(f"setup: slots filled, {len(loop.served)} request(s) sent, "
         f"compiled {len(fill_compiles.names)} program(s)")

    stats0 = engine.stats
    t0 = time.perf_counter_ns()
    setup_s = t0 / 1e9 - t_start
    profiler = None
    if trace:
        # the slice closes the window: writing the trace out stalls the
        # loop for seconds, and then no measured work waits behind it
        start = t0 + int(max(0.0, seconds - TRACE_SECONDS) * 1e9)
        profiler = Profiler(jax, start, start + int(
            min(TRACE_SECONDS, seconds) * 1e9))
    try:
        with _Counting(jax) as window_compiles:
            t1 = loop.run(seconds, profiler.poll if profiler is not None
                          else lambda now: None)
    finally:
        if profiler is not None:
            profiler.close()
    stats1 = engine.stats
    peak = _peak_bytes(jax)
    device["memory_peak_bytes"] = peak
    served = loop.served
    _say(f"window: {(t1 - t0) / 1e9:.3f} s, compiles in window "
         f"{len(window_compiles.names)} {sorted(set(window_compiles.names))}")
    _say(f"window: peak_bytes_in_use after set-up {peak_built}, "
         f"after window {peak}")

    gaps = inter_token_ms(served, t0, t1)
    if gaps:
        _say(f"window: itl ms over {len(gaps)}: " + ", ".join(
            f"p{q} {percentile(gaps, q):.3f}" for q in (50, 90, 95, 99)))
    metrics: Dict[str, Any] = {}
    breakdown = None
    if not trace:
        for m in cell.end_to_end:
            v = end_to_end(m["name"], served, t0, t1, setup_s, peak)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        ctx = trace_mod.Context(
            cell=cell, model=model, served=served, t0=t0, t1=t1,
            stats0=stats0, stats1=stats1,
            peak=spec.peaks(cell.root, device["kind"]))
        ctx.trace = trace_mod.reduce_dir(profiler.dir, recorder,
                                         profiler.anchor, profiler.a,
                                         profiler.b)
        shutil.rmtree(profiler.dir, ignore_errors=True)
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        breakdown = ctx.trace.breakdown()
        for m in cell.per_layer:
            v = spec.load_reader(cell.root, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    return Window(served=served, t0=t0, t1=t1, metrics=metrics,
                  device=device, breakdown=breakdown)


def verify(cell: spec.Cell, window: Window, seed: int,
           control: bool = False) -> Dict[str, Any]:
    """The check, once the program's state is freed: a sample of the
    requests that were served tokens, drawn from the seed, against the
    reference (``check.py``)."""
    gc.collect()
    model = spec.model_config(cell.config)
    rng = np.random.default_rng(weights.seed32(seed, "check"))
    picked = check.sample([r for r in window.served if r.tokens],
                          int(cell.traffic["check_requests"]), rng)
    params = weights.make_params(model, seed)
    t = time.perf_counter()
    readings = check.compare(params, model, picked, cell.capacity, control)
    _say(f"check: {readings['requests_compared']} request(s), "
         f"{readings['tokens_compared']} served token(s), "
         f"{time.perf_counter() - t:.3f} s")
    return readings


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, require_chip: bool = True
             ) -> Dict[str, Any]:
    """One run; returns the result object ``run.py`` prints last."""
    cell, jax = prepare(root, workload, require_chip)
    window = serve_window(cell, jax, seed, seconds, trace, t_start)
    served = window.served
    attempted = len(served)
    failed = sum(r.refused for r in served)
    _say(f"window: requests sent {attempted}, finished "
         f"{sum(r.done for r in served)}, failed {failed}")
    readings = verify(cell, window, seed)
    for name in ("logit_gap", "mean_gap", "mismatch_share"):
        _say(f"reading: {name} {readings[name]}")
    # the configuration's limits name the numbers compared
    compared = {name: {"value": readings[name], "limit": limit}
                for name, limit in cell.config["limits"].items()}
    correct = (readings["requests_compared"] > 0 and failed == 0
               and all(c["value"] <= c["limit"] for c in compared.values()))
    for name, c in compared.items():
        _say(f"compared: {name} {c['value']} limit {c['limit']}")
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": window.metrics,
           "device": window.device}
    if window.breakdown is not None:
        out["breakdown"] = window.breakdown
    out["compared"] = compared
    return out
