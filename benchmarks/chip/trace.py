"""From a profiler trace and the program's spans to per-layer numbers.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. On the TPU's plane (``/device:TPU:0``) the line ``XLA Modules``
holds one event per jitted program run (``jit__decode_step(<hash>)``) and
``XLA Ops`` one per HLO instruction run, named by the instruction's text
(``%moe_ffn.36 = bf16[...] custom-call(...), custom_call_target=
"tpu_custom_call" ...``). A Mosaic kernel is a ``tpu_custom_call``; its
instruction is named after the jitted wrapper that holds it (``moe_ffn``
for the grouped expert kernels).

Reduced here:

* device busy time, the union of the op intervals cut to the traced
  window (ops nest: a ``while`` holds its body's ops, so a plain sum
  would count twice);
* each op's self time (its duration less what nested ops cover), summed
  by instruction name — the ``device_ops`` breakdown;
* the idle gaps between busy intervals, each named after the innermost
  program span open at its middle — the ``idle_gaps`` breakdown.

Device and host timestamps share the profiler's clock; a
``TraceAnnotation`` opened at a known ``perf_counter_ns`` reading (the
anchor) maps the program's spans, which use ``perf_counter_ns``, onto it.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Any, Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = "/device:TPU:0"
ANCHOR = "chipbench/anchor"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
_INSTR = re.compile(r"^%?([^\s=]+)")

Interval = Tuple[int, int]          # [start_ns, end_ns)


def instruction(event_name: str) -> str:
    """``%moe_ffn.36 = bf16[...] ...`` -> ``moe_ffn.36``."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def base(name: str) -> str:
    """Instruction or module name without its numeric suffix or hash:
    ``moe_ffn.36`` -> ``moe_ffn``, ``jit__decode_step(123)`` ->
    ``jit__decode_step``."""
    name = name.split("(", 1)[0]
    return re.sub(r"\.\d+$", "", name)


def union_ns(intervals: Sequence[Interval]) -> int:
    """Length of the union of half-open intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(ops: Sequence[Tuple[str, int, int]]) -> Dict[str, int]:
    """Self time per name: each op's duration less the part of it that
    ops nested inside it cover (ops nest, they do not cross)."""
    out: Dict[str, int] = defaultdict(int)
    stack: List[List[Any]] = []      # [name, start, end, child cover]

    def close(item):
        name, start, end, cover = item
        out[name] += (end - start) - cover

    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0])
    while stack:
        close(stack.pop())
    return dict(out)


@dataclasses.dataclass
class Span:
    track: str
    name: str
    start: int                   # perf_counter_ns
    end: int
    args: Optional[Dict[str, Any]]

    @property
    def key(self) -> str:
        return f"{self.track}/{self.name}"


def spans_of(recorder, a: int, b: int) -> List[Span]:
    """The recorder's complete spans that lie inside [a, b]."""
    if recorder is None:
        return []
    return [Span(ev.track, ev.name, ev.ts_ns, ev.ts_ns + ev.dur_ns, ev.args)
            for ev in recorder.events()
            if ev.kind == "X" and ev.ts_ns >= a and ev.ts_ns + ev.dur_ns <= b]


@dataclasses.dataclass
class Reduction:
    """A trace reduced, on the host's ``perf_counter_ns`` clock."""
    a: int                       # traced window, host clock
    b: int
    ops: List[Tuple[str, int, int]]      # (event name, start, end)
    modules: List[Tuple[str, int, int]]
    spans: List[Span]

    @property
    def window_s(self) -> float:
        return (self.b - self.a) / 1e9

    def clipped(self) -> List[Interval]:
        """Every op's interval cut to the traced window [a, b]: a step
        still running when the profiler stops counts only up to b."""
        return [(max(s, self.a), min(e, self.b))
                for _, s, e in self.ops if e > self.a and s < self.b]

    @property
    def busy_s(self) -> float:
        return union_ns(self.clipped()) / 1e9

    def module_runs(self, module: str) -> List[Interval]:
        return [(s, e) for n, s, e in self.modules if base(n) == module]

    def kernel_seconds(self, kernel: str,
                       module: Optional[str] = None) -> float:
        """Device time of the Mosaic kernel ``kernel`` (instruction base
        name), optionally only inside runs of jitted program ``module``."""
        runs = merged(self.module_runs(module)) if module else None
        starts = [s for s, _ in runs] if runs else None
        total = 0
        for name, s, e in self.ops:
            if KERNEL_MARK not in name or base(instruction(name)) != kernel:
                continue
            if runs is not None:
                i = bisect.bisect_right(starts, s) - 1
                if i < 0 or s >= runs[i][1]:
                    continue
            total += e - s
        return total / 1e9

    def top_ops(self, n: int = 10) -> List[List[Any]]:
        st = self_times([(instruction(nm), s, e) for nm, s, e in self.ops])
        top = sorted(st.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in top]

    def idle_gaps(self, n: int = 10) -> List[List[Any]]:
        """The ``n`` longest device-idle gaps inside the window, each
        named by the innermost program span open at its middle."""
        busy = merged(self.clipped())
        edges = [self.a] + [x for iv in busy for x in iv] + [self.b]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            mid = (s + e) // 2
            open_ = [sp for sp in self.spans if sp.start <= mid < sp.end]
            name = (min(open_, key=lambda sp: sp.end - sp.start).key
                    if open_ else "no program span")
            out.append([name, (e - s) / 1e9])
        return out

    def breakdown(self) -> Dict[str, Any]:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def _events(line):
    for ev in line.events:
        yield ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)


def reduce_dir(trace_dir: str, recorder, anchor_ns: int, a: int, b: int
               ) -> Reduction:
    """Reads the one ``.xplane.pb`` under ``trace_dir``. ``anchor_ns`` is
    the ``perf_counter_ns`` reading taken inside the ``ANCHOR``
    annotation; ``[a, b]`` the traced window on that clock."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one xplane file, found {files}")
    data = ProfileData.from_file(files[0])
    offset = None
    ops: List[Tuple[str, int, int]] = []
    modules: List[Tuple[str, int, int]] = []
    device_lines = {}
    for plane in data.planes:
        if plane.name == DEVICE_PLANE:
            device_lines = {line.name: line for line in plane.lines}
        elif plane.name.startswith("/host:") and offset is None:
            for line in plane.lines:
                for name, s, _ in _events(line):
                    if name == ANCHOR:
                        offset = s - anchor_ns
                        break
    if offset is None:
        raise RuntimeError(f"no {ANCHOR!r} event in the host planes")
    if "XLA Ops" not in device_lines:
        raise RuntimeError(f"no XLA Ops line on {DEVICE_PLANE}")
    for name, s, e in _events(device_lines["XLA Ops"]):
        ops.append((name, s - offset, e - offset))
    if "XLA Modules" in device_lines:
        for name, s, e in _events(device_lines["XLA Modules"]):
            modules.append((name, s - offset, e - offset))
    return Reduction(a=a, b=b, ops=ops, modules=modules,
                     spans=spans_of(recorder, a, b))


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader gets (``metrics/<name>.py``)."""
    cell: Any                    # spec.Cell
    model: Any                   # the program's ModelConfig
    served: List[Any]            # harness.Served, every request sent
    t0: int                      # measured window, perf_counter_ns
    t1: int
    stats0: Any                  # EngineStats at the window's start
    stats1: Any                  # ... and at its end
    peak: Dict[str, float]       # peaks.json row of this device
    trace: Optional[Reduction] = None
