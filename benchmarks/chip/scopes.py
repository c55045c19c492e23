"""Device time of the decode step by program layer.

The decode step names its stages with ``jax.named_scope``: ``embed``,
``attn``, ``router``, ``moe_probe``, ``moe_gather``, ``moe_dispatch``,
``moe_experts``, ``moe_commit``, ``moe_prefetch`` and ``lm_head``
(``serving/engine.py``, ``core/collaborative.py``). A scope reaches every
HLO instruction's ``op_name`` metadata
(``jit(_decode_step)/while/body/closed_call/moe_gather/gather``).

The profiler's ``XLA Ops`` events carry an instruction's name and text but
not its metadata, so the metadata is read from the program itself: the
cell's decode step is built again on abstract values (``jax.eval_shape``,
no arrays), lowered through the engine's own jitted step, and compiled for
the device the run used — the persistent compilation cache usually holds
it — and ``compile().as_text()`` gives every instruction's ``op_name``.
The trace's instruction names must all be found there, with the same
opcode, or nothing is read.

XLA's passes create instructions without metadata (the loops a scatter is
rewritten into, the pieces a gather is assembled from, the zero buffers
they start from). Each takes the scope of the nearest scoped instruction
it exchanges data with inside its computation, producers first; failing
that, the scope of the instruction that calls its computation (a loop XLA
built for a scatter carries the scatter's ``op_name``). What is left has
no scope, and its share is printed beside the reading.

Only runs of ``jit__decode_step`` that lie wholly inside the traced slice
count; time is each op's self time (``trace.self_times``), so a ``while``
does not count its body twice, divided by the number of those runs.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
import sys
import traceback
from collections import defaultdict, deque
from typing import Dict, List, Optional, Tuple

from . import spec, weights
from . import trace as trace_mod

SCOPES = ("embed", "attn", "router", "moe_probe", "moe_gather",
          "moe_dispatch", "moe_experts", "moe_commit", "moe_prefetch",
          "lm_head")
MODULE = "jit__decode_step"
NO_SCOPE = ""

_HEADER = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{$")
_INSTR = re.compile(r"^\s+(?:ROOT )?%(\S+) = .*? ([a-z][a-z0-9-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"\b(?:body|condition|to_apply|true_computation|"
                     r"false_computation)=%([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_REF = re.compile(r"%([\w.\-]+)")


def scope_of(op_name: str) -> str:
    """The first named stage on an ``op_name`` path, or NO_SCOPE."""
    return next((p for p in op_name.split("/") if p in SCOPES), NO_SCOPE)


@dataclasses.dataclass
class _Instr:
    comp: str
    opcode: str
    scope: str
    has_op_name: bool
    operands: List[str]


def _operand_text(line: str, opcode: str) -> str:
    """The parenthesised operand list after ``opcode(``."""
    i = line.index(f" {opcode}(") + len(opcode) + 2
    depth = 1
    for j in range(i, len(line)):
        depth += {"(": 1, ")": -1}.get(line[j], 0)
        if depth == 0:
            return line[i:j]
    return line[i:]


def parse_hlo(text: str) -> Dict[str, Tuple[str, str, bool]]:
    """Instruction name -> (opcode, scope, has an op_name of its own) for
    every instruction of a compiled module's text, scopes inherited as the
    module docstring says."""
    instrs: Dict[str, _Instr] = {}
    callers: Dict[str, str] = {}           # computation -> calling instr
    comp = None
    for line in text.splitlines():
        m = _HEADER.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if comp is None or not m:
            continue
        name, opcode = m.groups()
        o = _OP_NAME.search(line)
        instrs[name] = _Instr(comp, opcode,
                              scope_of(o.group(1)) if o else NO_SCOPE,
                              o is not None,
                              _REF.findall(_operand_text(line, opcode)))
        called = _CALLED.findall(line)
        b = _BRANCHES.search(line)
        if b:
            called += _REF.findall(b.group(1))
        for c in called:
            callers.setdefault(c, name)

    users: Dict[str, List[str]] = defaultdict(list)
    for name, ins in instrs.items():
        for op in ins.operands:
            users[op].append(name)

    def nearest(name: str) -> str:
        """Breadth-first over the computation's dataflow, producers
        before users at each distance; constants, which XLA shares
        between stages, are not passed through."""
        comp = instrs[name].comp
        seen, todo = {name}, deque([name])
        while todo:
            cur = todo.popleft()
            for nxt in instrs[cur].operands + users.get(cur, []):
                ins = instrs.get(nxt)
                if nxt in seen or ins is None or ins.comp != comp \
                        or ins.opcode == "constant":
                    continue
                if ins.scope:
                    return ins.scope
                seen.add(nxt)
                todo.append(nxt)
        return NO_SCOPE

    resolved: Dict[str, str] = {}

    def resolve(name: str) -> str:
        if name not in resolved:
            ins = instrs[name]
            scope = ins.scope or nearest(name)
            caller = callers.get(ins.comp)
            if not scope and caller is not None and caller != name:
                resolved[name] = NO_SCOPE          # cycle guard
                scope = resolve(caller)
            resolved[name] = scope
        return resolved[name]

    return {name: (ins.opcode, resolve(name), ins.has_op_name)
            for name, ins in instrs.items()}


def decode_lowered(model, cell: spec.Cell, sharding=None):
    """The cell's decode step, lowered by the engine
    (``CollaborativeEngine.lower_decode``) on abstract values: parameters,
    slot state and expert tiers are shapes (``jax.eval_shape`` over
    ``repro.serving.build``), so nothing is allocated. ``sharding`` places
    every argument (a described chip); None leaves them on the default
    device."""
    import jax
    from repro.serving import build

    eng = spec.engine_settings(cell)
    box = {}

    def make(params):
        engine, sched = build(model, cache=eng["cache"],
                              serving=eng["serving"], params=params)
        box["engine"] = engine
        return sched.state, engine.fast

    shapes = weights.param_shapes(model)
    args = (shapes, *jax.eval_shape(make, shapes))
    if sharding is not None:
        args = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=sharding), args)
    return box["engine"].lower_decode(*args)


def decode_scopes(ctx) -> Dict[str, Tuple[str, str, bool]]:
    """``parse_hlo`` of the cell's decode step compiled for this device."""
    return parse_hlo(decode_lowered(ctx.model, ctx.cell).compile().as_text())


@dataclasses.dataclass
class Reading:
    steps: int                       # decode runs wholly in the slice
    ms: Dict[str, float]             # ms a step by scope (NO_SCOPE too)
    no_op_name_share: float          # of decode time: no metadata of its own
    unscoped_share: float            # of decode time: no scope after all

    def total(self, *names: str) -> float:
        return sum(self.ms.get(n, 0.0) for n in names)


def _decode_ops(tr):
    """(decode runs wholly inside the slice, their ops as (instruction,
    start, end), instruction -> opcode)."""
    runs = [(s, e) for s, e in trace_mod.merged(tr.module_runs(MODULE))
            if s >= tr.a and e <= tr.b]
    starts = [s for s, _ in runs]
    ops, opcodes = [], {}
    for name, s, e in tr.ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][1]:
            instr = trace_mod.instruction(name)
            ops.append((instr, s, e))
            opcodes[instr] = _opcode(name)
    return len(runs), ops, opcodes


def _opcode(event_name: str) -> str:
    m = re.match(r"^%?\S+ = .*? ([a-z][a-z0-9-]*)\(", event_name)
    return m.group(1) if m else ""


def reading(ctx) -> Optional[Reading]:
    """The decode step's device time by scope in this traced run, or None
    where the trace has no whole decode run, the program names no scope,
    or the trace's instructions are not the compiled step's. Read once a
    run — kept on the run's ``ctx`` for the other readers — and printed
    to stderr."""
    if not hasattr(ctx, "scopes_reading"):
        ctx.scopes_reading = _reading(ctx)
    return ctx.scopes_reading


def _reading(ctx) -> Optional[Reading]:
    steps, ops, opcodes = _decode_ops(ctx.trace)
    if steps == 0:
        return None
    try:
        table = decode_scopes(ctx)
    except Exception:  # noqa: BLE001 — a reader never fails the run
        print("scopes: decode step not compiled for its op_names:",
              file=sys.stderr)
        traceback.print_exc()
        return None
    if not any(scope for _, scope, _ in table.values()):
        print("scopes: the decode step names no scope", file=sys.stderr)
        return None
    unknown = sorted(n for n, op in opcodes.items()
                     if n not in table or table[n][0] != op)
    if unknown:
        print(f"scopes: {len(unknown)} traced instruction(s) not in the "
              f"compiled decode step, e.g. {unknown[:3]}", file=sys.stderr)
        return None
    by_scope: Dict[str, int] = defaultdict(int)
    bare = 0
    for name, ns in trace_mod.self_times(ops).items():
        _, scope, has_op_name = table[name]
        by_scope[scope] += ns
        bare += 0 if has_op_name else ns
    total = sum(by_scope.values())
    r = Reading(steps=steps,
                ms={s: ns / steps / 1e6 for s, ns in by_scope.items()},
                no_op_name_share=bare / total if total else 0.0,
                unscoped_share=by_scope.get(NO_SCOPE, 0) / total
                if total else 0.0)
    print(f"scopes: {steps} decode step(s), ms a step: " + ", ".join(
        f"{s or '(none)'} {r.ms[s]:.3f}"
        for s in sorted(r.ms, key=lambda s: -r.ms[s])), file=sys.stderr)
    print(f"scopes: decode-step device time under no scope "
          f"{100 * r.unscoped_share:.2f}%; in instructions without an "
          f"op_name of their own {100 * r.no_op_name_share:.2f}%",
          file=sys.stderr)
    return r
