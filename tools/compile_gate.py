#!/usr/bin/env python
"""Compile-count regression gate for the serving hot path.

The static rules (``python -m repro.analysis``) catch *patterns* that
cause recompilation; this harness catches the *fact* of it. It runs a
short two-request serve on the reduced model under ``jax_log_compiles``,
attributes every XLA compilation to a phase, and asserts the steady-state
decode phase triggers ZERO recompiles:

* ``warmup``     — engine build + request A served end-to-end: every
                   stage (chunked/segmented prefill, decode step, KV
                   writes, token selection) traces and compiles here.
* ``admission``  — request B submitted to the warm engine and ticked
                   until its first token: admission-geometry compiles
                   (a new prefill chunk/segment shape) land here and are
                   reported but allowed.
* ``steady``     — request B's remaining decode ticks: the
                   continuous-batching loop is geometry-stable by
                   design, so ANY compilation here is a regression (the
                   ragged-segment and paged-CSR paths are one stray
                   Python-int static argument away from per-step
                   recompiles) and fails the gate.

Run ``PYTHONPATH=src python tools/compile_gate.py`` (CI adds
``--json COMPILE_GATE.json`` and archives the attribution artifact; use
``--kv-paged`` / ``--prefill-segment`` to gate those paths too).
"""
from __future__ import annotations

import argparse
import json
import logging
import sys
from typing import List, Optional

PHASES = ("warmup", "admission", "steady")
_COMPILE_PREFIX = "Compiling "
_COMPILE_MARKER = " with global shapes"


class CompileLog(logging.Handler):
    """Captures ``jax_log_compiles`` records and stamps each compilation
    with the currently active serve phase."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.phase = "warmup"
        self.events: List[dict] = []

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith(_COMPILE_PREFIX) and _COMPILE_MARKER in msg:
            fn = msg[len(_COMPILE_PREFIX):].split(_COMPILE_MARKER, 1)[0]
            self.events.append({"phase": self.phase, "fn": fn})

    def counts(self) -> dict:
        out = {p: 0 for p in PHASES}
        for e in self.events:
            out[e["phase"]] += 1
        return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="serve two requests under jax_log_compiles and fail "
                    "on any steady-state decode recompilation")
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--kv-paged", action="store_true")
    ap.add_argument("--prefill-segment", type=int, default=0,
                    metavar="C", help="segment-streamed prefill with "
                    "C-token segments (0 = replay prefill)")
    ap.add_argument("--json", default=None,
                    help="write the per-phase compile attribution here "
                         "(the CI artifact)")
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_log_compiles", True)
    log = CompileLog()
    # jax emits "Compiling <fn> with global shapes and types [...]" on
    # this logger at WARNING when jax_log_compiles is set
    logging.getLogger("jax._src.interpreters.pxla").addHandler(log)
    # drop the per-compile "Finished tracing/compilation" timing spam the
    # same flag turns on — the gate only needs the Compiling records
    logging.getLogger("jax._src.dispatch").setLevel(logging.ERROR)

    import numpy as np
    from repro.config import get_config, reduced
    from repro.obs import TraceRecorder
    from repro.serving import build

    cfg = reduced(get_config(args.arch))
    serving = dict(max_batch=args.slots, capacity=64,
                   prefill_chunk=args.prefill_chunk)
    if args.kv_paged:
        serving.update(kv_paged=True)
    if args.prefill_segment:
        serving.update(prefill_segment=args.prefill_segment)
    # gate WITH tracing live: the obs drain helpers are host-only work,
    # so a recorder must never change what compiles (a trace-induced
    # recompile would show up here as a steady-phase failure)
    recorder = TraceRecorder()
    _, sched = build(cfg, cache=dict(policy="lru"), serving=serving,
                     seed=0, recorder=recorder)

    rng = np.random.default_rng(0)
    prompt_a = rng.integers(0, cfg.vocab_size, 6)
    prompt_b = rng.integers(0, cfg.vocab_size, 8)
    ticks = {p: 0 for p in PHASES}

    def tick_until(phase: str, done, limit: int = 400) -> None:
        while not done():
            if ticks[phase] >= limit:
                print(f"compile_gate: phase {phase!r} exceeded {limit} "
                      f"ticks without completing", file=sys.stderr)
                sys.exit(2)
            sched.step()
            ticks[phase] += 1

    # warmup: request A end-to-end — every stage compiles here
    sched.submit(prompt_a, max_new_tokens=args.new_tokens)
    tick_until("warmup", lambda: sched.stats.requests_finished >= 1)

    # admission: request B enters the warm engine, up to its first token
    log.phase = "admission"
    sched.submit(prompt_b, max_new_tokens=args.new_tokens)
    first = sched.stats.first_tokens
    tick_until("admission", lambda: sched.stats.first_tokens > first)

    # steady: request B's remaining decode — must be compile-free
    log.phase = "steady"
    tick_until("steady", lambda: sched.stats.requests_finished >= 2)

    counts = log.counts()
    report = {
        "config": {"arch": args.arch, "slots": args.slots,
                   "new_tokens": args.new_tokens,
                   "prefill_chunk": args.prefill_chunk,
                   "kv_paged": args.kv_paged,
                   "prefill_segment": args.prefill_segment,
                   "traced": True},
        "trace_events": len(recorder),
        "ticks": ticks,
        "counts": counts,
        "events": log.events,
        "ok": counts["steady"] == 0 and ticks["steady"] > 0,
    }
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
            f.write("\n")

    for phase in PHASES:
        fns = [e["fn"] for e in log.events if e["phase"] == phase]
        print(f"compile_gate: {phase}: {len(fns)} compilation(s) over "
              f"{ticks[phase]} tick(s)"
              + (f" — {', '.join(sorted(set(fns)))}" if fns else ""))

    if ticks["steady"] == 0:
        print("compile_gate: FAIL — steady phase ran zero decode ticks "
              "(nothing was gated)", file=sys.stderr)
        return 2
    if counts["steady"]:
        print(f"compile_gate: FAIL — {counts['steady']} recompilation(s) "
              f"in steady-state decode; the hot loop must be "
              f"geometry-stable", file=sys.stderr)
        return 1
    print("compile_gate: OK — zero steady-state decode recompilations")
    return 0


if __name__ == "__main__":
    sys.exit(main())
