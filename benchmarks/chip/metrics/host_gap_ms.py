"""Host time between two decode steps: from the end of step n's
``engine/wait`` (the device has finished it) to the end of step n+1's
``engine/dispatch`` (the next step is on its way), mean over consecutive
steps whose second tick did no admission work (its ``sched/tick`` args
``admitted`` and ``warming`` both 0). In between lie the leaves
``engine/drain``, ``sched/select``, ``sched/emit``, ``sched/admission``,
``engine/plan`` and ``engine/dispatch``; their means over the same gaps
are printed to stderr."""
import sys
from collections import defaultdict

LEAVES = ("engine/drain", "sched/select", "sched/emit", "sched/admission",
          "engine/plan", "engine/dispatch")


def read(ctx):
    spans = ctx.trace.spans
    marks = sorted((s.end, s.key) for s in spans
                   if s.key in ("engine/wait", "engine/dispatch"))
    ticks = [s for s in spans if s.key == "sched/tick"]
    gaps = []
    for (end, key), (nxt, nxt_key) in zip(marks, marks[1:]):
        if key != "engine/wait" or nxt_key != "engine/dispatch":
            continue
        tick = next((t for t in ticks if t.start <= nxt <= t.end), None)
        if tick is None or (tick.args or {}).get("admitted", 1) \
                or (tick.args or {}).get("warming", 1):
            continue
        gaps.append((end, nxt))
    if not gaps:
        return None
    leaf_ns = defaultdict(int)
    for s in spans:
        if s.key in LEAVES and any(a <= s.start and s.end <= b
                                   for a, b in gaps):
            leaf_ns[s.key] += s.end - s.start
    print(f"host_gap: {len(gaps)} gap(s), mean ms by leaf: " + ", ".join(
        f"{k} {leaf_ns[k] / len(gaps) / 1e6:.3f}" for k in LEAVES
        if k in leaf_ns), file=sys.stderr)
    return sum(b - a for a, b in gaps) / len(gaps) / 1e6
