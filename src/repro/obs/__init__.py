"""Event-sourced tracing and metrics for the collaborative serving stack.

The paper's argument is a latency decomposition — where each decode
step's milliseconds go (cache hit vs. miss-fetch vs. CPU lane) — so the
serving stack records *timelines*, not just end-of-run counters:

* ``trace``   — ``TraceRecorder``: a preallocated ring buffer of spans,
  instants and counter samples on the monotonic clock, plus the
  ``NULL_RECORDER`` no-op twin used when tracing is off.
* ``metrics`` — ``LogHistogram``: streaming log-bucket histograms that
  yield p50/p95/p99 for TTFT, TPOT and admission stall without storing
  raw samples.
* ``export``  — Chrome trace-event JSON (loadable in Perfetto /
  ``chrome://tracing``) with one track per request, per slot and per
  lane, and a structural validator CI runs on the artifact.

A decode step's lane attribution (hit, fetched and cpu-lane experts)
rides the ``engine/decode_step`` span's args; the ``lane:*`` tracks carry
prefetch reservations and landings and the host executor's busy time.
Between two decode steps the leaf spans — ``engine/drain``,
``sched/select``, ``sched/emit``, ``sched/admission``, ``engine/plan``,
``engine/dispatch`` — tile the host's time, and ``engine/wait`` covers
the device running the step.

Drain-point rule (enforced by reprolint RL007): emission calls —
``complete`` / ``instant`` / ``counter`` / ``span`` — are only legal at
the scheduler's sanctioned drain points, i.e. inside ``_obs_*`` helpers
called AFTER the per-tick token drain. Device-side stages are timed by
bracketing the jitted calls at the drain, never by syncing inside them;
nothing inside the jitted/pure_callback graph may emit.
"""
from .metrics import LogHistogram
from .trace import (NULL_RECORDER, NoopRecorder, TraceEvent, TraceRecorder,
                    now_ns)
from .export import (chrome_trace, validate_chrome_trace,
                     write_chrome_trace)

__all__ = [
    "LogHistogram",
    "NULL_RECORDER",
    "NoopRecorder",
    "TraceEvent",
    "TraceRecorder",
    "now_ns",
    "chrome_trace",
    "validate_chrome_trace",
    "write_chrome_trace",
]
