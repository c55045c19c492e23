"""Continuous-batching request scheduler over the collaborative engine.

The paper's framework decodes one request at a time; production MoE
serving (HybriMoE, DAOP) interleaves many. This scheduler generalizes the
workflow to T = ``EngineConfig.max_batch`` concurrent *slots* over ONE
shared expert cache:

  * admission   — a queued request claims a free slot: the shared prefill
                  trace runs once (first token sampled immediately, KV
                  state scattered into the slot's rows), then the slot
                  enters the PREFILLING phase while its cache-warming
                  replay drains. With
                  ``EngineConfig.admit_chunks_per_tick > 0`` the replay
                  advances at most that many chunks per tick BETWEEN
                  decode steps — established slots keep decoding while
                  the newcomer warms (no head-of-line blocking); with 0
                  the replay drains synchronously on the admission tick.
                  Under ``EngineConfig.prefill_segment`` the admission
                  tick runs NO forward at all: the slot enters
                  PREFILLING immediately and each tick streams (at most
                  ``admit_chunks_per_tick``) prompt segments through the
                  backbone — forward, KV append and cache warm fused —
                  with the first token sampled on the tick whose segment
                  completes the prompt.
  * decode tick — every step decodes the whole padded slot batch in one
                  jitted call; each slot sits at its own KV position
                  (per-slot ``pos`` vector) and inactive or PREFILLING
                  slots are masked out of the shared expert cache, the
                  stats and the output. Next tokens are drawn by the
                  engine's vectorized per-slot sampler, each row under
                  its own request's SamplingParams and PRNG chain.
  * retirement  — a request finishes on ``max_new_tokens``, ``eos_id`` or
                  one of its ``stop_sequences``; its slot frees
                  immediately and the next queued request is admitted on
                  the same tick (continuous batching: the batch never
                  drains to refill).
  * cancellation — :meth:`cancel` retires a queued or in-flight request
                  mid-decode or mid-warm: the slot frees for the next
                  admission (a PREFILLING slot's ticket is dropped), a
                  terminal ``(rid, -1, done=True)`` event is emitted,
                  and no further tokens are decoded for it.
  * backpressure — ``max_queue`` bounds the waiting line:
                  ``submit(..., block=False)`` raises :class:`QueueFull`
                  when it is at capacity (counted in ``queue_rejected``),
                  the blocking default drives ticks until space frees.
                  :meth:`pause_admission` / :meth:`resume_admission` let
                  a consumer hold new admissions (queued requests wait;
                  in-flight slots keep decoding).

Callers observe tokens as they decode: :meth:`stream` yields
``(rid, token, done)`` events in emission order, and each request may
carry an ``on_token`` callback invoked at append time. Everything here is
host-side orchestration (numpy + python lists) around the engine's jitted
primitives — the scheduler adds no traced code, so the decode step
compiles exactly once per (T, capacity) geometry.
"""
from __future__ import annotations

import os
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, Iterator, List, Optional, \
    Sequence, Tuple

import jax
import numpy as np

from repro.obs.metrics import LogHistogram
from repro.obs.trace import NULL_RECORDER, now_ns

from .engine import CollaborativeEngine, PrefillTicket, _one_prompt
from .sampling import GREEDY, SamplingParams, fold_keys, request_key
from .stats import RunStats

__all__ = ["Request", "ContinuousBatchingScheduler", "StreamEvent",
           "QueueFull"]

StreamEvent = Tuple[int, int, bool]          # (rid, token, done)


class QueueFull(RuntimeError):
    """Raised by ``submit(..., block=False)`` when the scheduler's
    bounded queue (``max_queue``) is at capacity — the consumer's typed
    backpressure signal."""


@dataclass(eq=False)
class Request:
    """One generation request: prompt, per-request sampling, termination
    conditions, optional streaming callback, and accumulated output.

    Identity semantics (``eq=False``): ``rid`` is the key; a generated
    ``__eq__`` would compare the ``np.ndarray`` prompt element-wise and
    make ``req in queue`` / ``list.remove`` raise on two distinct
    requests ("truth value of an array is ambiguous")."""
    rid: int
    prompt: np.ndarray                  # [P] int32
    max_new_tokens: int
    eos_id: Optional[int] = None
    sampling: SamplingParams = GREEDY
    stop_sequences: Tuple[Tuple[int, ...], ...] = ()
    on_token: Optional[Callable[[int, bool], None]] = None
    generated: List[int] = field(default_factory=list)
    cancelled: bool = False
    # lifecycle stamps (perf_counter_ns; 0 = phase not reached) written as
    # the request moves submit → admit → first token → done. Plain clock
    # reads — the spans they become are emitted retroactively at the
    # scheduler's _obs_retire drain point, never on the hot path.
    t_submit: int = 0
    t_admit: int = 0
    t_first: int = 0
    t_last: int = 0
    t_done: int = 0
    slot: int = -1

    @property
    def done(self) -> bool:
        if self.cancelled:
            return True
        if len(self.generated) >= self.max_new_tokens:
            return True
        if not self.generated:
            return False
        if self.eos_id is not None and self.generated[-1] == self.eos_id:
            return True
        for seq in self.stop_sequences:
            n = len(seq)
            if n and len(self.generated) >= n \
                    and tuple(self.generated[-n:]) == tuple(seq):
                return True
        return False

    @property
    def output(self) -> np.ndarray:
        return np.asarray(self.generated, np.int32)


class ContinuousBatchingScheduler:
    """Slot-based continuous batching for :class:`CollaborativeEngine`.

    ``key`` seeds the fallback per-request sampling chains (requests whose
    SamplingParams carry no explicit ``seed``); a request's i-th token
    always draws from ``fold_in(request_base, i)``, so runs are
    reproducible per (scheduler seed, admission order) and — for
    explicitly seeded requests — per request, independent of batch
    composition. ``max_queue`` bounds the waiting line (None =
    unbounded); see :meth:`submit` for the blocking/raising behaviour."""

    def __init__(self, engine: CollaborativeEngine, key=None,
                 max_queue: Optional[int] = None, recorder=None):
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self.engine = engine
        # trace recorder (repro.obs.TraceRecorder, or the no-op twin when
        # tracing is off); a recorder passed here also becomes the
        # engine's, so one flag wires the whole stack. Emission happens
        # only in the _obs_* drain helpers (reprolint RL007).
        self.obs = recorder if recorder is not None else engine.obs
        if recorder is not None:
            engine.obs = recorder
        # streaming latency histograms: always on (cheap host float math
        # feeding the RunStats percentiles, tracing or not)
        self._h_ttft = LogHistogram()
        self._h_tpot = LogHistogram()
        self._h_stall = LogHistogram()
        self.num_slots = engine.ecfg.max_batch
        self.max_queue = max_queue
        self.state = engine.init_slots()
        self.slots: List[Optional[Request]] = [None] * self.num_slots
        # PREFILLING phase: slot t warms through _tickets[t] and is masked
        # out of decode until the ticket drains (None = decoding/free)
        self._tickets: List[Optional[PrefillTicket]] = [None] * self.num_slots
        self.queue: Deque[Request] = deque()
        self._next = np.zeros((self.num_slots, 1), np.int32)
        self._rid = 0
        self._key = key if key is not None else jax.random.PRNGKey(0)
        self._bases = np.zeros((self.num_slots, 2), np.uint32)
        self.finished: List[Request] = []
        self._submitted = 0
        self._paused = False
        self._admission_stalls = 0
        self._queue_rejected = 0
        # events/retirements produced OUTSIDE a consumer-driven tick
        # (cancellations, ticks driven by a blocking submit): buffered
        # here and delivered at the start of the next tick so stream()
        # never loses a token or a terminal done=True
        self._pending_events: List[StreamEvent] = []
        self._pending_done: List[Request] = []
        # REPRO_DEBUG_INVARIANTS=1: audit the page pool's refcount/free-
        # list/prefix-index invariants after every tick (tests set this;
        # production leaves it off — the audit walks the whole pool)
        self._debug_invariants = \
            os.environ.get("REPRO_DEBUG_INVARIANTS") == "1"

    def _split(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    # -- request intake ----------------------------------------------------
    def submit(self, prompt, max_new_tokens: int,
               eos_id: Optional[int] = None,
               sampling: Optional[SamplingParams] = None,
               stop_sequences: Sequence[Sequence[int]] = (),
               on_token: Optional[Callable[[int, bool], None]] = None,
               block: bool = True) -> Request:
        """Queue one request. Validates the prompt against the engine
        geometry here — at submission — so an oversized request fails
        fast with a clear error instead of mid-run after other requests
        already decoded.

        Bounded admission (``max_queue`` set): when the queue is at
        capacity, ``block=True`` (default) drives scheduler ticks until a
        queue slot frees — the natural backpressure for a synchronous
        producer — while ``block=False`` raises :class:`QueueFull`
        immediately (counted in ``queue_rejected``). A full queue with
        admission paused raises :class:`QueueFull` in both modes: ticks
        cannot drain it."""
        prompt = _one_prompt(prompt)[0]      # [P]; rejects [B, P] batches
        plen, cap = prompt.shape[0], self.engine.ecfg.capacity
        if plen < 1:
            raise ValueError("prompt must contain at least one token")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if plen + max_new_tokens > cap:
            raise ValueError(
                f"prompt length {plen} + max_new_tokens {max_new_tokens} "
                f"exceeds engine KV capacity {cap}; shorten the prompt or "
                f"raise EngineConfig.capacity")
        while self.max_queue is not None \
                and len(self.queue) >= self.max_queue:
            if not block or self._paused:
                self._queue_rejected += 1
                raise QueueFull(
                    f"scheduler queue is at max_queue={self.max_queue}"
                    + (" and admission is paused" if self._paused else
                       "; retry later or submit(block=True)"))
            # drain work until space frees; the ticks' events/retirements
            # re-enter the pending buffers so a later stream()/step()
            # still delivers every token and terminal done=True
            finished, events = self._tick()
            self._pending_events.extend(events)
            self._pending_done.extend(finished)
        req = Request(self._rid, prompt, int(max_new_tokens), eos_id,
                      sampling if sampling is not None else GREEDY,
                      tuple(tuple(int(t) for t in s)
                            for s in stop_sequences),
                      on_token, t_submit=now_ns())
        self._rid += 1
        self._submitted += 1
        self.queue.append(req)
        return req

    def pause_admission(self) -> None:
        """Hold new admissions: queued requests stay queued (and keep
        counting ``admission_stalls``) while in-flight slots decode and
        PREFILLING slots keep warming. ``stream()``/``run()`` drain only
        the in-flight work while paused — call :meth:`resume_admission`
        to serve the queue again."""
        self._paused = True

    def resume_admission(self) -> None:
        """Reopen admission; the next tick admits queued requests into
        free slots as usual."""
        self._paused = False

    @property
    def admission_paused(self) -> bool:
        return self._paused

    def cancel(self, rid: int) -> bool:
        """Cancel a queued or in-flight request mid-decode or mid-warm.

        An in-flight request's slot frees IMMEDIATELY — the next tick's
        admission can hand it to a waiting request without the cancelled
        one decoding another token; a PREFILLING slot additionally drops
        its warming ticket (no further chunks replay). The request
        retires with a terminal ``(rid, -1, done=True)`` stream event,
        delivered ahead of the next tick's events (-1, never a real
        token: every generated token was already streamed exactly once);
        its ``on_token`` callback fires once more with ``(-1, True)``.
        Returns True if the request was found live (queued or in a slot),
        False if unknown or already finished — cancelling is idempotent
        and never raises."""
        req = None
        for r in self.queue:
            if r.rid == rid:
                req = r
                self.queue.remove(r)
                break
        if req is None:
            for t, r in enumerate(self.slots):
                if r is not None and r.rid == rid:
                    if r.done:
                        # finished on the last tick, awaiting retirement:
                        # its terminal done=True event already streamed —
                        # emitting a second one would break the
                        # one-terminal-event contract
                        return False
                    req = r
                    self.slots[t] = None          # slot free for admission
                    self._tickets[t] = None       # mid-warm: drop the ticket
                    self.engine.release_slot(t)   # paged: pages back now
                    break
        if req is None:
            return False
        req.cancelled = True                      # done; rejects new tokens
        req.t_done = now_ns()
        self.finished.append(req)
        self._pending_done.append(req)            # next _tick reports it
        self._pending_events.append((req.rid, -1, True))
        if req.on_token is not None:
            req.on_token(-1, True)
        self._obs_retire([req])
        return True

    def fork(self, rid: int, max_new_tokens: Optional[int] = None,
             sampling: Optional[SamplingParams] = None) -> Request:
        """Fork a live request into a free slot (paged KV only).

        The child shares ALL the parent's KV pages — zero KV is copied
        now; the partial last page copy-on-writes when either side next
        appends — and continues decoding from the parent's pending next
        token under its own sampling chain (``sampling``; parent's by
        default) and budget (``max_new_tokens``; parent's by default).
        The parent must be fully warmed (not PREFILLING) and not done;
        raises :class:`~repro.serving.kv_pool.PoolExhausted` when the
        pool cannot commit the child's decode pages."""
        if not self.engine.ecfg.kv_paged:
            raise RuntimeError("fork requires EngineConfig.kv_paged")
        src = next((t for t, r in enumerate(self.slots)
                    if r is not None and r.rid == rid), None)
        if src is None or self.slots[src].done:
            raise ValueError(f"request {rid} is not in a live slot")
        if self._tickets[src] is not None:
            raise ValueError(
                f"request {rid} is still PREFILLING; fork after warmup")
        dst = next((t for t in range(self.num_slots)
                    if self.slots[t] is None), None)
        if dst is None:
            raise RuntimeError("no free slot to fork into")
        parent = self.slots[src]
        new_max = parent.max_new_tokens if max_new_tokens is None \
            else int(max_new_tokens)
        plen, cap = parent.prompt.shape[0], self.engine.ecfg.capacity
        if new_max <= len(parent.generated):
            raise ValueError(
                f"max_new_tokens {new_max} <= tokens already generated "
                f"({len(parent.generated)}): the child would be born done")
        if plen + new_max > cap:
            raise ValueError(
                f"prompt length {plen} + max_new_tokens {new_max} exceeds "
                f"engine KV capacity {cap}")
        child = Request(self._rid, parent.prompt, new_max, parent.eos_id,
                        sampling if sampling is not None else parent.sampling,
                        parent.stop_sequences,
                        generated=list(parent.generated))
        # the child is born mid-decode: its lifecycle starts (and its
        # queued/prefill phases collapse to zero) at the fork instant
        child.t_submit = child.t_admit = child.t_first = child.t_last \
            = now_ns()
        child.slot = dst
        self._rid += 1
        self._submitted += 1
        self.state = self.engine.fork_slot(self.state, src, dst,
                                           plen + new_max)
        self._next[dst, 0] = self._next[src, 0]
        self._bases[dst] = request_key(child.sampling, self._split())
        self.slots[dst] = child
        self._tickets[dst] = None
        return child

    # -- slot bookkeeping --------------------------------------------------
    @property
    def active_mask(self) -> np.ndarray:
        """Occupied slots — decoding OR warming (PREFILLING)."""
        return np.array([s is not None for s in self.slots], bool)

    @property
    def decode_mask(self) -> np.ndarray:
        """Slots that decode this tick: occupied and fully warmed (a
        PREFILLING slot is masked out until its ticket drains)."""
        return np.array([s is not None and tk is None
                         for s, tk in zip(self.slots, self._tickets)], bool)

    @property
    def num_active(self) -> int:
        return int(self.active_mask.sum())

    @property
    def prefill_pending(self) -> int:
        """Slots currently in the PREFILLING phase (warming mid-replay)."""
        return sum(tk is not None for tk in self._tickets)

    def _retire(self) -> List[Request]:
        out = []
        for t, req in enumerate(self.slots):
            if req is not None and req.done:
                self.slots[t] = None
                self._tickets[t] = None   # done mid-warm: drop the replay
                self.engine.release_slot(t)   # paged: pages back to pool
                out.append(req)
        self.finished.extend(out)
        if out:
            self._obs_retire(out)
        return out

    def _append(self, req: Request, tok: int,
                events: List[StreamEvent]) -> None:
        t = now_ns()
        req.generated.append(tok)
        if req.t_first == 0:
            req.t_first = t
            self._h_ttft.observe((t - req.t_submit) / 1e6)
        else:
            self._h_tpot.observe((t - req.t_last) / 1e6)
        req.t_last = t
        done = req.done
        if done:
            req.t_done = t
        events.append((req.rid, tok, done))
        if req.on_token is not None:
            req.on_token(tok, done)

    def _admit(self, events: List[StreamEvent]) -> int:
        if self._paused:
            return 0
        admitted = 0
        for t in range(self.num_slots):
            if self.slots[t] is None and self.queue:
                req = self.queue[0]
                if not self.engine.can_admit(req.prompt,
                                             req.max_new_tokens):
                    # paged KV backpressure: the FIFO head can't commit
                    # its pages yet — stop admitting (skipping ahead would
                    # starve it); retirements free pages, so it clears on
                    # a later tick, counted by the stall signal below
                    break
                self.queue.popleft()
                req.t_admit = now_ns()
                req.slot = t
                admitted += 1
                base = request_key(req.sampling, self._split())
                self._bases[t] = base
                ticket = self.engine.start_prefill(
                    req.prompt,
                    max_total_tokens=(req.prompt.shape[0]
                                      + req.max_new_tokens))
                if ticket.logits is None:
                    # segment-streamed: no forward ran on this tick — the
                    # slot goes straight into PREFILLING and the first
                    # token is sampled when _advance_prefills drains the
                    # stream. claim_slot pre-binds the page table so a
                    # mid-stream cancel releases pages normally.
                    self.engine.claim_slot(ticket, t)
                    self.slots[t] = req
                    self._tickets[t] = ticket
                    continue
                try:
                    first_tok = self.engine.sample_first(
                        ticket, req.sampling,
                        key=jax.random.fold_in(base, 0))
                    self.state = self.engine.bind_slot(self.state, ticket, t)
                except BaseException:
                    # the ticket's pages are allocated but not yet bound
                    # to the slot: release them or a failed admission
                    # leaks the table
                    self.engine.abort_ticket(ticket)
                    raise
                # claim the slot BEFORE the first-token callback fires so
                # an on_token handler that calls cancel() finds the
                # request live (cancel then frees the slot right here)
                self._next[t, 0] = first_tok
                self.slots[t] = req
                self._tickets[t] = None if ticket.done else ticket
                self._append(req, first_tok, events)
        return admitted

    def _advance_prefills(self, events: List[StreamEvent]) -> None:
        """Drive every PREFILLING slot's warming replay (or segment
        stream): the whole ticket at once when
        ``admit_chunks_per_tick == 0`` (synchronous admission), at most
        that many chunks/segments otherwise — the overlapped path that
        keeps decode ticks flowing under a long-prompt admission. A
        drained ticket flips its slot into the decode set of THIS tick
        (matching the synchronous path's admit-and-decode-same-tick
        behaviour). A drained segment-streamed ticket additionally owes
        the request its deferred first token: sampled, bound and
        streamed here."""
        per_tick = self.engine.ecfg.admit_chunks_per_tick
        for t, ticket in enumerate(self._tickets):
            if ticket is None or self.slots[t] is None:
                continue
            budget = ticket.remaining if per_tick == 0 \
                else min(per_tick, ticket.remaining)
            self.state, done = self.engine.advance_prefill_state(
                ticket, self.state, budget)
            if done:
                self._tickets[t] = None
                if ticket.seg > 0:
                    req = self.slots[t]
                    first_tok = self.engine.sample_first(
                        ticket, req.sampling,
                        key=jax.random.fold_in(self._bases[t], 0))
                    self.state = self.engine.bind_slot(self.state, ticket, t)
                    self._next[t, 0] = first_tok
                    self._append(req, first_tok, events)

    # -- the decode loop ---------------------------------------------------
    def _tick(self) -> Tuple[List[Request], List[StreamEvent]]:
        """One scheduler tick: retire -> admit -> advance warming -> one
        padded decode step over the warmed slots.
        Returns (requests finished this tick, stream events in order)."""
        events: List[StreamEvent] = []
        finished: List[Request] = []
        t0 = now_ns()
        if self._pending_events or self._pending_done:
            # buffered events since the last consumer-driven tick drain
            # first, in production order — a cancellation's done=True and
            # everything a blocking submit() decoded precede what this
            # tick decodes — and their retirements count toward this
            # tick's finished return like any other
            events.extend(self._pending_events)
            self._pending_events.clear()
            finished.extend(self._pending_done)
            self._pending_done.clear()
        finished += self._retire()
        t_adm0 = now_ns()
        warming = self.prefill_pending
        admitted = self._admit(events)
        finished += self._retire()       # an admitted req may already be done
        if self.queue:
            # a request is waiting and no slot took it this tick (every
            # slot busy, or admission paused): the head-of-line signal
            self._admission_stalls += 1
        self._advance_prefills(events)
        # a deferred first token may have completed a max_new_tokens=1
        # request just now: retire it before the decode step so its slot
        # neither decodes a phantom token nor blocks a later admission
        finished += self._retire()
        active = self.decode_mask
        t_adm1 = now_ns()
        if admitted or warming:
            # the admission-stall sample: time this tick spent on
            # admission work (prefill forward, warm replay, slot binding)
            # that the established slots' decode step had to wait behind
            self._h_stall.observe((t_adm1 - t_adm0) / 1e6)
        decoded = 0
        t_sel = t_emit = t_adm1
        if active.any():
            decoded = int(active.sum())
            logits, self.state = self.engine.decode_batch(
                self._next, self.state, active)
            t_sel = now_ns()
            params = [r.sampling if r is not None and tk is None else GREEDY
                      for r, tk in zip(self.slots, self._tickets)]
            if all(p.greedy for p in params):
                keys = None                   # greedy: skip key derivation
            else:
                counts = np.array([len(r.generated) if r is not None else 0
                                   for r in self.slots], np.int32)
                keys = fold_keys(self._bases, counts)
            # the sanctioned once-per-tick token drain: selected tokens
            # MUST reach the host to stream to callers and feed the next
            # step's input buffer — this is the tick's single sync point
            toks = np.asarray(jax.device_get(self.engine.select_tokens(  # reprolint: allow[RL002] once-per-tick token drain
                logits[:, 0], params, keys))).astype(np.int32)
            t_emit = now_ns()
            for t, req in enumerate(self.slots):
                if req is None or not active[t]:
                    continue
                self._append(req, int(toks[t]), events)
                self._next[t, 0] = toks[t]
        self._obs_tick((t0, t_adm1, t_sel, t_emit), admitted, warming,
                       decoded)
        if self._debug_invariants and self.engine.kv_pool is not None:
            self.engine.kv_pool.check_invariants()
        return finished, events

    # -- trace drain helpers (the ONLY emission sites; see RL007) ----------
    def _obs_tick(self, marks: Tuple[int, int, int, int], admitted: int,
                  warming: int, decoded: int) -> None:
        """Sanctioned drain point: the tick's step-phase spans, emitted
        after the tick's token drain from plain clock readings the tick
        collected along the way (reading the clock is not emission).

        ``marks`` = (tick start, end of the retire/admit bookkeeping,
        start of token selection, start of emission). Leaves: ``admission``
        — retire, admit, advance prefills, on every tick (``tick``'s args
        say whether it did admission work; on a tick that decodes nothing
        it runs to the tick's end); on decoding ticks ``select`` — the
        selection program and its token drain — and ``emit`` — the
        per-token appends and callbacks. With the engine's leaves they
        tile the host's time between two decode steps."""
        t0, t_adm1, t_sel, t_emit = marks
        t1 = now_ns()
        self.obs.complete("sched", "tick", t0, t1,
                          {"admitted": admitted, "warming": warming,
                           "decoded": decoded,
                           "queued": len(self.queue)})
        self.obs.complete("sched", "admission", t0, t_adm1 if decoded else t1)
        if decoded:
            self.obs.complete("sched", "decode+drain", t_adm1, t1)
            self.obs.complete("sched", "select", t_sel, t_emit)
            self.obs.complete("sched", "emit", t_emit, t1)

    def _obs_retire(self, reqs: Sequence[Request]) -> None:
        """Sanctioned drain point: each retired (or cancelled) request's
        lifecycle spans, emitted retroactively from its timing stamps —
        the queued / prefill / decode phases, the terminal instant, and
        the slot-occupancy span on the slot's own track."""
        for req in reqs:
            track = f"req:{req.rid}"
            end = req.t_done if req.t_done else now_ns()
            if req.t_admit:
                self.obs.complete(track, "queued", req.t_submit,
                                  req.t_admit)
                first = req.t_first if req.t_first else end
                self.obs.complete(
                    track, "prefill", req.t_admit, first,
                    {"prompt_tokens": int(req.prompt.shape[0])})
                if req.t_first:
                    self.obs.complete(
                        track, "decode", req.t_first, end,
                        {"tokens": len(req.generated),
                         "ttft_ms": (req.t_first - req.t_submit) / 1e6})
            else:
                # cancelled while still queued: its whole life was the
                # queue — there is no prefill or decode phase to cover
                self.obs.complete(track, "queued", req.t_submit, end)
            self.obs.instant(
                track, "cancelled" if req.cancelled else "done",
                {"generated": len(req.generated)}, ts_ns=end)
            if req.slot >= 0 and req.t_admit:
                self.obs.complete(f"slot:{req.slot}", "occupied",
                                  req.t_admit, end, {"rid": req.rid})

    def step(self) -> List[Request]:
        """One tick; returns the requests that finished on it."""
        finished, _ = self._tick()
        return finished

    def stream(self) -> Iterator[StreamEvent]:
        """Drain queue + slots, yielding ``(rid, token, done)`` the moment
        each token is decoded — a request's events arrive in generation
        order and its final event (and only that one) carries
        ``done=True``. Requests interleave exactly as the continuous batch
        decodes them. While admission is paused the queue cannot drain:
        stream() finishes the in-flight work and returns, leaving queued
        requests waiting for :meth:`resume_admission`."""
        while (self.queue and not self._paused) or self._pending_events \
                or any(s is not None for s in self.slots):
            _, events = self._tick()
            for ev in events:
                yield ev
        self._retire()

    def run(self) -> Dict[int, np.ndarray]:
        """Drain queue + slots to completion; returns {rid: output tokens}."""
        for _ in self.stream():
            pass
        return {r.rid: r.output for r in self.finished}

    @property
    def stats(self) -> RunStats:
        """Typed run statistics: request accounting + the admission
        channel + an immutable engine counter snapshot (rates
        zero-guarded on EngineStats)."""
        ttft, tpot, stall = self._h_ttft, self._h_tpot, self._h_stall
        return RunStats(engine=self.engine.stats,
                        requests_submitted=self._submitted,
                        requests_finished=len(self.finished),
                        requests_active=self.num_active,
                        requests_queued=len(self.queue),
                        prefill_pending=self.prefill_pending,
                        admission_stalls=self._admission_stalls,
                        queue_rejected=self._queue_rejected,
                        ttft_ms_p50=ttft.percentile(50.0),
                        ttft_ms_p95=ttft.percentile(95.0),
                        ttft_ms_p99=ttft.percentile(99.0),
                        tpot_ms_p50=tpot.percentile(50.0),
                        tpot_ms_p95=tpot.percentile(95.0),
                        tpot_ms_p99=tpot.percentile(99.0),
                        stall_ms_p50=stall.percentile(50.0),
                        stall_ms_p95=stall.percentile(95.0),
                        stall_ms_p99=stall.percentile(99.0))
