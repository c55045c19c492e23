"""Collaborative serving engine: the paper's workflow, runnable end-to-end.

Serves an MoE LM with the expert weights split across the two tiers of
repro.core.collaborative: attention/router/norm weights plus an N-index
M-way expert cache resident in the fast tier; the full expert table in the
host tier. Every decode step runs the staged collaborative pipeline —
probe (cache check + grouping), execute (grouped tiered gmm), commit
(state update + async post-fetch) — all inside one jitted step function
whose cache state threads functionally (donated buffers).

With ``EngineConfig.prefetch`` the decode scan becomes a *software
pipeline* with cross-layer speculative prefetch (DAOP / Pre-gated style):
after layer *l*'s FFN, layer *l+1*'s router runs on layer *l*'s output
hidden state and the predicted top-k experts are reserved in the cache and
streamed in while layer *l+1*'s attention computes
(``prefetch_min_prob`` confidence-gates the reservations on router
probability). Prefetch changes residency and counters, never numerics.

With ``EngineConfig.host_compute`` the execute stage becomes the hybrid
CPU/GPU dispatcher of :mod:`repro.hostexec`: cache-miss expert groups the
calibrated cost model favors ship their activations to a multithreaded
host executor instead of paying the weight fetch, counted in the
``cpu_expert_calls`` / ``cpu_tokens`` stats channel. Cache bookkeeping is
identical on every lane; the in-graph ``host_backend="jax"`` keeps tokens
bit-identical to the all-GPU path.

Prefill is *request-shaped* and resumable: :meth:`start_prefill` runs the
one shared prefill trace (the backbone's prefill mode with the routing
trace emitted — there is no second prefill implementation) and returns a
:class:`PrefillTicket`; :meth:`advance_prefill` replays the prompt's
routing trace through the staged probe → execute → commit pipeline chunk
by chunk, so the prompt's own expert-routing warms the shared cache
before the first decode step (the paper's long-prompt scenario) — all at
once on the synchronous path (:meth:`prefill_chunked`), or one
``EngineConfig.admit_chunks_per_tick`` slice per scheduler tick on the
overlapped-admission path. The hidden states, KV cache and first-token
logits come from the trace in every mode, so warming — however paced —
changes cache residency and the ``prefill_*`` stat channel, never the
generated tokens.

With ``EngineConfig.prefill_segment`` the admission-tick forward itself
goes incremental: :meth:`start_prefill` only tokenizes and (paged)
allocates pages, and each :meth:`advance_prefill` runs ONE C-token
prompt segment through the backbone's segment mode — the segment
attends to the request's KV so far at its absolute offset, appends its
own KV (dense slot or pool pages), and its freshly emitted routing
trace warms the cache inside the same jitted step (the forward IS the
trace source; no separate replay pass). First-token logits emerge at
the last segment, so the per-tick admission cost drops from O(prompt)
to O(segment). Under paged KV a prefix-index hit skips the shared
span's forward AND warm outright — only the unshared suffix is ever
forwarded — counted in ``prefix_tokens_skipped``. Tokens stay
bit-identical to the one-shot forward: a segment row's flash-attention
chunk decomposition over the key axis is independent of how the query
axis is sliced, and the MoE combine is row-order invariant.

The engine is *batch-capable*: one decode step serves up to
``EngineConfig.max_batch`` concurrent requests, each at its own sequence
position (per-slot KV positions), all sharing ONE expert cache. The
request lifecycle (admission, streaming, retirement) lives in
repro.serving.scheduler; the engine exposes the batch-state primitives it
needs: ``init_slots`` / ``prefill_request`` / ``write_slot`` /
``decode_batch`` / ``select_tokens``. Sampling is per-request: there is no
engine-wide greedy/temperature knob — ``select_tokens`` is a vectorized
per-slot sampler driven by a ``[T]`` :class:`SamplingParams` batch.

Counters are typed: :attr:`stats` snapshots an immutable
:class:`~repro.serving.stats.EngineStats` with separate demand, prefetch
and prefill channels plus per-layer series, consumed by the fig5/fig6
benchmarks in live-model mode, benchmarks/decode_prefetch, and the
examples.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import CacheConfig, ModelConfig
from repro.core import collaborative as collab
from repro.obs.trace import NULL_RECORDER, now_ns
from repro.models import transformer
from repro.models import attention as attn
from repro.models.layers import rmsnorm
from repro.models.moe import route
from .kv_pool import KVPagePool, PageTable
from .sampling import GREEDY, SamplingParams, batch_arrays, fold_keys, \
    sample_tokens
from .stats import EngineStats

Params = Dict[str, Any]


@dataclass(frozen=True)
class EngineConfig:
    """Engine geometry and pipeline toggles.

    Sampling is deliberately NOT here: it is a per-request property
    (:class:`~repro.serving.sampling.SamplingParams` on ``Request``), not
    an engine property.
    """
    cache: CacheConfig
    max_batch: int = 1            # concurrent request slots (T)
    capacity: int = 512           # KV capacity
    prefetch: bool = False        # cross-layer speculative expert prefetch
    prefetch_min_prob: float = 0.0  # confidence gate on reservations
    prefill_chunk: int = 8        # cache-warming prefill chunk (0 = bypass)
    # overlapped admission: a newly admitted request advances its
    # cache-warming replay by at most this many chunks per scheduler tick
    # BETWEEN decode steps (its slot sits in the PREFILLING phase until
    # the replay drains), so established requests keep decoding while the
    # newcomer warms. 0 = synchronous admission (the whole replay runs on
    # the admission tick — head-of-line blocking on long prompts).
    admit_chunks_per_tick: int = 0
    # segment-streamed prefill: forward the prompt in this-many-token
    # segments, one advance_prefill call each, instead of one full-prompt
    # forward on the admission tick (0 = one-shot). Each segment appends
    # its own KV and warms the expert cache from its own routing trace in
    # the same jitted step; prefill_chunk degrades to an on/off warming
    # toggle here (the warm granularity IS the segment).
    prefill_segment: int = 0
    # live host execution (repro.hostexec): compute cache-miss experts on
    # the CPU when the cost model favors it over the weight fetch
    host_compute: bool = False
    host_threads: int = 8         # executor pool / cost-model thread count
    host_backend: str = "jax"     # "jax" (in-graph, bit-exact) | "callback"
    # batch small same-step CPU-miss groups (<= this many valid tokens)
    # into one stacked numpy matmul instead of one pool task each
    host_fuse_small: int = 4
    # paged KV: one global [num_pages, page_size, ...] pool per layer
    # replaces the dense [max_batch, capacity, ...] per-slot cache;
    # requests hold refcounted pages through per-slot page tables, and
    # admission reuses an existing request's pages for a shared prompt
    # prefix (copy-on-write on divergence). Bit-identical tokens to the
    # dense cache by construction.
    kv_paged: bool = False
    page_size: int = 16           # tokens per KV page
    kv_pages: Optional[int] = None  # pool size (None = dense-equivalent)
    # paged KV: when a retiring request drops the last reference on
    # prefix-indexed pages, park up to this many in the pool's eviction
    # LRU instead of freeing them — a later admission with the same
    # prompt prefix adopts them back (0 = free eagerly)
    prefix_keep_pages: int = 0
    # rank speculative-prefetch reservations by cross-batch vote count so
    # experts many rows predict claim cache ways first
    prefetch_rank_votes: bool = True

    def __post_init__(self):
        if self.prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk must be >= 0, got {self.prefill_chunk}")
        if self.admit_chunks_per_tick < 0:
            raise ValueError(
                f"admit_chunks_per_tick must be >= 0, got "
                f"{self.admit_chunks_per_tick}")
        if self.prefill_segment < 0:
            raise ValueError(
                f"prefill_segment must be >= 0, got {self.prefill_segment}")
        if self.prefix_keep_pages < 0:
            raise ValueError(
                f"prefix_keep_pages must be >= 0, got "
                f"{self.prefix_keep_pages}")
        if self.prefix_keep_pages > 0 and not self.kv_paged:
            raise ValueError(
                "prefix_keep_pages retains pool pages: it requires kv_paged")
        if not 0.0 <= self.prefetch_min_prob < 1.0:
            raise ValueError(
                f"prefetch_min_prob must be in [0, 1), got "
                f"{self.prefetch_min_prob}")
        if self.host_threads < 1:
            raise ValueError(
                f"host_threads must be >= 1, got {self.host_threads}")
        if self.host_backend not in ("jax", "callback"):
            raise ValueError(
                f"host_backend must be 'jax' or 'callback', got "
                f"{self.host_backend!r}")
        if self.host_fuse_small < 0:
            raise ValueError(
                f"host_fuse_small must be >= 0, got {self.host_fuse_small}")
        if self.page_size < 1:
            raise ValueError(
                f"page_size must be >= 1, got {self.page_size}")
        if self.kv_paged:
            if self.capacity % self.page_size != 0:
                raise ValueError(
                    f"paged KV needs capacity ({self.capacity}) divisible "
                    f"by page_size ({self.page_size})")
            min_pages = self.capacity // self.page_size
            if self.kv_pages is not None and self.kv_pages < min_pages:
                raise ValueError(
                    f"kv_pages ({self.kv_pages}) < capacity/page_size "
                    f"({min_pages}): one full-capacity request could "
                    f"never hold its pages")


@dataclass(eq=False)
class PrefillTicket:
    """Resumable cache-warming prefill for ONE request (identity
    semantics: a generated ``__eq__`` over the held device arrays would
    raise, like Request's ndarray prompt).

    Produced by :meth:`CollaborativeEngine.start_prefill`. On the
    trace-replay path the shared prefill trace already ran (so ``logits``
    and ``state`` are final — sampling the first token never waits on
    warming) and the ticket holds the routing trace padded to whole
    chunks plus the replay cursor. On the segment-streamed path
    (``seg > 0``) NO forward has run yet: ``logits`` stays ``None`` — the
    scheduler's discriminator for deferred first-token sampling — and the
    cursor counts forwarded segments instead; ``logits`` lands with the
    last segment. :meth:`CollaborativeEngine.advance_prefill` drives
    either — the scheduler interleaves one ticket advance per tick
    between decode steps so established requests keep decoding while the
    newcomer warms."""
    prompt_len: int
    chunk: int                    # warm-chunk token count (0 = bypass)
    n_chunks: int
    logits: Optional[jax.Array] = None  # [1, 1, V] first-token logits
    state: Optional[Params] = None      # decode state, pos = prompt_len
    top_i: Optional[jax.Array] = None   # [L, n_chunks*chunk, K]
    top_w: Optional[jax.Array] = None
    h2: Optional[jax.Array] = None      # [L, n_chunks*chunk, D]
    cursor: int = 0               # chunks already replayed
    # segment-streamed prefill (seg > 0): segment token count, the first
    # absolute position the forward starts at (past a shared prefix),
    # the prompt padded to whole segments [1, fwd_start + n_chunks*seg],
    # whether the KV streams straight into the pool pages (paged) and
    # whether each segment also warms the expert cache from its trace
    seg: int = 0
    fwd_start: int = 0
    tokens: Optional[np.ndarray] = None
    page_ids: Optional[np.ndarray] = None  # [max_pages], num_pages-padded
    kv_streamed: bool = False
    warm: bool = True
    # paged KV: the request's page table (allocated at start_prefill,
    # bound to a slot by bind_slot), its prompt (for the pool's prefix
    # index) and the token count served from a shared prefix — those
    # chunks' warm replay is skipped (cursor starts past them: the
    # prefix's original admission already warmed the cache with the
    # identical routing)
    table: Optional[PageTable] = None
    prompt: Optional[np.ndarray] = None
    shared_tokens: int = 0

    @property
    def done(self) -> bool:
        return self.cursor >= self.n_chunks

    @property
    def remaining(self) -> int:
        return self.n_chunks - self.cursor


def _one_prompt(prompt) -> np.ndarray:
    """Normalize a single request's prompt to [1, P]; reject batches (a
    [B, P] batch would otherwise silently concatenate into one prompt)."""
    prompt = np.asarray(prompt, np.int32)
    if prompt.ndim == 2 and prompt.shape[0] == 1:
        prompt = prompt[0]
    if prompt.ndim != 1:
        raise ValueError(
            f"per-request prefill serves ONE prompt: expected shape [P] or "
            f"[1, P], got {prompt.shape}; use engine.prefill / generate "
            f"for static batches")
    return prompt.reshape(1, -1)


class CollaborativeEngine:
    """Single-host engine (the paper's consumer scenario, batched).

    Only homogeneous decoder-only MoE archs (every layer MoE) are accepted
    here — matching the paper's Mixtral/Phi targets. The generic serving
    path without the cache lives in launch/serve.py for all archs.
    """

    def __init__(self, cfg: ModelConfig, params: Params, ecfg: EngineConfig,
                 key=None, recorder=None):
        assert cfg.moe is not None and cfg.moe_every == 1 and not cfg.is_encdec
        slots, G, R = transformer.build_slots(cfg)
        assert len(slots) == 1 and R == 0, "engine expects homogeneous stacks"
        self.cfg, self.ecfg = cfg, ecfg
        self.params = params
        # trace recorder (repro.obs): the no-op twin when tracing is off,
        # so the instrumented path is identical either way. All emission
        # happens in the _obs_* drain helpers — never inside jitted code
        # or between a dispatch and its drain (reprolint RL007).
        self.obs = recorder if recorder is not None else NULL_RECORDER
        # last-seen cumulative pool/executor counters, so the drain
        # helpers can emit per-step deltas as instants
        self._obs_prev: Dict[str, int] = {}
        key = key if key is not None else jax.random.PRNGKey(0)

        # Split expert weights out of the param tree into the two tiers.
        # The host tier is read-only and IS the param tree's expert table
        # (see _tiers) — it is deliberately NOT donated (donating it would
        # delete the params' buffers under prefill's feet); only the
        # mutable fast-tier state (slot buffers + tags/age) threads
        # through with donation.
        moe_p = params["scan"]["s0"]["moe"]
        tiers = collab.init_tiers(
            moe_p["w1"], moe_p["w3"], moe_p["w2"], ecfg.cache,
            num_experts=cfg.moe.num_experts, key=key)
        self.fast = (tiers.slot_w1, tiers.slot_w3, tiers.slot_w2, tiers.state)

        # live host execution: cost-model split table + (callback backend)
        # the multithreaded numpy executor over the host expert table
        self.host_executor = None
        self.dispatch_policy = None
        self._dispatch_execute = None
        self._cpu_table = None
        if ecfg.host_compute:
            from repro import hostexec
            self._dispatch_execute = hostexec.dispatch_execute
            self.dispatch_policy = hostexec.HostDispatchPolicy(
                hostexec.timings_for(cfg.name), ecfg.host_threads)
            table = self.dispatch_policy.decision_table(
                ecfg.max_batch * cfg.moe.top_k)
            self._cpu_table = jnp.asarray(table)
            if ecfg.host_backend == "callback" and table.any():
                # an all-False table can never dispatch: skip the executor
                # so the step pays no per-layer host round-trip for nothing
                # (the in-graph path is the exact no-op)
                self.host_executor = hostexec.HostExpertExecutor(
                    moe_p["w1"], moe_p["w3"], moe_p["w2"],
                    threads=ecfg.host_threads,
                    fuse_small=ecfg.host_fuse_small)

        # paged KV geometry (kv_paged only): the pool and per-slot page
        # tables are host-side bookkeeping created by init_slots; the
        # device-side page pool rides the scan state exactly where the
        # dense cache did
        self.max_pages = ecfg.capacity // ecfg.page_size
        self.num_pages = (ecfg.kv_pages if ecfg.kv_pages is not None
                          else ecfg.max_batch * self.max_pages)
        self.kv_pool: Optional[KVPagePool] = None
        self._slot_tables = [None] * ecfg.max_batch
        self._slot_pages: Optional[np.ndarray] = None

        # every jitted step takes the params as its first argument: a
        # closed-over param tree would be baked into the program as
        # constants (gigabytes of HLO at published widths)
        self._decode = jax.jit(self._decode_step, donate_argnums=(2, 3))
        self._write = jax.jit(self._write_slot, donate_argnums=(0,))
        self._write_paged = jax.jit(self._write_slot_paged,
                                    donate_argnums=(0,))
        self._cow = jax.jit(self._copy_page, donate_argnums=(0,))
        self._prefill = jax.jit(self._prefill_trace,
                                static_argnames=("want_trace",))
        self._warm = jax.jit(self._warm_chunk, donate_argnums=(1,))
        self._segment = jax.jit(self._segment_step, donate_argnums=(2, 3),
                                static_argnames=("warm",))
        L = cfg.num_layers
        self._counters = {
            "hits": 0, "accesses": 0, "host_assignments": 0,
            "fetched_experts": 0, "tokens": 0, "steps": 0,
            "prefetch_issued": 0, "prefetch_hits": 0, "prefetch_wasted": 0,
            "predicted": 0, "predicted_correct": 0,
            "prefill_hits": 0, "prefill_accesses": 0, "prefill_fetched": 0,
            "prefill_tokens": 0, "prefill_chunks": 0, "first_tokens": 0,
            "prefill_segments": 0, "prefix_tokens_skipped": 0,
            "cpu_expert_calls": 0, "cpu_tokens": 0, "miss_expert_groups": 0,
            "fused_groups": 0, "kv_pages_in_use": 0, "prefix_hits": 0,
            "cow_forks": 0, "prefix_pages_retained": 0}
        self._per_layer_hits = np.zeros(L, np.int64)
        self._per_layer_accesses = np.zeros(L, np.int64)

    # -- typed stats -------------------------------------------------------
    @property
    def stats(self) -> EngineStats:
        """Immutable snapshot of the engine counters (typed; derived rates
        and the per-layer hit-rate array live on EngineStats). The paged-KV
        channel reads the pool directly: ``kv_pages_in_use`` is a gauge,
        ``prefix_hits`` / ``cow_forks`` the pool's cumulative ledger."""
        c = dict(self._counters)
        if self.kv_pool is not None:
            c["kv_pages_in_use"] = self.kv_pool.pages_in_use
            c["prefix_hits"] = self.kv_pool.prefix_hits
            c["cow_forks"] = self.kv_pool.cow_forks
            c["prefix_pages_retained"] = self.kv_pool.prefix_pages_retained
        if self.host_executor is not None:
            # the executor's pool-census channel: best-effort floors (the
            # pure_callback lane may re-invoke), surfaced so the artifact
            # schema carries them — see test_bench_schema.py pins
            c["census_calls"] = self.host_executor.census_calls
            c["census_threads"] = self.host_executor.census_threads
            c["affinity_hits"] = self.host_executor.affinity_hits
            c["host_busy_us"] = self.host_executor.busy_ns // 1000
            c["host_queue_peak"] = self.host_executor.queue_peak
        return EngineStats(
            per_layer_hits=tuple(int(x) for x in self._per_layer_hits),
            per_layer_accesses=tuple(int(x) for x in self._per_layer_accesses),
            **c)

    @staticmethod
    def _tiers(params, fast) -> collab.ExpertTiers:
        s1, s3, s2, state = fast
        moe_p = params["scan"]["s0"]["moe"]
        return collab.ExpertTiers(host_w1=moe_p["w1"], host_w3=moe_p["w3"],
                                  host_w2=moe_p["w2"],
                                  slot_w1=s1, slot_w3=s3, slot_w2=s2,
                                  state=state)

    # -- one decode step with the staged collaborative pipeline -----------
    def _decode_step(self, params, tokens, state, fast, active,
                     pages=None):
        """tokens [T, 1]; state['pos'] [T] per-slot positions; active [T]
        bool — padded slots neither touch the shared cache nor the stats;
        pages [T, max_pages] int32 per-slot physical page ids (paged KV
        only; rows padded with num_pages — attention drops their writes).

        The layer scan is a software pipeline: each iteration probes /
        executes / commits layer *l*'s MoE, then (``prefetch`` enabled)
        predicts layer *l+1*'s picks from layer *l*'s output and issues
        reservations + weight streams so the next probe finds them
        resident. The prediction and the issued-fetch set ride the scan
        carry one iteration so accuracy and wasted fetches are scored
        against the *actual* next-layer routing.

        Each stage runs under a ``jax.named_scope`` (``embed``, ``attn``,
        ``router``, ``moe_prefetch``, ``lm_head`` here; the MoE stages name
        theirs in ``core/collaborative.py``), so the device trace can sum
        a step's time by stage."""
        cfg = self.cfg
        ccfg = self.ecfg.cache
        tiers = self._tiers(params, fast)
        with jax.named_scope("embed"):
            x = transformer._embed_inputs(params, {"tokens": tokens}, cfg)
        pos = state["pos"]
        slots, _, _ = transformer.build_slots(cfg)
        slot = slots[0]
        T, K = tokens.shape[0], cfg.moe.top_k
        E = cfg.moe.num_experts
        NG = min(T * K, E + 1)             # dispatch groups per layer

        scan_p = params["scan"]["s0"]
        xs = {"params": scan_p, "state": state["scan"]["s0"]}
        if self.ecfg.prefetch:
            # next layer's ln2 + router, aligned to the current iteration:
            # at layer l the pipeline runs router[l+1] on this layer's
            # output (the pre-gating approximation of layer l+1's true
            # router input). The wrapped last entry is masked via has_next
            # — the next token's layer-0 input is unknowable before
            # sampling. Only the prefetch build pays for the rolled
            # weight-table duplicates.
            with jax.named_scope("moe_prefetch"):
                xs.update(
                    ln2_next=jnp.roll(scan_p["ln2"], -1, axis=0),
                    router_next=jnp.roll(scan_p["moe"]["router"], -1,
                                         axis=0),
                    has_next=jnp.arange(cfg.num_layers) < cfg.num_layers - 1)

        def body(carry, xs):
            x, tiers, layer, pred_prev, rep_prev, issued_prev = carry
            lp, st = xs["params"], xs["state"]
            with jax.named_scope("attn"):
                h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
                if self.ecfg.kv_paged:
                    o, new_st = attn.decode_attention_paged(
                        lp["attn"], h, st, pos, pages, cfg, slot.window,
                        active=active)
                else:
                    o, new_st = attn.decode_attention(
                        lp["attn"], h, st, pos, cfg, slot.window)
                x = x + o
            with jax.named_scope("router"):
                h2 = rmsnorm(lp["ln2"], x, cfg.norm_eps)
                _, top_i, top_w = route(lp["moe"]["router"],
                                        h2[:, 0].astype(jnp.float32), K)

            # staged collaborative MoE: probe -> dispatch/execute -> commit
            pr = collab.probe(tiers, layer, top_i, ccfg, active=active)
            if self.ecfg.host_compute:
                # hybrid dispatcher (repro.hostexec): GPU-hit groups run
                # the grouped kernels, CPU-miss groups the host executor,
                # cost-model-chosen; cache warming identical either way
                y, w, dstats = self._dispatch_execute(
                    tiers, layer, h2[:, 0], top_w, pr, ccfg,
                    self._cpu_table, self.host_executor,
                    self.ecfg.host_fuse_small)
            else:
                y, w = collab.execute(tiers, layer, h2[:, 0], top_w, pr,
                                      ccfg)
                dstats = {"cpu_expert_calls": jnp.zeros((), jnp.int32),
                          "cpu_tokens": jnp.zeros((), jnp.int32),
                          "miss_expert_groups": jnp.zeros((), jnp.int32),
                          "fused_groups": jnp.zeros((), jnp.int32)}
            tiers, fetch = collab.commit(tiers, layer, pr, w, ccfg)
            x = x + y[:, None].astype(x.dtype)

            if self.ecfg.prefetch:
                with jax.named_scope("moe_prefetch"):
                    # score the prediction the previous iteration made for
                    # THIS layer: accuracy per predicted assignment, and
                    # issued fetches whose expert the layer never demanded
                    pred_valid = (pred_prev >= 0) & active[:, None]
                    pred_ok = (pred_prev[:, :, None]
                               == top_i[:, None, :]).any(-1)
                    demanded = (rep_prev[:, None]
                                == pr.flat_e[None, :]).any(-1)
                    wasted = (issued_prev & ~demanded).sum()
                    predicted = pred_valid.sum()
                    pred_correct = (pred_ok & pred_valid).sum()
                    # speculative prefetch for layer l+1 (reservations +
                    # streams; invisible until the next probe lands them).
                    # Pre-gating prediction: layer l+1's router on layer l's
                    # OUTPUT residual (its true input one attention block
                    # later) — the DAOP-style one-layer lookahead; the
                    # reservation's transfer hides under layer l+1's
                    # attention
                    h_pred = rmsnorm(xs["ln2_next"], x, cfg.norm_eps)
                    pred_p, pred_i, _ = route(
                        xs["router_next"], h_pred[:, 0].astype(jnp.float32),
                        K)
                    gate = xs["has_next"] & active[:, None]
                    if self.ecfg.prefetch_min_prob > 0.0:
                        # confidence gate: only reserve picks whose router
                        # probability clears the threshold — mispredictions
                        # are the only source of cache pollution, and low-
                        # confidence picks are where they live
                        p_pick = jnp.take_along_axis(pred_p, pred_i, axis=1)
                        gate = gate & (p_pick >= self.ecfg.prefetch_min_prob)
                    pred_i = jnp.where(gate, pred_i, -1).astype(jnp.int32)
                tiers, rep_p, issued, n_issued = collab.prefetch(
                    tiers, layer + 1, pred_i, ccfg, active=active,
                    rank_votes=self.ecfg.prefetch_rank_votes)
            else:
                # prefetch disabled: no rolled weight tables, no scoring —
                # only constant-zero counters so the stats shape is stable
                pred_i = jnp.full((T, K), -1, jnp.int32)
                rep_p = jnp.full((NG,), -1, jnp.int32)
                issued = jnp.zeros((NG,), bool)
                n_issued = wasted = jnp.zeros((), jnp.int32)
                predicted = pred_correct = jnp.zeros((), jnp.int32)

            stats = {
                **collab._stats(pr, fetch),
                **dstats,
                "prefetch_issued": n_issued,
                "prefetch_wasted": wasted,
                "predicted": predicted,
                "predicted_correct": pred_correct,
            }
            return (x, tiers, layer + 1, pred_i, rep_p, issued), \
                (new_st, stats)

        carry0 = (x, tiers, jnp.zeros((), jnp.int32),
                  jnp.full((T, K), -1, jnp.int32),
                  jnp.full((NG,), -1, jnp.int32), jnp.zeros((NG,), bool))
        (x, tiers, _, _, _, _), (new_scan, stats) = jax.lax.scan(
            body, carry0, xs)
        with jax.named_scope("lm_head"):
            x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
            logits = transformer.lm_logits(params, x, cfg)
        new_state = {"scan": {"s0": new_scan},
                     "pos": pos + active.astype(jnp.int32)}
        new_fast = (tiers.slot_w1, tiers.slot_w3, tiers.slot_w2, tiers.state)
        return logits, new_state, new_fast, stats

    def lower_decode(self, params, state: Params, fast) -> jax.stages.Lowered:
        """The jitted decode step lowered, not run, for ``params``, the slot
        ``state`` and the expert tiers ``fast`` — arrays or
        ``jax.ShapeDtypeStruct``s — at this engine's slot geometry, with
        the token, mask and page-table arguments placed like ``params``'
        first leaf. Its compiled text names every instruction with the
        stage scope it came from, which a device trace of the step lacks."""
        T = self.ecfg.max_batch
        where = jax.tree.leaves(params)[0].sharding

        def arg(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=where)

        pages = (arg((T, self.max_pages), jnp.int32)
                 if self.ecfg.kv_paged else None)
        return self._decode.lower(params, arg((T, 1), jnp.int32), state,
                                  fast, arg((T,), jnp.bool_), pages)

    # -- batch-state primitives for the scheduler -------------------------
    def init_slots(self) -> Params:
        """Empty decode state for max_batch request slots.

        Paged KV: the per-layer KV leaves become the global page pool
        ``[num_pages, page_size, Hk, hd]`` (pages play the dense cache's
        batch role, so the backbone's init_state builds them unchanged)
        and a fresh :class:`KVPagePool` takes over the host-side page
        bookkeeping — any previously bound tables are dropped with it."""
        if self.ecfg.kv_paged:
            state = transformer.init_state(self.cfg, self.num_pages,
                                           self.ecfg.page_size)
            self.kv_pool = KVPagePool(
                self.num_pages, self.ecfg.page_size,
                prefix_keep_pages=self.ecfg.prefix_keep_pages)
            self._slot_tables = [None] * self.ecfg.max_batch
            self._slot_pages = np.full(
                (self.ecfg.max_batch, self.max_pages), self.num_pages,
                np.int32)
        else:
            state = transformer.init_state(self.cfg, self.ecfg.max_batch,
                                           self.ecfg.capacity)
        state["pos"] = jnp.zeros((self.ecfg.max_batch,), jnp.int32)
        return state

    @staticmethod
    def _write_slot(batch_state, one_state, slot):
        """Scatter a single prefilled request's state into batch slot
        ``slot`` (scan leaves are [G, B, ...]; the incoming state is B=1)."""
        new_scan = jax.tree.map(lambda full, one: full.at[:, slot].set(one[:, 0]),
                                batch_state["scan"], one_state["scan"])
        pos = batch_state["pos"].at[slot].set(one_state["pos"])
        return {"scan": new_scan, "pos": pos}

    def write_slot(self, batch_state: Params, one_state: Params,
                   slot: int) -> Params:
        return self._write(batch_state, one_state, jnp.asarray(slot, jnp.int32))

    def _write_slot_paged(self, batch_state, one_state, page_ids,
                          write_mask, slot):
        """Scatter one prefilled request's dense [1, capacity, ...] KV
        into its pool pages. page_ids [max_pages] physical pages (padded
        with num_pages); write_mask [max_pages] — False rows (padding AND
        shared-prefix pages, whose content the prefix's original request
        already wrote) are dropped, so a shared page is never rewritten
        while other requests read it."""
        ps = self.ecfg.page_size
        dst = jnp.where(write_mask, page_ids, self.num_pages)

        def scatter(pool, one):
            L = pool.shape[0]
            chunks = one[:, 0].reshape((L, self.max_pages, ps)
                                       + one.shape[3:])
            return pool.at[:, dst].set(chunks, mode="drop")

        new_scan = jax.tree.map(scatter, batch_state["scan"],
                                one_state["scan"])
        pos = batch_state["pos"].at[slot].set(one_state["pos"])
        return {"scan": new_scan, "pos": pos}

    @staticmethod
    def _copy_page(batch_state, src, dst):
        """Copy-on-write page duplication: clone physical page ``src``
        into ``dst`` across every layer's K and V pools."""
        new_scan = jax.tree.map(lambda pool: pool.at[:, dst].set(pool[:, src]),
                                batch_state["scan"])
        return {"scan": new_scan, "pos": batch_state["pos"]}

    # -- paged slot lifecycle (scheduler-facing) ---------------------------
    def can_admit(self, prompt, max_new_tokens: int) -> bool:
        """Page-pool admission gate: True iff the pool can commit pages
        for the prompt plus ``max_new_tokens`` decode appends right now
        (shared-prefix pages excluded from the requirement). Dense KV has
        per-slot storage by construction — always True."""
        if not self.ecfg.kv_paged or self.kv_pool is None:
            return True
        p = _one_prompt(prompt)[0]
        return self.kv_pool.can_admit(p, p.shape[0] + int(max_new_tokens))

    def bind_slot(self, batch_state: Params, ticket: "PrefillTicket",
                  slot: int) -> Params:
        """Bind a finished prefill to batch slot ``slot``: the paged twin
        of :meth:`write_slot` (which it falls back to for dense KV).
        Scatters the ticket's KV into the table's non-shared pages and
        registers the prompt's full-page prefixes in the pool's prefix
        index — AFTER the write, so the index only ever maps populated
        pages."""
        if not self.ecfg.kv_paged:
            return self.write_slot(batch_state, ticket.state, slot)
        table = ticket.table
        assert table is not None and ticket.prompt is not None, \
            "paged ticket lost its page table (start_prefill not paged?)"
        if ticket.kv_streamed:
            # segment-streamed admission already wrote every segment's KV
            # straight into the pool pages — nothing to scatter, only the
            # slot bookkeeping and the prefix registration remain
            if ticket.logits is None:
                raise RuntimeError(
                    "segment-streamed ticket not drained: advance_prefill "
                    "to done before bind_slot")
            self._slot_tables[slot] = table
            self._slot_pages[slot] = ticket.page_ids
            pos = batch_state["pos"].at[slot].set(ticket.prompt_len)
            self.kv_pool.register(ticket.prompt, table)
            return {"scan": batch_state["scan"], "pos": pos}
        n = len(table.pages)
        ids = np.full((self.max_pages,), self.num_pages, np.int32)
        ids[:n] = table.pages
        mask = np.zeros((self.max_pages,), bool)
        mask[ticket.shared_tokens // self.ecfg.page_size:n] = True
        self._slot_tables[slot] = table
        self._slot_pages[slot] = ids
        state = self._write_paged(batch_state, ticket.state,
                                  jnp.asarray(ids), jnp.asarray(mask),
                                  jnp.asarray(slot, jnp.int32))
        self.kv_pool.register(ticket.prompt, table)
        return state

    def claim_slot(self, ticket: "PrefillTicket", slot: int) -> None:
        """Pre-bind a segment-streamed ticket's page table to the slot it
        will occupy, BEFORE the stream drains — so a cancellation mid-
        stream releases the pages through the ordinary
        :meth:`release_slot` path. Decode never reads the slot while it
        is PREFILLING (inactive rows' writes drop), so exposing the page
        ids early is safe. Dense KV: nothing to claim."""
        if not self.ecfg.kv_paged or ticket.table is None:
            return
        self._slot_tables[slot] = ticket.table
        self._slot_pages[slot] = ticket.page_ids

    def release_slot(self, slot: int) -> None:
        """Return a retired/cancelled slot's pages to the pool
        (refcount-aware: pages a prefix-sharing peer still holds stay
        allocated). Dense KV: no-op — the slot's rows are overwritten on
        reuse."""
        if not self.ecfg.kv_paged:
            return
        table = self._slot_tables[slot]
        if table is not None:
            self.kv_pool.free(table)
            self._slot_tables[slot] = None
            self._slot_pages[slot] = self.num_pages

    def abort_ticket(self, ticket: "PrefillTicket") -> None:
        """Release an open ticket's page table after a failed admission —
        the exception-path twin of :meth:`bind_slot`. Idempotent and
        double-free safe: the ticket's table is taken exactly once, any
        slot already claiming it (a segment-streamed admission claims
        before draining) is unbound first, and dense tickets are a
        no-op."""
        table, ticket.table = ticket.table, None
        if table is None or self.kv_pool is None:
            return
        for i, t in enumerate(self._slot_tables):
            if t is table:
                self._slot_tables[i] = None
                self._slot_pages[i] = self.num_pages
        self.kv_pool.free(table)

    def fork_slot(self, batch_state: Params, src: int, dst: int,
                  total_tokens: int) -> Params:
        """Clone slot ``src``'s sequence into free slot ``dst`` sharing
        ALL its KV pages (zero KV copied now; the partial last page is
        copy-on-written by whichever side appends first). total_tokens
        bounds the child's final length for page commitment."""
        if not self.ecfg.kv_paged:
            raise RuntimeError("fork_slot requires EngineConfig.kv_paged")
        parent = self._slot_tables[src]
        if parent is None:
            raise ValueError(f"slot {src} holds no page table")
        child = self.kv_pool.fork(parent, int(total_tokens))
        self._slot_tables[dst] = child
        ids = np.full((self.max_pages,), self.num_pages, np.int32)
        ids[:len(child.pages)] = child.pages
        self._slot_pages[dst] = ids
        pos = batch_state["pos"].at[dst].set(batch_state["pos"][src])
        return {"scan": batch_state["scan"], "pos": pos}

    # -- prefill: one shared trace, two cache modes ------------------------
    def _prefill_trace(self, params, tokens, plen, want_trace: bool = False):
        """Full-prompt forward: the backbone's prefill mode, directly.

        tokens [B, capacity] (prompt left-aligned, zero-padded); plen —
        traced scalar count of real prompt tokens. There is ONE prefill
        implementation: ``transformer.backbone(mode="prefill")``, whose
        ``want_trace`` flag additionally emits the per-layer routing
        trace the cache-warming replay consumes (the bypass path skips
        the O(L*S*D) trace materialization entirely). First-token logits
        are read at position ``plen - 1`` — the last *real* prompt token
        (pad positions are causally masked out of every real position's
        attention).

        Returns (logits [B, 1, V], decode state with pos=plen,
        trace {top_i [L, B, S, K], top_w [L, B, S, K], h2 [L, B, S, D]}
        — or None without ``want_trace``).
        """
        cfg = self.cfg
        x, state, _, trace = transformer.backbone(
            params, {"tokens": tokens}, cfg, "prefill", remat=False,
            want_trace=want_trace)
        h_last = jax.lax.dynamic_slice_in_dim(x, plen - 1, 1, axis=1)
        logits = transformer.lm_logits(params, h_last, cfg)
        state = {"scan": state["scan"], "pos": jnp.asarray(plen, jnp.int32)}
        # homogeneous stack: the one scanned slot's trace IS the engine's
        # [L, B, S, ...] routing trace
        trace = trace["scan"]["s0"] if want_trace else None
        return logits, state, trace

    def _padded_prefill(self, tokens, want_trace: bool = False):
        """Validate, pad to capacity and run the prefill trace.
        tokens [B, P] -> (logits [B, 1, V], state, routing trace|None)."""
        tokens = jnp.asarray(tokens, jnp.int32)
        B, P = tokens.shape
        cap = self.ecfg.capacity
        if not 1 <= P < cap:
            raise ValueError(
                f"prompt length {P} outside [1, capacity={cap}) — decode "
                f"needs at least one free KV slot")
        pad = jnp.zeros((B, cap - P), tokens.dtype)
        return self._prefill(self.params,
                             jnp.concatenate([tokens, pad], 1),
                             jnp.asarray(P, jnp.int32),
                             want_trace=want_trace)

    def prefill(self, tokens: jax.Array) -> Tuple[jax.Array, Params]:
        """Bypass prefill (tiers untouched: the cache stays cold until
        decode). tokens [B, P] -> (last-real-position logits [B, 1, V],
        decode state with pos=P)."""
        self._require_dense("prefill")
        logits, state, _ = self._padded_prefill(tokens)
        return logits, state

    def _require_dense(self, what: str) -> None:
        """The static-batch convenience paths produce dense-shaped states
        with no page-table bookkeeping — under kv_paged they would leak
        pages or decode against the wrong cache layout, so they refuse."""
        if self.ecfg.kv_paged:
            raise RuntimeError(
                f"{what}() is a dense-KV path; under EngineConfig.kv_paged "
                f"use the scheduler primitives (start_prefill / bind_slot "
                f"/ decode_batch / release_slot)")

    def _warm_chunk(self, params, fast, top_i, top_w, h2, active):
        """Route one prompt chunk through probe → execute → commit.

        top_i/top_w [L, C, K]; h2 [L, C, D]; active [C] (False = pad rows
        beyond the prompt). The chunk's C tokens play the role of the T
        decode rows: the probe's demand accesses and the commit's
        post-fetch warm the shared tiers exactly as a decode step would;
        execute's grouped FFN output has no consumer here (the hidden
        states come from the shared prefill trace, keeping chunked and
        bypass prefill bit-identical), so XLA prunes the matmuls and what
        remains is the pipeline's *data movement* — the per-unique-expert
        weight gathers and slot writes. Returns (fast, per-layer stats).
        """
        ccfg = self.ecfg.cache
        tiers = self._tiers(params, fast)

        def body(carry, xs):
            tiers, layer = carry
            pr = collab.probe(tiers, layer, xs["top_i"], ccfg, active=active)
            _, w = collab.execute(tiers, layer, xs["h2"], xs["top_w"], pr,
                                  ccfg)
            tiers, fetch = collab.commit(tiers, layer, pr, w, ccfg)
            return (tiers, layer + 1), collab._stats(pr, fetch)

        (tiers, _), stats = jax.lax.scan(
            body, (tiers, jnp.zeros((), jnp.int32)),
            {"top_i": top_i, "top_w": top_w, "h2": h2})
        new_fast = (tiers.slot_w1, tiers.slot_w3, tiers.slot_w2, tiers.state)
        return new_fast, stats

    def _segment_step(self, params, tokens, scan_state, fast, pos0, plen,
                      pages, wmin, warm: bool = True):
        """One C-token prompt segment, forward + warm fused.

        Runs the backbone's segment mode: the segment attends to the
        request's KV so far at absolute offset ``pos0`` (offset causal
        mask), appends its own KV — into the ticket's dense B=1 state
        (``pages is None``) or straight into the batch pool's pages with
        writes masked to ``[wmin, plen)`` so shared-prefix pages stay
        immutable — and (``warm``) routes its freshly emitted trace
        through probe → execute → commit. The forward IS the trace
        source: no separate replay pass, one jitted step per segment.

        First-token logits are read at ``plen - 1`` relative to the
        segment (clamped — only the LAST segment's read is meaningful;
        earlier segments' logits are overwritten by later calls).
        Returns (logits, new scan leaves, fast, new pos clamped to plen,
        warm stats | None). Pad rows past ``plen`` are computed but
        write-masked (paged) or overwritten by decode appends before any
        read (dense) — they never reach real rows through the causal
        mask, so segmentation never changes tokens."""
        cfg = self.cfg
        C = tokens.shape[1]
        state = {"scan": scan_state, "pos": pos0}
        x, new_state, _, trace = transformer.backbone(
            params, {"tokens": tokens}, cfg, "segment", state=state,
            remat=False, want_trace=warm, pages=pages,
            kv_write_min=wmin, kv_write_max=plen)
        rel = jnp.clip(plen - 1 - pos0, 0, C - 1)
        h_last = jax.lax.dynamic_slice_in_dim(x, rel, 1, axis=1)
        logits = transformer.lm_logits(params, h_last, cfg)
        wstats = None
        if warm:
            tr = trace["scan"]["s0"]
            active = (pos0 + jnp.arange(C)) < plen
            fast, wstats = self._warm_chunk(
                params, fast, tr["top_i"][:, 0], tr["top_w"][:, 0],
                tr["h2"][:, 0], active)
        new_pos = jnp.minimum(new_state["pos"], plen)
        return logits, new_state["scan"], fast, new_pos, wstats

    # -- resumable prefill: ticket primitives ------------------------------
    def start_prefill(self, prompt: np.ndarray,
                      chunk: Optional[int] = None,
                      max_total_tokens: Optional[int] = None
                      ) -> "PrefillTicket":
        """Run the shared prefill trace once and open a resumable
        cache-warming ticket.

        The returned :class:`PrefillTicket` carries the first-token
        logits, the request's decode state (pos=len(prompt)) and the
        prompt's routing trace padded to whole ``chunk``-token chunks,
        plus a chunk cursor. The caller drives the warming replay with
        :meth:`advance_prefill` — one call per scheduler tick for
        overlapped admission, or all at once for the synchronous path.
        With ``chunk == 0`` (bypass prefill) no trace is materialized and
        the ticket is born done.

        Paged KV: the pool allocates the request's page table here —
        committing pages up to ``max_total_tokens`` (prompt + decode
        budget; defaults to capacity) — and a prefix-index hit makes the
        new table share the matching request's full prompt-prefix pages.
        The warm replay skips the shared span's chunks (the prefix's
        original admission already routed those exact tokens through the
        cache). With ``EngineConfig.prefill_segment`` NO forward runs
        here at all: the ticket comes back with ``logits is None`` and
        :meth:`advance_prefill` streams the prompt forward one segment
        per call — on a prefix hit the shared span's forward AND warm are
        skipped outright (the stream starts past it). Raises
        :class:`~repro.serving.kv_pool.PoolExhausted` when the pool
        cannot commit the pages (gate with :meth:`can_admit` first); any
        error past the page allocation frees the table before the raise
        reaches the caller — a rejected admission never leaks pages."""
        chunk = self.ecfg.prefill_chunk if chunk is None else int(chunk)
        if chunk < 0:
            raise ValueError(f"chunk must be >= 0, got {chunk}")
        prompt = _one_prompt(prompt)
        P = prompt.shape[1]
        table, shared = None, 0
        if self.ecfg.kv_paged:
            if self.kv_pool is None:
                raise RuntimeError(
                    "paged KV: call init_slots() before start_prefill()")
            total = (self.ecfg.capacity if max_total_tokens is None
                     else int(max_total_tokens))
            table, shared = self.kv_pool.alloc_prompt(prompt[0], total)
        try:
            return self._open_ticket(prompt, chunk, table, shared)
        except BaseException:
            if table is not None:
                self.kv_pool.free(table)
            raise

    def _open_ticket(self, prompt: np.ndarray, chunk: int,
                     table: Optional[PageTable], shared: int
                     ) -> "PrefillTicket":
        """Build the ticket for an allocated admission (anything that
        raises from here is caught by start_prefill's page-release
        guard)."""
        P = prompt.shape[1]
        if self.ecfg.prefill_segment > 0:
            return self._start_segmented(prompt, table, shared,
                                         warm=chunk != 0)
        if chunk == 0:
            logits, state, _ = self._padded_prefill(prompt)
            return PrefillTicket(prompt_len=P, chunk=0, n_chunks=0,
                                 logits=logits, state=state,
                                 table=table, prompt=prompt[0],
                                 shared_tokens=shared)
        logits, state, trace = self._padded_prefill(prompt, want_trace=True)
        # fixed [L, chunk, ...] shapes: the warm step compiles once per
        # chunk size; only the chunk count varies with prompt length. The
        # trace stays device-resident on the ticket — no device->host
        # sync on the admission path.
        top_i = trace["top_i"][:, 0]                    # [L, S, K]
        top_w = trace["top_w"][:, 0]
        h2 = trace["h2"][:, 0]                          # [L, S, D]
        n_chunks = -(-P // chunk)
        pad_to = n_chunks * chunk
        if pad_to > top_i.shape[1]:
            ext = ((0, 0), (0, pad_to - top_i.shape[1]), (0, 0))
            top_i, top_w, h2 = (jnp.pad(a, ext) for a in (top_i, top_w, h2))
        return PrefillTicket(prompt_len=P, chunk=chunk, n_chunks=n_chunks,
                             logits=logits, state=state,
                             top_i=top_i, top_w=top_w, h2=h2,
                             cursor=min(shared // chunk, n_chunks),
                             table=table, prompt=prompt[0],
                             shared_tokens=shared)

    def _start_segmented(self, prompt: np.ndarray,
                         table: Optional[PageTable], shared: int,
                         warm: bool) -> "PrefillTicket":
        """Open a segment-streamed ticket: tokenize + cursor only, no
        forward. A prefix hit advances the stream's start past the
        shared span — ``fwd_start = min(shared, P - 1)`` keeps the LAST
        prompt token in the stream even when the whole prompt is shared
        (its recompute reads the shared pages, write-masked, and
        produces the first-token logits)."""
        P = prompt.shape[1]
        cap = self.ecfg.capacity
        if not 1 <= P < cap:
            raise ValueError(
                f"prompt length {P} outside [1, capacity={cap}) — decode "
                f"needs at least one free KV slot")
        seg = self.ecfg.prefill_segment
        fwd_start = min(shared, P - 1)
        n_seg = -(-(P - fwd_start) // seg)
        tok = np.zeros((1, fwd_start + n_seg * seg), np.int32)
        tok[:, :P] = prompt
        self._counters["prefix_tokens_skipped"] += fwd_start
        ticket = PrefillTicket(
            prompt_len=P, chunk=seg, n_chunks=n_seg,
            seg=seg, fwd_start=fwd_start, tokens=tok, warm=warm,
            table=table, prompt=prompt[0], shared_tokens=shared)
        if self.ecfg.kv_paged:
            ids = np.full((self.max_pages,), self.num_pages, np.int32)
            ids[:len(table.pages)] = table.pages
            ticket.page_ids = ids
            ticket.kv_streamed = True
        else:
            state = transformer.init_state(self.cfg, 1, cap)
            ticket.state = {"scan": state["scan"],
                            "pos": jnp.asarray(fwd_start, jnp.int32)}
        return ticket

    def advance_prefill(self, ticket: "PrefillTicket",
                        max_chunks: int = 1) -> bool:
        """Advance a ticket by up to ``max_chunks`` units. Trace-replay
        tickets: warm chunks through the staged probe/execute/commit
        pipeline, in prompt order — warming moves expert weights
        (shared-tier residency + the ``prefill_*`` stat channel) and
        never touches the ticket's logits/state, so decode tokens are
        bit-identical however the replay is paced. Segment-streamed
        tickets: prompt-forward segments (dense only on this signature —
        a paged stream writes the BATCH pool and must thread it through
        :meth:`advance_prefill_state`). Returns True when drained."""
        _, done = self.advance_prefill_state(ticket, None, max_chunks)
        return done

    def advance_prefill_state(self, ticket: "PrefillTicket",
                              batch_state: Optional[Params],
                              max_chunks: int = 1
                              ) -> Tuple[Optional[Params], bool]:
        """State-threading twin of :meth:`advance_prefill` for the
        scheduler: a paged segment-streamed ticket appends its KV into
        the batch pool leaves, so the batch state rides through and
        comes back rebuilt (other modes return it untouched). Returns
        (batch_state, done)."""
        chunk, P = ticket.chunk, ticket.prompt_len
        t0 = now_ns()
        if ticket.seg > 0:
            n = 0
            plen = jnp.asarray(P, jnp.int32)
            while ticket.cursor < ticket.n_chunks and n < max_chunks:
                s = ticket.fwd_start + ticket.cursor * ticket.seg
                tok = jnp.asarray(ticket.tokens[:, s:s + ticket.seg])
                pos0 = jnp.asarray(s, jnp.int32)
                if ticket.kv_streamed:
                    if batch_state is None:
                        raise RuntimeError(
                            "paged segment stream appends into the batch "
                            "pool: use advance_prefill_state(ticket, "
                            "batch_state)")
                    pages = jnp.asarray(ticket.page_ids[None])
                    wmin = jnp.asarray(ticket.shared_tokens, jnp.int32)
                    logits, new_scan, self.fast, _, wstats = self._segment(
                        self.params, tok, batch_state["scan"], self.fast,
                        pos0, plen, pages, wmin, warm=ticket.warm)
                    batch_state = {"scan": new_scan,
                                   "pos": batch_state["pos"]}
                else:
                    logits, new_scan, self.fast, new_pos, wstats = \
                        self._segment(self.params, tok,
                                      ticket.state["scan"], self.fast,
                                      pos0, plen, None, None,
                                      warm=ticket.warm)
                    ticket.state = {"scan": new_scan, "pos": new_pos}
                ticket.logits = logits
                if ticket.warm:
                    self._accumulate_prefill(
                        wstats, max(0, min(ticket.seg, P - s)))
                    self._counters["prefill_chunks"] += 1
                ticket.cursor += 1
                n += 1
            self._counters["prefill_segments"] += n
            self._obs_prefill(t0, n, ticket)
            return batch_state, ticket.done
        advanced = []
        while ticket.cursor < ticket.n_chunks and len(advanced) < max_chunks:
            s = ticket.cursor * chunk
            active = jnp.arange(s, s + chunk) < P
            self.fast, wstats = self._warm(
                self.params, self.fast, ticket.top_i[:, s:s + chunk],
                ticket.top_w[:, s:s + chunk], ticket.h2[:, s:s + chunk],
                active)
            advanced.append((wstats, min(chunk, P - s)))
            ticket.cursor += 1
        # stats convert after the mini-loop: a full synchronous drain pays
        # one device->host sync, the per-tick overlapped path one per tick
        for wstats, n_tok in advanced:
            self._accumulate_prefill(wstats, n_tok)
        self._counters["prefill_chunks"] += len(advanced)
        self._obs_prefill(t0, len(advanced), ticket)
        return batch_state, ticket.done

    def prefill_chunked(self, prompt: np.ndarray,
                        chunk: Optional[int] = None
                        ) -> Tuple[jax.Array, Params]:
        """Cache-warming chunked prefill (ROADMAP's long-prompt item).

        Runs the prompt through the shared prefill trace (bit-identical
        hidden states / KV / logits to :meth:`prefill`), then drains the
        whole warming replay synchronously — :meth:`start_prefill` +
        :meth:`advance_prefill` in one call. The warming accesses land in
        the separate ``prefill_*`` stat channel; decode-channel counters
        and generated tokens are untouched by construction (residency
        changes never change logits)."""
        self._require_dense("prefill_chunked")
        chunk = self.ecfg.prefill_chunk if chunk is None else int(chunk)
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        ticket = self.start_prefill(prompt, chunk)
        try:
            self.advance_prefill(ticket, ticket.n_chunks)
        except BaseException:
            self.abort_ticket(ticket)
            raise
        return ticket.logits, ticket.state

    def sample_first(self, ticket: "PrefillTicket",
                     sampling: SamplingParams = GREEDY, key=None) -> int:
        """Select a request's first token from its ticket's prefill
        logits under the request's own SamplingParams (``key``: the
        request's first-step PRNG key; required for non-greedy sampling).
        Counted in the ``first_tokens`` channel — prefill-sampled tokens
        are generated output, so token-based throughput must see them."""
        if ticket.logits is None:
            raise RuntimeError(
                "segment-streamed ticket has no logits yet: drain "
                "advance_prefill to done before sample_first")
        keys = None if key is None else np.asarray(key).reshape(1, 2)
        tok = int(np.asarray(
            self.select_tokens(ticket.logits[:, 0], [sampling], keys))[0])
        self._counters["first_tokens"] += 1
        return tok

    def prefill_request(self, prompt: np.ndarray,
                        sampling: SamplingParams = GREEDY,
                        key=None) -> Tuple[int, Params]:
        """Prefill one request synchronously; returns (first token, decode
        state with pos=len(prompt), B=1). Uses the cache-warming chunked
        path when ``EngineConfig.prefill_chunk > 0``, the cold bypass
        otherwise — the first token is identical either way. The
        overlapped-admission scheduler uses the underlying ticket
        primitives directly instead."""
        self._require_dense("prefill_request")
        ticket = self.start_prefill(prompt)
        try:
            self.advance_prefill(ticket, ticket.n_chunks)
            tok = self.sample_first(ticket, sampling, key)
        except BaseException:
            self.abort_ticket(ticket)
            raise
        return tok, ticket.state

    # -- vectorized per-slot sampling --------------------------------------
    def select_tokens(self, logits: jax.Array,
                      sampling: Union[None, SamplingParams,
                                      Sequence[SamplingParams]] = None,
                      keys=None) -> jax.Array:
        """Next-token selection from step logits [T, V], one
        SamplingParams per row (a scalar broadcasts; None = all greedy).
        keys [T, 2] uint32 — per-row step keys, required as soon as any
        row samples. Returns [T] int32."""
        T = logits.shape[0]
        if sampling is None:
            sampling = [GREEDY] * T
        elif isinstance(sampling, SamplingParams):
            sampling = [sampling] * T
        if len(sampling) != T:
            raise ValueError(f"params batch {len(sampling)} != rows {T}")
        greedy, temp, top_k, top_p = batch_arrays(sampling)
        if greedy.all():
            # the dominant path: skip the sampling graph (sorts, softmax,
            # discarded categorical draw) entirely
            return jnp.argmax(logits.astype(jnp.float32), -1) \
                .astype(jnp.int32)
        if keys is None:
            raise ValueError("non-greedy sampling needs per-row keys")
        return sample_tokens(logits, greedy, temp, top_k, top_p,
                             jnp.asarray(keys))

    def decode_batch(self, tokens, state: Params, active
                     ) -> Tuple[jax.Array, Params]:
        """One padded decode step for the whole slot batch. tokens [T, 1];
        active [T] bool. Updates the shared expert-cache tiers and the
        engine counters (padded rows excluded); returns (logits, state).

        Paged KV: before the step, every active slot's table plans this
        token's append — allocating a fresh page on a page boundary and
        copy-on-writing a partial last page another table still shares —
        and the (possibly updated) page-id rows ride into the jitted step;
        after the step the appends commit (the plan is idempotent, so a
        step that dies between plan and commit replans identically)."""
        # derive the host-side views (page planning, stats row count) from
        # the caller's host value BEFORE it becomes a device array — the
        # old order np.asarray(jnp.asarray(active)) round-tripped through
        # the device and blocked the decode loop twice per step
        t0 = now_ns()
        active_np = np.asarray(active, bool)
        active = jnp.asarray(active_np)
        pages = None
        if self.ecfg.kv_paged:
            act = np.nonzero(active_np)[0]
            for t in act:
                table = self._slot_tables[int(t)]
                if table is None:
                    raise RuntimeError(
                        f"active slot {t} has no bound page table — "
                        f"admit requests via bind_slot under kv_paged")
                plan = self.kv_pool.prepare_append(table)
                if plan.cow_src is not None:
                    state = self._cow(state,
                                      jnp.asarray(plan.cow_src, jnp.int32),
                                      jnp.asarray(plan.page, jnp.int32))
                self._slot_pages[int(t), len(table.pages) - 1] = plan.page
            pages = jnp.asarray(self._slot_pages)
        t_plan = now_ns()
        logits, state, self.fast, stats = self._decode(
            self.params, jnp.asarray(tokens, jnp.int32), state, self.fast,
            active, pages)
        t_disp = now_ns()                 # async dispatch returned
        if self.ecfg.kv_paged:
            for t in act:
                self.kv_pool.commit_append(self._slot_tables[int(t)])
        t_commit = now_ns()
        # the drain below blocks on these outputs anyway: waiting for them
        # first splits the device's time from the host's reads
        jax.block_until_ready((logits, stats))  # reprolint: allow[RL002] the drain's own wait, timed apart
        t_wait = now_ns()
        c = self._counters
        snap = (c["hits"], c["fetched_experts"], c["cpu_expert_calls"],
                c["prefetch_issued"], c["prefetch_hits"])
        busy0 = (self.host_executor.busy_ns
                 if self.host_executor is not None else 0)
        n_active = int(active_np.sum())
        self._accumulate(stats, n_active)
        self._obs_decode((t0, t_plan, t_disp, t_commit, t_wait), snap,
                         busy0, n_active)
        return logits, state

    def _accumulate(self, stats, n_active: int) -> None:
        c = self._counters
        for k in ("hits", "accesses", "fetched_experts", "prefetch_issued",
                  "prefetch_hits", "prefetch_wasted", "predicted",
                  "predicted_correct", "cpu_expert_calls", "cpu_tokens",
                  "miss_expert_groups", "fused_groups"):
            c[k] += int(np.asarray(stats[k]).sum())
        c["host_assignments"] += int(
            np.asarray(stats["host_flops_assignments"]).sum())
        # scan stacks one entry per layer: accumulate the per-layer series
        # the aggregates above collapse
        self._per_layer_hits += np.asarray(stats["hits"], np.int64)
        self._per_layer_accesses += np.asarray(stats["accesses"], np.int64)
        c["tokens"] += n_active
        c["steps"] += 1

    def _accumulate_prefill(self, stats, n_tokens: int) -> None:
        """Fold one warm chunk's per-layer stats into the prefill channel
        (kept apart from the decode demand channel on purpose)."""
        c = self._counters
        c["prefill_hits"] += int(np.asarray(stats["hits"]).sum())
        c["prefill_accesses"] += int(np.asarray(stats["accesses"]).sum())
        c["prefill_fetched"] += int(
            np.asarray(stats["fetched_experts"]).sum())
        c["prefill_tokens"] += n_tokens

    # -- trace drain helpers (the ONLY emission sites; see RL007) ----------
    def _obs_decode(self, marks: Tuple[int, int, int, int, int], snap,
                    busy0: int, n_active: int) -> None:
        """Sanctioned drain point: emit the decode step's spans AFTER
        ``_accumulate`` drained the step's stats. ``marks`` are the clock
        readings that end each host phase of the step; the leaf spans
        ``plan`` / ``dispatch`` / ``commit`` / ``wait`` (the device
        running the step) / ``drain`` (the stats reads) tile
        ``decode_step`` end to end. The step's lane attribution (hit,
        fetched and cpu-lane experts) rides ``decode_step``'s args."""
        t1 = now_ns()
        obs = self.obs
        c = self._counters
        hit = c["hits"] - snap[0]
        fetch = c["fetched_experts"] - snap[1]
        cpu = c["cpu_expert_calls"] - snap[2]
        obs.complete("engine", "decode_step", marks[0], t1,
                     {"tokens": n_active, "hit_experts": hit,
                      "fetched_experts": fetch, "cpu_expert_calls": cpu})
        edges = (*marks, t1)
        for name, a, b in zip(("plan", "dispatch", "commit", "wait",
                               "drain"), edges, edges[1:]):
            obs.complete("engine", name, a, b)
        if c["prefetch_issued"] - snap[3]:
            obs.instant("lane:fetch", "prefetch_reserve",
                        {"issued": c["prefetch_issued"] - snap[3]},
                        ts_ns=t1)
        if c["prefetch_hits"] - snap[4]:
            obs.instant("lane:gpu", "prefetch_land",
                        {"hits": c["prefetch_hits"] - snap[4]}, ts_ns=t1)
        if self.host_executor is not None:
            dbusy = self.host_executor.busy_ns - busy0
            if dbusy > 0:
                # the host pool's aggregate busy time this step, placed to
                # end at the drain (per-worker placement is unknowable
                # without timing inside the callback)
                obs.complete("lane:cpu", "host_execute", t1 - dbusy, t1,
                             {"queue_peak": self.host_executor.queue_peak})
        if self.kv_pool is not None:
            pool = self.kv_pool
            obs.counter("engine", "kv_pages_in_use", pool.pages_in_use,
                        ts_ns=t1)
            for name, cur in (("prefix_hits", pool.prefix_hits),
                              ("cow_forks", pool.cow_forks),
                              ("retention_evictions",
                               pool.retention_evictions)):
                prev = self._obs_prev.get(name, 0)
                if cur > prev:
                    obs.instant("engine", name, {"count": cur - prev},
                                ts_ns=t1)
                    self._obs_prev[name] = cur

    def _obs_prefill(self, t0: int, n_units: int,
                     ticket: "PrefillTicket") -> None:
        """Sanctioned drain point: one span per advance_prefill_state
        call (its per-unit ``_accumulate_prefill`` drains already
        synchronized), covering the segments/chunks it advanced."""
        if n_units == 0:
            return
        self.obs.complete(
            "engine",
            "segment_stream" if ticket.seg > 0 else "warm_replay",
            t0, now_ns(),
            {"units": n_units, "cursor": ticket.cursor,
             "of": ticket.n_chunks})

    # -- static-batch convenience path ------------------------------------
    def generate(self, prompt: np.ndarray, steps: int,
                 sampling: SamplingParams = GREEDY,
                 key=None) -> Tuple[np.ndarray, EngineStats]:
        """Static-batch generation: all prompt rows start and stop
        together with one shared SamplingParams (the scheduler path
        interleaves requests with per-request sampling instead). Uses
        bypass prefill — the warming path is per-request."""
        self._require_dense("generate")
        base = np.asarray(jax.random.PRNGKey(sampling.seed)
                          if sampling.seed is not None else
                          (key if key is not None else jax.random.PRNGKey(0)))
        B, P = prompt.shape
        logits, state = self.prefill(jnp.asarray(prompt))
        state["pos"] = jnp.full((B,), P, jnp.int32)

        def step_keys(i):
            if sampling.greedy:               # greedy: no key derivation
                return None
            row0 = np.asarray(jax.random.fold_in(base, i))
            return fold_keys(np.broadcast_to(row0, (B, 2)), np.arange(B))

        tok = self.select_tokens(logits[:, 0], sampling, step_keys(0))[:, None]
        # the B prefill-sampled tokens are generated output: count them in
        # the first_tokens channel so token totals don't undercount by one
        # per sequence
        self._counters["first_tokens"] += B
        active = jnp.ones((B,), bool)
        out = [np.asarray(tok)]
        for i in range(steps - 1):
            logits, state, self.fast, stats = self._decode(
                self.params, tok, state, self.fast, active)
            tok = self.select_tokens(logits[:, 0], sampling,
                                     step_keys(i + 1))[:, None]
            out.append(np.asarray(tok))
            self._accumulate(stats, B)
        return np.concatenate(out, 1), self.stats
