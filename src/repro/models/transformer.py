"""Decoder-only LM assembly with period-scanned heterogeneous layers.

Architectures repeat a *period* of P layer slots (P = lcm of the attention/
mamba interleave, the MoE interleave and the sliding-window pattern, e.g.
P=1 for llama-likes, 6 for gemma3, 8 for jamba, 2 for llama4). We scan over
``G = L // P`` groups — HLO size is O(P), independent of depth — and unroll
the ``L % P`` remainder. Slot descriptors (kind / window / moe) are static
Python, so each slot body specializes fully.

Modes:
  train   — logits for the full sequence (+ MoE aux loss), no caches.
  prefill — logits of the last position + populated decode state.
  decode  — one token in, logits + in-place-updated state (donate it).
  segment — a [C]-token prompt segment at offset ``state["pos"]`` against
            the request's existing KV (offset causal mask): appends the
            segment's KV in place (dense slot or paged pool via ``pages``)
            and emits the segment's routing trace, so a prompt forward can
            stream across scheduler ticks (repro.serving.engine).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.sharding import constrain
from repro.sharding.ctx import constrain_sp
from . import attention as attn
from . import ssm
from .layers import embed_lookup, embed_params, ffn_apply, ffn_params, \
    logits_from_embed, norm, norm_params, _dense_init
from .moe import moe_apply, moe_params, route

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Slot:
    kind: str        # "attn" | "mamba"
    is_moe: bool
    window: int      # -1 = global


def build_slots(cfg: ModelConfig) -> Tuple[List[Slot], int, int]:
    """Returns (period slots, num scanned groups, num remainder layers)."""
    p = len(cfg.layer_pattern)
    if cfg.moe is not None:
        p = math.lcm(p, cfg.moe_every)
    if cfg.window_pattern:
        p = math.lcm(p, len(cfg.window_pattern))
    p = min(p, cfg.num_layers)
    slots = [Slot(cfg.layer_kind(i), cfg.is_moe_layer(i), cfg.window_for_layer(i))
             for i in range(p)]
    return slots, cfg.num_layers // p, cfg.num_layers % p


def _slot_has_ffn(cfg: ModelConfig, slot: Slot) -> bool:
    return slot.is_moe or cfg.d_ff > 0


# --------------------------------------------------------------------------
# Parameter construction
# --------------------------------------------------------------------------

def _layer_params(key, cfg: ModelConfig, slot: Slot) -> Params:
    ks = jax.random.split(key, 4)
    p: Params = {"ln1": norm_params(cfg.d_model, cfg.norm)}
    if slot.kind == "attn":
        p["attn"] = attn.attn_params(ks[0], cfg.d_model, cfg.num_heads,
                                     cfg.num_kv_heads, cfg.head_dim,
                                     cfg.qkv_bias, cfg.attn_out_bias)
    else:
        p["mamba"] = ssm.mamba_params(ks[0], cfg)
    if _slot_has_ffn(cfg, slot):
        p["ln2"] = norm_params(cfg.d_model, cfg.norm)
        if slot.is_moe:
            p["moe"] = moe_params(ks[1], cfg.d_model, cfg.moe)
        else:
            p["ffn"] = ffn_params(ks[1], cfg.d_model, cfg.d_ff)
    return p


def init_params(cfg: ModelConfig, key) -> Params:
    slots, G, R = build_slots(cfg)
    keys = jax.random.split(key, 4 + len(slots) * (G + 1))
    params: Params = {"embed": embed_params(keys[0], cfg.vocab_size, cfg.d_model),
                      "final_norm": norm_params(cfg.d_model, cfg.norm)}
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_params(keys[1], cfg.vocab_size, cfg.d_model)
    if cfg.lm_head_bias:
        params["logits"] = {"bias": jnp.zeros((cfg.vocab_size,), jnp.bfloat16)}
    if cfg.frontend_embed_dim:
        params["frontend_proj"] = _dense_init(
            keys[2], (cfg.frontend_embed_dim, cfg.d_model))
    ki = 3
    scan: Params = {}
    for j, slot in enumerate(slots):
        stacked = [ _layer_params(keys[ki + g], cfg, slot) for g in range(G) ]
        ki += G
        scan[f"s{j}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *stacked) \
            if G > 1 else jax.tree.map(lambda x: x[None], stacked[0])
    params["scan"] = scan
    rem: Params = {}
    for j in range(R):
        rem[f"r{j}"] = _layer_params(keys[ki], cfg, slots[j % len(slots)])
        ki += 1
    if rem:
        params["rem"] = rem
    return params


# --------------------------------------------------------------------------
# Per-layer state (KV cache / SSM state)
# --------------------------------------------------------------------------

def _layer_state(cfg: ModelConfig, slot: Slot, batch: int, capacity: int) -> Params:
    if slot.kind == "attn":
        return attn.init_kv_cache(batch, capacity, cfg.num_kv_heads, cfg.head_dim)
    return ssm.init_ssm_state(cfg, batch)


def init_state(cfg: ModelConfig, batch: int, capacity: int) -> Params:
    """Decode state pytree, mirroring the scan/rem param structure. Every
    state starts at zero; each stacked leaf is allocated once, at its
    final shape, so building the state holds no second copy of it."""
    slots, G, R = build_slots(cfg)
    state: Params = {"scan": {}, "pos": jnp.zeros((), jnp.int32)}
    for j, slot in enumerate(slots):
        st = jax.eval_shape(
            lambda slot=slot: _layer_state(cfg, slot, batch, capacity))
        state["scan"][f"s{j}"] = jax.tree.map(
            lambda s: jnp.zeros((G,) + s.shape, s.dtype), st)
    if R:
        state["rem"] = {f"r{j}": _layer_state(cfg, slots[j % len(slots)], batch, capacity)
                        for j in range(R)}
    return state


# --------------------------------------------------------------------------
# Layer application
# --------------------------------------------------------------------------

def _apply_layer(lp: Params, x: jax.Array, slot: Slot, cfg: ModelConfig,
                 positions, mode: str, state: Optional[Params], pos,
                 want_trace: bool = False, pages=None,
                 kv_write_min=None, kv_write_max=None
                 ) -> Tuple[jax.Array, Optional[Params], jax.Array,
                            Optional[Params]]:
    """Returns (x, new_state, aux_loss, routing trace).

    ``want_trace`` (prefill/segment-mode MoE slots only) additionally emits
    the per-layer routing trace — ``top_i``/``top_w`` [B, S, K] and the
    post-ln2 hidden states ``h2`` [B, S, D] — that the serving engine's
    cache-warming replay consumes (repro.serving.engine). The trace is
    derived from the SAME router weights and the SAME h2 that moe_apply
    consults, so replaying it reproduces the prompt's expert demand
    exactly; emitting it never changes x / new_state / aux. Trace is None
    everywhere else (the default skips the O(L*S*D) materialization).

    Segment mode appends a [C]-token prompt segment at offset ``pos`` to
    the layer's KV (dense cache, or the paged pool when ``pages`` is a
    [B, max_pages] table; ``kv_write_min``/``kv_write_max`` bound which
    absolute positions may land — shared prefix pages stay immutable)."""
    aux = jnp.zeros((), jnp.float32)
    h = norm(lp["ln1"], x, cfg)
    new_state = None
    if slot.kind == "attn":
        if mode == "decode":
            o, new_state = attn.decode_attention(lp["attn"], h, state, pos, cfg,
                                                 slot.window)
        elif mode == "segment":
            if pages is not None:
                o, new_state = attn.segment_attention_paged(
                    lp["attn"], h, state, pos, positions, pages, cfg,
                    slot.window, kv_write_min, kv_write_max)
            else:
                o, new_state = attn.segment_attention(
                    lp["attn"], h, state, pos, positions, cfg, slot.window)
        else:
            o = attn.self_attention(lp["attn"], h, positions, cfg, slot.window)
            if mode == "prefill":
                # rebuild k/v for the cache (cheap projections; avoids
                # threading internals out of the flash path)
                q, k, v = attn._project_qkv(lp["attn"], h, cfg)
                _, k = attn._rope_qk(q, k, positions, cfg)
                new_state = {"k": k, "v": v}
    else:
        if mode == "decode":
            o, new_state = ssm.mamba_apply(lp["mamba"], h, cfg, state, decode=True)
        elif mode == "segment":
            raise NotImplementedError(
                "segment-streamed prefill supports attention layers only")
        elif mode == "prefill":
            o, new_state = ssm.mamba_apply(
                lp["mamba"], h, cfg, ssm.init_ssm_state(cfg, x.shape[0]))
        else:
            o, _ = ssm.mamba_apply(lp["mamba"], h, cfg)
    x = x + o
    trace = None
    if _slot_has_ffn(cfg, slot):
        h2 = norm(lp["ln2"], x, cfg)
        if slot.is_moe:
            # train drops at capacity (standard); serving must not — a
            # prefill-dropped token would diverge from the decode path
            cf = None if mode == "train" else cfg.moe.serve_capacity_factor
            f, aux = moe_apply(lp["moe"], h2, cfg.moe, capacity_factor=cf)
            if want_trace and mode in ("prefill", "segment"):
                B, S, _ = h2.shape
                K = cfg.moe.top_k
                _, top_i, top_w = route(lp["moe"]["router"],
                                        h2.reshape(B * S, -1), K, cfg.moe)
                trace = {"top_i": top_i.reshape(B, S, K),
                         "top_w": top_w.reshape(B, S, K), "h2": h2}
        else:
            f = ffn_apply(lp["ffn"], h2)
        x = x + f
    x = constrain_sp(x) if mode == "train" else \
        constrain(x, ("pod", "data"), None, None)
    return x, new_state, aux, trace


# --------------------------------------------------------------------------
# Backbone
# --------------------------------------------------------------------------

def _embed_inputs(params: Params, batch: Dict[str, jax.Array], cfg: ModelConfig):
    x = embed_lookup(params["embed"], batch["tokens"]).astype(jnp.bfloat16)
    if cfg.family == "vlm" and "patches" in batch \
            and x.shape[1] > batch["patches"].shape[1]:
        # multimodal stub: precomputed patch embeddings replace the prefix
        # (train/prefill only — decode steps are pure text continuation)
        pe = (batch["patches"] @ params["frontend_proj"]).astype(x.dtype)
        x = jnp.concatenate([pe, x[:, pe.shape[1]:, :]], axis=1)
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return constrain(x, ("pod", "data"), None, None)


def _positions(batch: Dict[str, jax.Array], cfg: ModelConfig, S: int, B: int):
    if cfg.mrope:
        if "positions" in batch:
            return batch["positions"]
        p = jnp.arange(S)[None].repeat(B, 0)
        return jnp.stack([p, p, p])            # text-only: t = h = w
    return jnp.arange(S)[None]


def backbone(params: Params, batch: Dict[str, jax.Array], cfg: ModelConfig,
             mode: str, state: Optional[Params] = None,
             remat: bool = True, want_trace: bool = False,
             pages: Optional[jax.Array] = None,
             kv_write_min=None, kv_write_max=None
             ) -> Tuple[jax.Array, Optional[Params], jax.Array,
                        Optional[Params]]:
    """Runs embedding + all layers. Returns (hidden, new_state, aux, trace).

    ``want_trace`` (prefill/segment modes) collects every MoE layer's
    routing trace into a pytree mirroring the scan/rem param structure:
    ``trace["scan"]["s{j}"]`` holds ``top_i``/``top_w`` [G, B, S, K] and
    ``h2`` [G, B, S, D] for MoE slot j (plus ``trace["rem"]`` for
    remainder MoE layers). This is the ONE prefill implementation — the
    serving engine replays the trace to warm its expert cache; there is no
    hand-mirrored copy of the prefill branch anywhere else. Trace is None
    without the flag (and the trace materialization is skipped).

    Segment mode streams a prompt forward: ``batch["tokens"]`` holds one
    [B, C] segment, ``state["pos"]`` its first absolute position, and the
    per-layer states carry the request's KV so far (dense [B, cap] slots,
    or the paged pool with ``pages``/``kv_write_min``/``kv_write_max``
    forwarded to the paged segment attention). The forward IS the trace
    source — first-token logits emerge once the caller has streamed the
    last segment."""
    slots, G, R = build_slots(cfg)
    want_trace = want_trace and mode in ("prefill", "segment")
    x = _embed_inputs(params, batch, cfg)
    B, S = x.shape[0], x.shape[1]
    pos = state["pos"] if mode in ("decode", "segment") else None
    if mode == "decode":
        positions = None
    elif mode == "segment":
        p = pos + jnp.arange(S)[None]                      # [1, S] absolute
        positions = jnp.stack([jnp.broadcast_to(p, (B, S))] * 3) \
            if cfg.mrope else p
    else:
        positions = _positions(batch, cfg, S, B)

    # Nested remat for multi-slot periods (jamba: 8 sub-layers/group): the
    # outer checkpoint alone would rematerialize ALL sub-layers' internals
    # simultaneously in the group's backward (~90 GB/device for jamba) —
    # checkpointing each sub-layer bounds live internals to one layer.
    nested = remat and mode == "train" and len(slots) > 1

    def group_body(carry, xs):
        x, aux = carry
        lp_group = xs["params"]
        st_group = xs.get("state")
        new_sts = {}
        traces = {}
        for j, slot in enumerate(slots):
            st = st_group[f"s{j}"] if st_group is not None else None
            layer_fn = functools.partial(_apply_layer, slot=slot, cfg=cfg,
                                         positions=positions, mode=mode,
                                         state=st, pos=pos,
                                         want_trace=want_trace, pages=pages,
                                         kv_write_min=kv_write_min,
                                         kv_write_max=kv_write_max)
            if nested:
                layer_fn = jax.checkpoint(layer_fn)
            x, new_st, a, tr = layer_fn(lp_group[f"s{j}"], x)
            if new_st is not None:
                new_sts[f"s{j}"] = new_st
            if tr is not None:
                traces[f"s{j}"] = tr
            aux = aux + a
        return (x, aux), (new_sts, traces)

    body = jax.checkpoint(group_body) if (remat and mode == "train") else group_body

    xs: Dict[str, Any] = {"params": params["scan"]}
    if mode in ("decode", "segment"):
        xs["state"] = state["scan"]
    (x, aux), (scan_states, scan_traces) = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), xs)

    rem_states = {}
    rem_traces = {}
    for j in range(R):
        slot = slots[j % len(slots)]
        st = state["rem"][f"r{j}"] if mode in ("decode", "segment") else None
        x, new_st, a, tr = _apply_layer(params["rem"][f"r{j}"], x, slot, cfg,
                                        positions, mode, st, pos,
                                        want_trace=want_trace, pages=pages,
                                        kv_write_min=kv_write_min,
                                        kv_write_max=kv_write_max)
        if new_st is not None:
            rem_states[f"r{j}"] = new_st
        if tr is not None:
            rem_traces[f"r{j}"] = tr
        aux = aux + a

    x = norm(params["final_norm"], x, cfg)

    new_state = None
    if mode in ("prefill", "decode", "segment"):
        new_state = {"scan": scan_states}
        if R:
            new_state["rem"] = rem_states
        if mode == "decode":
            new_state["pos"] = state["pos"] + 1
        elif mode == "segment":
            new_state["pos"] = jnp.asarray(state["pos"] + S, jnp.int32)
        else:
            new_state["pos"] = jnp.asarray(S, jnp.int32)
    trace = None
    if want_trace:
        trace = {"scan": scan_traces}
        if rem_traces:
            trace["rem"] = rem_traces
    return x, new_state, aux, trace


def lm_logits(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Logits of the final-normed hidden states, with the LM head's bias
    where the model has one (``params["logits"]["bias"]``)."""
    table = params.get("lm_head", params["embed"])
    lg = logits_from_embed(table, x, cfg.logit_softcap)
    return lg + params["logits"]["bias"] if "logits" in params else lg
