"""Top-k MoE FFN with sort-based capacity dispatch (EP-shardable).

The dispatch is the standard TPU formulation (cf. MaxText / Switch):
tokens' (expert, slot) coordinates are derived from a stable argsort of the
flat expert assignments; tokens beyond per-expert capacity are dropped
(train) — capacity is generous for decode. The [E, C, D] dispatch buffer is
sharded over the ``model`` mesh axis = expert parallelism; GSPMD inserts
the all-to-alls at the resharding boundaries.

Gradients flow through gather/scatter values and the combine weights, so
the router trains; indices are integer (non-differentiable) as usual. The
auxiliary load-balance loss is the Switch-style E * sum(f_e * P_e).

``moe_apply`` is the faithful dense-framework path. The *serving* path with
the paper's two-tier expert cache lives in repro.core.collaborative.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import MoEConfig
from repro.sharding import constrain
from .layers import _dense_init, ffn_apply, ffn_params

Params = Dict[str, jax.Array]


def moe_params(key, d_model: int, m: MoEConfig) -> Params:
    ks = jax.random.split(key, 5)
    E, F = m.num_experts, m.d_ff
    p = {
        "router": _dense_init(ks[0], (d_model, E), dtype=jnp.float32),
        "w1": _dense_init(ks[1], (E, d_model, F)),
        "w3": _dense_init(ks[2], (E, d_model, F)),
        "w2": _dense_init(ks[3], (E, F, d_model)),
    }
    if m.num_shared_experts:
        p["shared"] = ffn_params(ks[4], d_model, F * m.num_shared_experts)
    return p


def route(router_w: jax.Array, x: jax.Array, top_k: int,
          m: Optional[MoEConfig] = None
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: [T, D] -> (probs [T, E] fp32, top-k ids [T, K], top-k weights [T, K]).

    By ``m.router``: "softmax" (the default, also without ``m``) takes the
    top-k of the softmax and renormalises them over the k picks;
    "sparsemixer" picks and weighs as :func:`sparsemixer` with eps
    ``m.router_jitter``. ``probs`` is the softmax either way."""
    logits = x.astype(jnp.float32) @ router_w
    probs = jax.nn.softmax(logits, axis=-1)
    if m is not None and m.router == "sparsemixer":
        top_i, top_w = sparsemixer(logits, top_k, m.router_jitter)
        return probs, top_i, top_w
    top_w, top_i = jax.lax.top_k(probs, top_k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)  # renormalize
    return probs, top_i, top_w


def sparsemixer(logits: jax.Array, top_k: int, eps: float
                ) -> Tuple[jax.Array, jax.Array]:
    """SparseMixer's inference routing (arXiv:2310.00811), as
    Phi-3.5-MoE's published code runs it:
    logits [T, E] -> (ids [T, K] int32, weights [T, K] fp32).

    Pick k is the argmax of the logits with picks 1..k-1 set to -inf.
    Every logit s whose distance below that pick's logit m,
    ``(m - s) / max(|s|, m)``, exceeds ``2 * eps`` is masked to -inf, and
    the pick's weight is the softmax of what is left, at the pick. The k
    weights are not renormalised."""
    E = logits.shape[-1]
    masked = logits
    ids, weights = [], []
    for _ in range(top_k):
        top = masked.max(-1, keepdims=True)
        pick = jnp.argmax(masked, -1)
        far = (top - logits) / jnp.maximum(jnp.abs(logits), top) > 2 * eps
        gates = jax.nn.softmax(jnp.where(far, -jnp.inf, masked), -1)
        ids.append(pick)
        weights.append(jnp.take_along_axis(gates, pick[:, None], -1)[:, 0])
        masked = jnp.where(jax.nn.one_hot(pick, E, dtype=bool), -jnp.inf,
                           masked)
    return jnp.stack(ids, -1).astype(jnp.int32), jnp.stack(weights, -1)


def load_balance_loss(probs: jax.Array, top_i: jax.Array, num_experts: int) -> jax.Array:
    """Switch aux loss: E * sum_e f_e * P_e (fp32 scalar)."""
    T = probs.shape[0]
    f = jnp.zeros((num_experts,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    f = f / jnp.maximum(f.sum(), 1.0)
    P = probs.mean(axis=0)
    return num_experts * jnp.sum(f * P)


def sort_dispatch(top_i: jax.Array, capacity: int, num_experts: int):
    """Flat top-k expert ids -> dispatch coordinates.

    Returns (flat token index per assignment [A], buffer slot per assignment
    [A], keep mask [A]) with A = T*K, buffer slot in [0, E*C).
    """
    A = top_i.size
    flat_e = top_i.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    # position within each expert's run of the sorted list
    starts = jnp.searchsorted(sorted_e, jnp.arange(num_experts), side="left")
    pos_in_e = jnp.arange(A) - starts[sorted_e]
    keep = pos_in_e < capacity
    slot = sorted_e * capacity + jnp.minimum(pos_in_e, capacity - 1)
    token = order // top_i.shape[-1]
    return token, slot, keep, order


def moe_apply(p: Params, x: jax.Array, m: MoEConfig,
              capacity_factor: Optional[float] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, D] -> (y [B, S, D], aux loss scalar).

    Dispatch is *per example* (vmapped over B) when S > 1: a global
    argsort over B*S*K assignments cannot be sharded, so GSPMD would
    replicate the whole dispatch path on every device (measured: 64 GiB
    replicated gathers on qwen3-moe train cells). Per-example sort keeps
    everything sharded over the batch/data axis; the [B, E, C, D] buffer's
    expert axis carries the EP (model-axis) sharding. For S == 1 (decode)
    the assignment count is tiny and a single flat group is cheaper.
    """
    B, S, D = x.shape
    E, K = m.num_experts, m.top_k
    cf = m.capacity_factor if capacity_factor is None else capacity_factor
    # Serve-mode slack (capacity_factor given) only matters where drops
    # are probable: few assignments per dispatch group. At scale the law
    # of large numbers makes the train-style factor effectively dropless,
    # and an 8x buffer would be pure wasted expert compute.
    if capacity_factor is not None and (S if S > 1 else B * S) * K > 256:
        cf = m.capacity_factor

    xf = x.reshape(B * S, D)
    probs, top_i, top_w = route(p["router"], xf, K, m)
    aux = load_balance_loss(probs, top_i, E)

    if S == 1:
        y = _moe_one_group(p, xf, top_i, top_w, m, cf)
    else:
        C = max(int(S * K / E * cf), 1)
        C = (C + 7) // 8 * 8

        buf, token, slot, keep, order = jax.vmap(
            lambda xb, tib, twb: _dispatch(xb, tib, C, E))(
                x, top_i.reshape(B, S, K), top_w.reshape(B, S, K))
        buf = constrain(buf, ("pod", "data"), "model", None, None)  # EP
        h = jax.nn.silu(jnp.einsum("becd,edf->becf", buf, p["w1"])) * \
            jnp.einsum("becd,edf->becf", buf, p["w3"])
        h = constrain(h, ("pod", "data"), "model", None, None)
        out = jnp.einsum("becf,efd->becd", h, p["w2"])
        out = constrain(out, ("pod", "data"), "model", None, None)

        def combine(outb, tokenb, slotb, keepb, orderb, twb):
            contrib = outb.reshape(E * C, D)[slotb] * \
                (twb.reshape(-1)[orderb] * keepb)[:, None].astype(x.dtype)
            return jnp.zeros((S, D), x.dtype).at[tokenb].add(contrib)

        y = jax.vmap(combine)(out, token, slot, keep, order,
                              top_w.reshape(B, S, K))
        y = y.reshape(B * S, D)

    if "shared" in p:
        y = y + ffn_apply(p["shared"], xf)
    return y.reshape(B, S, D), aux


def _dispatch(xb: jax.Array, tib: jax.Array, C: int, E: int):
    """One example's dispatch: xb [S, D], tib [S, K] -> buffer + coords."""
    token, slot, keep, order = sort_dispatch(tib, C, E)
    gathered = xb[token] * keep[:, None].astype(xb.dtype)
    # .add, not .set: dropped assignments are zeroed and clamped onto slot
    # C-1, which must not clobber the kept token living there.
    buf = jnp.zeros((E * C, xb.shape[-1]), xb.dtype).at[slot].add(gathered)
    return buf.reshape(E, C, xb.shape[-1]), token, slot, keep, order


def _moe_one_group(p: Params, xf: jax.Array, top_i: jax.Array,
                   top_w: jax.Array, m: MoEConfig, cf: float) -> jax.Array:
    """Flat single-group dispatch (decode: T = B tokens, tiny sort)."""
    T, D = xf.shape
    E, K = m.num_experts, m.top_k
    C = max(int(T * K / E * cf), 1)
    C = (C + 7) // 8 * 8
    buf, token, slot, keep, order = _dispatch(xf, top_i, C, E)
    buf = constrain(buf, "model", None, None)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w1"])) * \
        jnp.einsum("ecd,edf->ecf", buf, p["w3"])
    out = jnp.einsum("ecf,efd->ecd", h, p["w2"])
    out = constrain(out, "model", None, None)
    flat_w = top_w.reshape(-1)
    contrib = out.reshape(E * C, D)[slot] * \
        (flat_w[order] * keep)[:, None].astype(xf.dtype)
    return jnp.zeros((T, D), xf.dtype).at[token].add(contrib)
