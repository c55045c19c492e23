"""Phi-3.5-MoE's block (``model_type`` ``phimoe``) served through the
normal path — ``build()``, ``CollaborativeEngine``, segment prefill and
paged decode — against the plain float32 reference
(``benchmarks/chip/references/phimoe.py``), on the CPU at a small size:
d 64, 4/2 heads of 16, 8 experts of 96, top-2, 2 layers, a vocabulary of
512, seeded random weights with every bias non-zero. Each published
feature left out of the program fails the comparison. SparseMixer on
hand-made logits, and Mixtral's served tokens and logits bit for bit as
before these features."""
import dataclasses
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import spec, weights
from repro.config import MoEConfig, get_config, reduced
from repro.models import init_params
from repro.models.moe import route, sparsemixer
from repro.serving import build

ROOT = Path(__file__).resolve().parents[1]
SEED = 1234
STEPS = 8
# the comparison: mean |program logit - reference logit| over every
# vocabulary row of every served position (prefill's first token and
# each paged decode step). The program computes in bfloat16 (weights and
# activations, ~2^-8 relative per rounding, over 2 layers) against the
# reference's float32 at HIGHEST: it reads 0.023 here. A router near-tie
# that bfloat16 flips moves one position by up to ~0.6, and the mean
# barely. Each feature left out reads 0.16 or more (below): 0.06 lies
# between, with room on both sides.
MEAN_GAP = 0.06


def _tiny_conf():
    conf = json.loads(
        (ROOT / "benchmarks/chip/configs/phi-3.5-moe-l4.json").read_text())
    rs = dict(conf["rope_scaling"])
    for kind in ("short_factor", "long_factor"):
        rs[kind] = rs[kind][::8]                  # 8 frequencies of head 16
    conf.update(hidden_size=64, intermediate_size=96, num_attention_heads=4,
                num_key_value_heads=2, num_local_experts=8,
                num_hidden_layers=2, vocab_size=512, rope_scaling=rs)
    return conf


def _leaf(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


@pytest.fixture(scope="module")
def tiny():
    conf = _tiny_conf()
    model = spec.model_config(ROOT, conf)
    params = weights.make_params(model, SEED)
    # biases drawn at 10x the benchmark's 0.02, so that each bias moves
    # the logits well past bfloat16's rounding at this size
    params = jax.tree_util.tree_map_with_path(
        lambda p, x: x * 10 if _leaf(p).rsplit("/", 1)[-1]
        in weights.BIASES else x, params)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 512, n).astype(np.int32) for n in (21, 34)]
    return conf, model, params, prompts


def _serve(model, params, prompts):
    """Segment prefill, then paged decode of both requests side by side
    in a 2-slot batch, greedy; per request the logits of each served
    token's position and the tokens."""
    engine, _ = build(model, cache={"num_indexes": 2, "num_ways": 2},
                      serving=dict(max_batch=2, capacity=64, kv_paged=True,
                                   page_size=16, prefill_segment=16,
                                   admit_chunks_per_tick=1), params=params)
    state = engine.init_slots()
    toks, rows = [], []
    for slot, prompt in enumerate(prompts):
        ticket = engine.start_prefill(prompt)
        done = False
        while not done:
            state, done = engine.advance_prefill_state(ticket, state, 1)
        state = engine.bind_slot(state, ticket, slot)
        lg = np.asarray(ticket.logits[0, 0], np.float32)
        rows.append([lg])
        toks.append([int(lg.argmax())])
    for _ in range(STEPS):
        last = np.array([[t[-1]] for t in toks], np.int32)
        logits, state = engine.decode_batch(last, state,
                                            np.ones(len(prompts), bool))
        for i in range(len(prompts)):
            lg = np.asarray(logits[i, 0], np.float32)
            rows[i].append(lg)
            toks[i].append(int(lg.argmax()))
    return toks, [np.stack(r) for r in rows]


def _mean_gap(conf, params, prompts, served) -> float:
    """Mean |logit gap| of the served rows against the reference's full
    forward over each prompt and the tokens served after it."""
    ref = spec.reference(ROOT, conf)
    keys = spec.published(conf)
    toks, rows = served
    gaps = []
    for prompt, t, r in zip(prompts, toks, rows):
        seq = np.concatenate([prompt, np.asarray(t[:-1], np.int32)])
        want = np.asarray(ref.logits(params, keys, seq, 64))[len(prompt) - 1:]
        gaps.append(np.abs(r - want).ravel())
    return float(np.concatenate(gaps).mean())


def test_prefill_and_paged_decode_match_the_reference(tiny):
    conf, model, params, prompts = tiny
    assert model.norm == "layer" and model.moe.router == "sparsemixer"
    assert model.qkv_bias and model.attn_out_bias and model.lm_head_bias
    served = _serve(model, params, prompts)
    assert _mean_gap(conf, params, prompts, served) <= MEAN_GAP


def _zeroed(params, which):
    return jax.tree_util.tree_map_with_path(
        lambda p, x: jnp.zeros_like(x) if which(_leaf(p)) else x, params)


def _rope(model, **change):
    return dataclasses.replace(model, rope_scaling=dataclasses.replace(
        model.rope_scaling, **change))


ABLATIONS = {
    "layernorm_bias_dropped": lambda m, p: (m, _zeroed(
        p, lambda n: n.endswith("bias") and ("/ln" in n or "final_norm" in n))),
    "o_bias_dropped": lambda m, p: (m, _zeroed(
        p, lambda n: n.endswith("attn/bo"))),
    "lm_head_bias_dropped": lambda m, p: (m, _zeroed(
        p, lambda n: n == "logits/bias")),
    "longrope_factors_ignored": lambda m, p: (_rope(
        m, short_factor=(1.0,) * len(m.rope_scaling.short_factor)), p),
    "mscale_ignored": lambda m, p: (_rope(m, short_mscale=1.0), p),
    "softmax_renormalise_routing": lambda m, p: (dataclasses.replace(
        m, moe=dataclasses.replace(m.moe, router="softmax")), p),
}


@pytest.mark.parametrize("ablation", list(ABLATIONS))
def test_a_published_feature_left_out_fails_the_comparison(tiny, ablation):
    """The program without one feature, compared on its own served
    tokens with the reference that has them all."""
    conf, model, params, prompts = tiny
    m, p = ABLATIONS[ablation](model, params)
    served = _serve(m, p, prompts)
    assert _mean_gap(conf, params, prompts, served) > MEAN_GAP


# logits rows, eps, expected (ids, weights): the mask threshold
# (m - s) / max(|s|, m) > 2·eps keeps near logits in the pick's softmax
# and drops far ones, the second pick runs with the first at -inf, and
# the weights are not renormalised
HAND = [
    ([2.0, 1.99, 0.5, -1.0], 0.01,
     ([0, 1], [1 / (1 + np.exp(-0.01)), 1.0])),
    ([1.0, 0.97, 0.5, 0.95], 0.02,
     ([0, 1], [1 / (1 + np.exp(-0.03)), 1 / (1 + np.exp(-0.02))])),
    # negative logits: the distance is over |s| (0.095 here), not over
    # the pick's |m| (0.105, which would mask it)
    ([-1.0, -1.105, -3.0, -4.0], 0.05,
     ([0, 1], [1 / (1 + np.exp(-0.105)), 1.0])),
    # 2.92 is masked from the first pick's softmax (0.027 below 3.0) but
    # kept in the second's (0.014 below 2.96)
    ([0.3, 3.0, 2.96, 2.92], 0.01,
     ([1, 2], [1 / (1 + np.exp(-0.04)), 1 / (1 + np.exp(-0.04))])),
]


@pytest.mark.parametrize("row,eps,want", HAND)
def test_sparsemixer_on_hand_made_logits(row, eps, want):
    ids, w = sparsemixer(jnp.asarray([row], jnp.float32), 2, eps)
    assert np.asarray(ids)[0].tolist() == want[0]
    np.testing.assert_allclose(np.asarray(w)[0], want[1], rtol=1e-6)
    ref = spec.reference(ROOT, _tiny_conf())
    gate = np.asarray(ref.sparsemixer(jnp.asarray([row], jnp.float32), eps))
    np.testing.assert_allclose(gate[0, want[0]], want[1], rtol=1e-6)
    assert np.count_nonzero(gate) == 2


def test_route_picks_sparsemixer_by_config():
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 5))
    m = MoEConfig(num_experts=5, top_k=2, d_ff=4, router="sparsemixer",
                  router_jitter=0.01)
    probs, ids, wts = route(w, x, 2, m)
    want_ids, want_w = sparsemixer(x @ w, 2, 0.01)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_allclose(wts, want_w, rtol=1e-6)
    np.testing.assert_allclose(probs, jax.nn.softmax(x @ w, -1), rtol=1e-6)
    assert not np.allclose(np.asarray(wts).sum(-1), 1.0)


# Mixtral at reduced widths served through build() and the scheduler
# (paged KV, segment prefill, 2 slots): the tokens, and the sha256 of
# every decode step's logits, as the tree before LayerNorm, the biases,
# LongRoPE and SparseMixer were added served them
MIXTRAL_TOKENS = [[113, 511, 106, 285, 76, 481], [273, 196, 5, 168, 415],
                  [116, 156, 482, 60, 410, 252, 476]]
MIXTRAL_LOGITS = \
    "1007b3d2ea656cc558e7f8e0ea7ae7571632936a8f1dac649df6c8965e04e833"


def test_mixtral_serves_the_tokens_and_logits_it_served_before():
    cfg = reduced(get_config("mixtral-8x7b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine, sched = build(cfg, cache={"num_indexes": 2, "num_ways": 2},
                          serving=dict(max_batch=2, capacity=64,
                                       kv_paged=True, page_size=16,
                                       prefill_segment=16,
                                       admit_chunks_per_tick=1),
                          params=params)
    seen = []
    decode = engine.decode_batch

    def recorded(tokens, state, active):
        logits, state = decode(tokens, state, active)
        seen.append(np.asarray(logits, np.float32))
        return logits, state

    engine.decode_batch = recorded
    rng = np.random.default_rng(3)
    for n, new in ((20, 6), (33, 5), (9, 7)):
        sched.submit(rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                     new)
    out = sched.run()
    assert [out[k].tolist() for k in sorted(out)] == MIXTRAL_TOKENS
    h = hashlib.sha256()
    for lg in seen:
        h.update(lg.tobytes())
    assert h.hexdigest() == MIXTRAL_LOGITS


def test_the_preset_builds_the_published_block():
    cfg = get_config("phi35-moe")
    conf = json.loads(
        (ROOT / "benchmarks/chip/configs/phi-3.5-moe-l4.json").read_text())
    mapped = spec.model_config(ROOT, conf)
    for f in cfg.__dataclass_fields__:
        if f not in ("name", "num_layers"):
            assert getattr(cfg, f) == getattr(mapped, f), f
    small = reduced(cfg)
    assert len(small.rope_scaling.short_factor) == small.head_dim // 2
    params = init_params(small, jax.random.PRNGKey(0))
    layer = params["scan"]["s0"]
    assert set(layer["ln1"]) == {"scale", "bias"}
    assert {"bq", "bk", "bv", "bo"} <= set(layer["attn"])
    assert params["logits"]["bias"].shape == (small.vocab_size,)
