"""The softmax scoring of ``RoutedExpertsLayer`` beside the grouped sigmoid
it had: the gates against the plain reference of the
``keye-vl-2.0-30b-a3b`` configuration, the default scoring left as it
was, the share test of a layer cut over chips, and the decode counters."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import keye_vl2 as ref
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    RoutedExpertsLayer, layer_from_dict, stream_counters)
from deeplearning4j_tpu.nn.layers import routed_experts

E, I, R, K = 32, 16, 16, 4
CFG = {"num_experts": R, "num_experts_per_tok": K, "norm_topk_prob": True}


def weights_of(seed=0, router=R):
    rng = np.random.default_rng(seed)
    return {"moe0/Wr": jnp.asarray(rng.normal(size=(E, router)) / 4,
                                   jnp.float32),
            "moe0/Wg": jnp.asarray(rng.normal(size=(R, E, I)) / 5,
                                   jnp.float32),
            "moe0/Wu": jnp.asarray(rng.normal(size=(R, E, I)) / 5,
                                   jnp.float32),
            "moe0/Wd": jnp.asarray(rng.normal(size=(R, I, E)) / 5,
                                   jnp.float32)}


def tokens(t, seed=1):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(t, E)),
                       jnp.float32)


@pytest.mark.parametrize("norm", [True, False])
def test_the_gates_are_the_references(norm):
    w, h = weights_of(), tokens(50)
    got = routed_experts.router_gates(
        h, w["moe0/Wr"], None, groups=1, top_groups=1, top_k=K, scale=1.0,
        scoring="softmax", norm_topk=norm)
    want = ref.route(h, w["moe0/Wr"], top_k=K, norm=norm, low=False)
    assert np.allclose(got, want, atol=1e-6)
    assert ((np.asarray(got) > 0).sum(axis=1) == K).all()
    if norm:
        assert np.allclose(np.asarray(got).sum(axis=1), 1.0, atol=1e-6)
    else:
        assert (np.asarray(got).sum(axis=1) < 1.0).all()
    scaled = routed_experts.router_gates(
        h, w["moe0/Wr"], None, groups=1, top_groups=1, top_k=K, scale=2.5,
        scoring="softmax", norm_topk=norm)
    assert np.allclose(scaled, 2.5 * np.asarray(got), atol=1e-6)


def test_equal_scores_go_to_the_lower_index():
    """A router that cannot tell the experts apart picks the first K."""
    h = tokens(6)
    flat = jnp.zeros((E, R), jnp.float32)
    got = np.asarray(routed_experts.router_gates(
        h, flat, None, groups=1, top_groups=1, top_k=K, scale=1.0,
        scoring="softmax"))
    assert np.allclose(got[:, :K], 1.0 / K) and not got[:, K:].any()
    assert np.array_equal(
        got, ref.route(h, flat, top_k=K, norm=True, low=False))


def test_the_default_scoring_is_the_grouped_sigmoid_it_was():
    """``scoring`` unsaid: sigma + b chooses by groups, gates sigma
    renormalised times scale, written out here as the layer had it."""
    rng = np.random.default_rng(2)
    h, wr = tokens(40), jnp.asarray(rng.normal(size=(E, R)) / 4, jnp.float32)
    br = jnp.asarray(rng.normal(size=(R,)) * 0.05, jnp.float32)
    got = np.asarray(routed_experts.router_gates(
        h, wr, br, groups=4, top_groups=2, top_k=K, scale=2.5))
    sig = 1 / (1 + np.exp(-(np.asarray(h, np.float64) @ np.asarray(wr))))
    choice = sig + np.asarray(br)
    for t in range(40):
        per = choice[t].reshape(4, 4)
        best = np.argsort(-np.sort(per, axis=1)[:, -2:].sum(axis=1),
                          kind="stable")[:2]
        allowed = np.full(R, -np.inf)
        for g in best:
            allowed[4 * g:4 * g + 4] = choice[t, 4 * g:4 * g + 4]
        chosen = np.argsort(-allowed, kind="stable")[:K]
        want = np.zeros(R)
        want[chosen] = sig[t, chosen] / sig[t, chosen].sum() * 2.5
        assert np.allclose(got[t], want, atol=1e-5), t
    layer = RoutedExpertsLayer(hidden=I, router_experts=R, held=(0, R),
                               top_k=K, groups=4, top_groups=2)
    assert (layer.scoring, layer.norm_topk) == ("sigmoid", True)
    p, _ = layer.init(jax.random.PRNGKey(0), InputType.recurrent(E, 8))
    assert list(p) == ["Wr", "br", "Wg", "Wu", "Wd", "Ws_g", "Ws_u", "Ws_d"]


def soft(held=(0, R), **kw):
    return RoutedExpertsLayer(hidden=I, router_experts=R, held=held,
                              top_k=K, scoring="softmax", shared=0, **kw)


def test_the_softmax_layer_has_no_bias_and_no_shared_expert():
    layer = soft()
    p, _ = layer.init(jax.random.PRNGKey(0), InputType.recurrent(E, 8))
    assert {k: v.shape for k, v in p.items()} == {
        "Wr": (E, R), "Wg": (R, E, I), "Wu": (R, E, I), "Wd": (R, I, E)}
    again = layer_from_dict(json.loads(json.dumps(layer.to_dict())))
    assert again == layer and again.scoring == "softmax"
    with pytest.raises(ValueError, match="scoring"):
        RoutedExpertsLayer(router_experts=8, held=(0, 8), groups=2,
                           scoring="softmax")
    with pytest.raises(ValueError, match="scoring"):
        RoutedExpertsLayer(router_experts=8, held=(0, 8), scoring="tanh")


def test_the_whole_layer_is_the_references_in_both_forms():
    w, h = weights_of(3), tokens(60, seed=4)
    want = ref.experts(CFG, w, 0, h, False)
    p = {k.split("/")[1]: v for k, v in w.items()}
    layer = soft()
    dense, _ = layer.apply(p, h.T[None], {})
    grouped, state = layer.apply(p, h.T[None], {}, stream=True)
    assert np.allclose(dense[0].T, want, atol=2e-5)
    assert np.allclose(grouped[0].T, want, atol=2e-5)
    stats = np.asarray(state["moe_stats"])
    # 60 tokens, K pairs each, whole tiles of 64 rows; a prime is no
    # decode call
    assert stats[:2].tolist() == [60, 60 * K] and stats[2] % 64 == 0
    assert stats[4:].tolist() == [0, 0]


def test_the_shares_of_a_cut_expert_layer_add_up_to_the_whole_layer():
    """Guide section 4: four chips hold four of sixteen experts each (the
    router keeps its sixteen outputs on every chip). The parts the four
    ``held`` ranges give add up to what the uncut reference gives for the
    whole layer, and each is what the reference gives for that range."""
    w, h = weights_of(5), tokens(48, seed=6)
    want = ref.experts(CFG, w, 0, h, False)
    total = jnp.zeros_like(want)
    for chip in range(4):
        first = 4 * chip
        p = {"Wr": w["moe0/Wr"]}
        p.update({k: w[f"moe0/{k}"][first:first + 4]
                  for k in ("Wg", "Wu", "Wd")})
        layer = soft(held=(first, 4))
        part, state = layer.apply(p, h.T[None], {}, stream=True)
        dense, _ = layer.apply(p, h.T[None], {})
        assert np.allclose(part, dense, atol=1e-5)
        assert np.allclose(part[0].T, ref.experts(CFG, w, 0, h, False,
                                                  held=(first, 4)),
                           atol=1e-5)
        total = total + part[0].T
        assert int(state["moe_stats"][0]) == 48
    assert np.allclose(total, want, atol=2e-5)
    # no part is the whole: every token's experts lie on several chips
    assert float(jnp.abs(total - part[0].T).max()) > 1e-3


def test_a_decode_call_counts_the_experts_it_touched():
    """Calls of one position a row: how many, and over them the held
    experts with at least one token — 3 rows x K pairs touch between K
    and 3 K experts; a call over a wider chunk adds to neither."""
    w = weights_of(7)
    p = {k.split("/")[1]: v for k, v in w.items()}
    layer = soft()
    decl = stream_counters(layer)
    assert decl.fields == ("tokens", "held_pairs", "rows_computed",
                           "max_expert_load", "decode_calls",
                           "decode_experts_touched")
    assert decl.maxima == ("max_expert_load",)
    state, touched = {}, 0
    for step in range(3):
        h = tokens(3, seed=10 + step)
        gates = ref.route(h, w["moe0/Wr"], top_k=K, norm=True, low=False)
        touched += int((np.asarray(gates) > 0).any(axis=0).sum())
        _, state = layer.apply(p, h[:, :, None], state, stream=True)
    stats = np.asarray(state["moe_stats"])
    assert stats[4] == 3 and stats[5] == touched
    assert 3 * K <= touched <= 3 * 3 * K
    _, state = layer.apply(p, tokens(5).T[None], state, stream=True)
    assert np.asarray(state["moe_stats"])[4:].tolist() == [3, touched]
    assert int(state["moe_stats"][0]) == 9 + 5
