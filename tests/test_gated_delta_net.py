"""GatedDeltaNetLayer (nn/conf/layers.py, nn/layers/linear_attention.py):
the chunked form, the one-step form and the plain recurrence agree; masked
positions leave state and convolution tail untouched; prime then decode is
the full forward; beta reaches (1, 2); the configuration round-trips. And
SelfAttentionLayer's new fields (no biases, the q/k norm, a prime's query
blocks) against a plain attention, with its defaults as they were."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    GatedDeltaNetLayer, SelfAttentionLayer, SlotLeaf, layer_from_dict,
    layer_to_dict, slot_leaves, stream_counters)
from deeplearning4j_tpu.nn.layers import linear_attention as la

H, DK, DV, F = 3, 8, 12, 24


def _recurrence(q, k, v, log_alpha, beta, state):
    """S <- a S + b k (v - a S^T k)^T, o = S^T q, token by token, in
    numpy float64: written from the equations, not from the layer."""
    q, k, v, log_alpha, beta, s = (np.asarray(a, np.float64) for a in
                                   (q, k, v, log_alpha, beta, state))
    s = s.copy()
    out = np.zeros(v.shape)
    for n in range(q.shape[0]):
        for h in range(q.shape[1]):
            for t in range(q.shape[2]):
                a, b = np.exp(log_alpha[n, h, t]), beta[n, h, t]
                kt, vt = k[n, h, t], v[n, h, t]
                u = b * (vt - a * s[n, h].T @ kt)
                s[n, h] = a * s[n, h] + np.outer(kt, u)
                out[n, h, t] = s[n, h].T @ q[n, h, t]
    return out, s


def _inputs(t, n=2, seed=0):
    rng = np.random.default_rng(seed)
    q = la.l2_normalize(jnp.asarray(rng.normal(size=(n, H, t, DK)),
                                    jnp.float32), 1e-6) * DK ** -0.5
    # keys with a common direction: the triangular system is not tame
    k = la.l2_normalize(jnp.asarray(rng.normal(size=(n, H, t, DK)) + 0.7,
                                    jnp.float32), 1e-6)
    v = jnp.asarray(rng.normal(size=(n, H, t, DV)), jnp.float32)
    log_alpha = -jnp.asarray(rng.uniform(0, 0.3, (n, H, t)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 2, (n, H, t)), jnp.float32)
    state = jnp.asarray(rng.normal(size=(n, H, DK, DV)), jnp.float32)
    return q, k, v, log_alpha, beta, state


@pytest.mark.parametrize("t", [2, 63, 64, 130, 200])
def test_chunked_form_is_the_recurrence(t):
    q, k, v, log_alpha, beta, state = _inputs(t)
    want_o, want_s = _recurrence(q, k, v, log_alpha, beta, state)
    o, s = la.gdn_chunked(q, k, v, log_alpha, beta, state)
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5)


def test_one_step_form_is_the_recurrence_and_the_chunked_form():
    q, k, v, log_alpha, beta, state = _inputs(7)
    want_o, want_s = _recurrence(q, k, v, log_alpha, beta, state)
    s, outs = state, []
    for t in range(7):
        o, s = la.gdn_step(q[:, :, t], k[:, :, t], v[:, :, t],
                           log_alpha[:, :, t], beta[:, :, t], s)
        outs.append(o)
    np.testing.assert_allclose(jnp.stack(outs, 2), want_o, atol=1e-5)
    np.testing.assert_allclose(s, want_s, atol=1e-5)
    o2, s2 = la.gdn_chunked(q, k, v, log_alpha, beta, state)
    np.testing.assert_allclose(o2, jnp.stack(outs, 2), atol=1e-5)
    np.testing.assert_allclose(s2, s, atol=1e-5)


def test_beta_over_one_turns_the_state_round():
    """``linear_allow_neg_eigval``: with beta in (1, 2) the factor
    ``I - beta k k^T`` has the eigenvalue ``1 - beta < 0`` along k."""
    k = jnp.zeros((1, 1, DK)).at[0, 0, 0].set(1.0)
    state = jnp.ones((1, 1, DK, DV))
    zero_v = jnp.zeros((1, 1, DV))
    for b, sign in ((0.5, 1.0), (1.6, -1.0)):
        _, s = la.gdn_step(k, k, zero_v, jnp.zeros((1, 1)),
                           jnp.full((1, 1), b), state)
        assert np.sign(np.asarray(s)[0, 0, 0, 0]) == sign
        np.testing.assert_allclose(s[0, 0, 0], 1 - b, atol=1e-6)
        np.testing.assert_allclose(s[0, 0, 1:], 1.0)
    layer, params = _layer()
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, F, 40)),
                    jnp.float32)
    beta = 2 * jax.nn.sigmoid(jnp.moveaxis(x, 1, 2) @ params["Wb"])
    assert float(beta.max()) > 1.2 and float(beta.min()) < 0.8
    assert not GatedDeltaNetLayer(allow_neg_eigval=False).allow_neg_eigval


def test_the_triangular_inverse_is_the_inverse():
    rng = np.random.default_rng(1)
    a = np.tril(rng.normal(size=(2, 3, 64, 64)) * 0.4 + 0.2, -1)
    inv = la._unit_lower_inverse(jnp.asarray(a, jnp.float32))
    np.testing.assert_allclose(
        np.asarray(inv, np.float64) @ (np.eye(64) + a),
        np.broadcast_to(np.eye(64), a.shape), atol=2e-4)


def _layer(**kw):
    layer = GatedDeltaNetLayer(n_out=F, n_heads=H, key_dim=DK,
                               value_dim=DV, **kw)
    params, state = layer.init(jax.random.PRNGKey(5),
                               InputType.recurrent(F, 16))
    assert state == {}
    return layer, params


def _plain_layer(layer, params, x):
    """The layer's equations over one sequence x [T, F], in float64."""
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    x = np.asarray(x, np.float64)
    t = x.shape[0]
    qkv = np.concatenate([x @ p["Wq"], x @ p["Wk"], x @ p["Wv"]], -1)
    past = np.concatenate([np.zeros((3, qkv.shape[1])), qkv])
    conv = sum(past[i:i + t] * p["conv"][i] for i in range(4))
    conv = conv / (1 + np.exp(-conv))
    q, k, v = np.split(conv, [H * DK, 2 * H * DK], -1)
    q = q.reshape(t, H, DK)
    k = k.reshape(t, H, DK)
    q = q / np.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) * DK ** -0.5
    k = k / np.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    beta = 2 / (1 + np.exp(-(x @ p["Wb"])))
    log_alpha = -np.exp(p["A_log"]) * np.logaddexp(
        0, x @ p["Wa"] + p["dt_bias"])

    def heads(a):
        return np.moveaxis(a, 0, 1)[None]

    o, _ = _recurrence(heads(q), heads(k), heads(v.reshape(t, H, DV)),
                       heads(log_alpha), heads(beta),
                       np.zeros((1, H, DK, DV)))
    o = np.moveaxis(o[0], 0, 1)                             # [T, H, dv]
    z = (x @ p["Wz"]).reshape(t, H, DV)
    y = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-6) * p["norm"] \
        * z / (1 + np.exp(-z))
    return y.reshape(t, H * DV) @ p["Wo"]


@pytest.mark.parametrize("t", [1, 9, 70])
def test_the_layer_is_its_equations(t):
    layer, params = _layer()
    x = np.random.default_rng(t).normal(size=(2, F, t)).astype(np.float32)
    y, state = layer.apply(params, jnp.asarray(x), {})
    assert state == {}                     # a training forward keeps none
    for n in range(2):
        np.testing.assert_allclose(
            np.asarray(y)[n].T, _plain_layer(layer, params, x[n].T),
            atol=3e-5)


def test_a_left_padded_prime_gives_the_unpadded_state_and_outputs():
    layer, params = _layer()
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(1, F, 37)), jnp.float32)
    y, st = layer.apply(params, x, {}, stream=True)
    for pad in (27, 91):                      # buckets of 64 and 128
        xp = jnp.concatenate(
            [jnp.asarray(rng.normal(size=(1, F, pad)), jnp.float32), x], 2)
        yp, sp = layer.apply(params, xp, {}, stream=True,
                             pad_left=jnp.int32(pad))
        np.testing.assert_allclose(yp[:, :, pad:], y, atol=1e-5)
        np.testing.assert_allclose(sp["gdn_s"], st["gdn_s"], atol=1e-5)
        np.testing.assert_allclose(sp["gdn_conv"], st["gdn_conv"],
                                   atol=1e-6)
        # scanned: whole chunks, pads included; fed: the real ones
        assert list(np.asarray(sp["gdn_stats"])) == [
            pad + 37 + -(pad + 37) % la.CHUNK, 37, 0]


def test_a_mask_skips_its_positions_whatever_its_shape():
    """Rows with pads on the left, on the right and in the middle: each
    gives the outputs and the state of its real positions alone, and a
    stream that goes on afterwards sees the right tail."""
    layer, params = _layer()
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(3, F, 20)), jnp.float32)
    mask = np.ones((3, 20), np.float32)
    mask[0, :6] = 0
    mask[1, 15:] = 0
    mask[2, [3, 4, 11]] = 0
    y, st = layer.apply(params, x, {}, stream=True, mask=jnp.asarray(mask))
    nxt = jnp.asarray(rng.normal(size=(3, F, 1)), jnp.float32)
    y2, _ = layer.apply(params, nxt, st, stream=True)
    for n in range(3):
        keep = np.flatnonzero(mask[n])
        alone, s1 = layer.apply(params, x[n:n + 1][:, :, keep], {},
                                stream=True)
        np.testing.assert_allclose(np.asarray(y)[n][:, keep], alone[0],
                                   atol=1e-5)
        np.testing.assert_allclose(st["gdn_s"][n], s1["gdn_s"][0],
                                   atol=1e-5)
        np.testing.assert_allclose(st["gdn_conv"][n], s1["gdn_conv"][0],
                                   atol=1e-6)
        a2, _ = layer.apply(params, nxt[n:n + 1], s1, stream=True)
        np.testing.assert_allclose(y2[n], a2[0], atol=1e-5)


def test_a_masked_single_step_leaves_state_and_tail_untouched():
    layer, params = _layer()
    rng = np.random.default_rng(6)
    _, st = layer.apply(params, jnp.asarray(
        rng.normal(size=(2, F, 10)), jnp.float32), {}, stream=True)
    x = jnp.asarray(rng.normal(size=(2, F, 1)), jnp.float32)
    _, s2 = layer.apply(params, x, st, stream=True,
                        mask=jnp.asarray([[1.0], [0.0]]))
    np.testing.assert_array_equal(s2["gdn_s"][1], st["gdn_s"][1])
    np.testing.assert_array_equal(s2["gdn_conv"][1], st["gdn_conv"][1])
    assert np.abs(np.asarray(s2["gdn_s"][0] - st["gdn_s"][0])).max() > 1e-4


@pytest.mark.parametrize("prime", [1, 30, 100])
def test_prime_then_decode_is_the_full_forward(prime):
    layer, params = _layer()
    x = jnp.asarray(np.random.default_rng(prime).normal(
        size=(2, F, prime + 6)), jnp.float32)
    full, _ = layer.apply(params, x, {})
    y, st = layer.apply(params, x[:, :, :prime], {}, stream=True)
    np.testing.assert_allclose(y, full[:, :, :prime], atol=1e-5)
    for t in range(prime, prime + 6):
        y, st = layer.apply(params, x[:, :, t:t + 1], st, stream=True)
        np.testing.assert_allclose(y[:, :, 0], full[:, :, t], atol=2e-5)
    # one update a row a step, on top of what the prime counted
    assert int(st["gdn_stats"][2]) == 2 * (6 + (prime == 1))


def test_bfloat16_keeps_the_state_in_float32():
    layer, params = _layer()
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                    params)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, F, 70)),
                    jnp.bfloat16)
    y, st = layer.apply(params, x, {}, stream=True)
    assert y.dtype == jnp.bfloat16 and st["gdn_s"].dtype == jnp.float32
    assert st["gdn_conv"].dtype == jnp.bfloat16
    full, _ = layer.apply(
        jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params),
        x.astype(jnp.float32), {})
    assert float(jnp.abs(y.astype(jnp.float32) - full).max()) \
        < 0.05 * float(jnp.abs(full).max())


def test_what_the_layer_declares():
    layer, _ = _layer()
    assert layer.carries_recurrent_state and layer.supports_streaming
    assert slot_leaves(layer) == (
        SlotLeaf("gdn_s", (H, DK, DV), "float32"),
        SlotLeaf("gdn_conv", (3, H * (2 * DK + DV)), None))
    assert sum(leaf.row_bytes("bfloat16") for leaf in slot_leaves(layer)) \
        == 4 * H * DK * DV + 2 * 3 * H * (2 * DK + DV)
    decl = stream_counters(layer)
    assert (decl.key, decl.kind) == ("gdn_stats", "linear_attn")
    assert decl.fields == ("scanned_positions", "fed_positions",
                           "state_updates") and decl.host is None
    assert slot_leaves(SelfAttentionLayer()) == ()
    assert stream_counters(SelfAttentionLayer()) is None


def test_the_configuration_round_trips():
    layer = GatedDeltaNetLayer(n_out=F, n_heads=H, key_dim=DK,
                               value_dim=DV, conv_kernel=4,
                               allow_neg_eigval=False, eps=1e-5)
    again = layer_from_dict(layer_to_dict(layer))
    assert again == layer and type(again) is GatedDeltaNetLayer
    from deeplearning4j_tpu.nn.conf.network import (
        ComputationGraphConfiguration)
    from deeplearning4j_tpu.zoo import HybridLinearTransformer
    conf = HybridLinearTransformer(_TINY, max_length=64).conf()
    back = ComputationGraphConfiguration.from_json(conf.to_json())
    assert back.to_json() == conf.to_json()
    kinds = [type(v.layer).__name__ for n, v in back.vertices.items()
             if n.startswith(("gdn", "attn"))]
    assert kinds == ["GatedDeltaNetLayer"] * 3 + ["SelfAttentionLayer"]


_TINY = dict(
    vocab_size=40, hidden_size=32, intermediate_size=48,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
    layer_types=["linear_attention"] * 3 + ["full_attention"],
    attention_bias=False, rms_norm_eps=1e-6, linear_num_key_heads=4,
    linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rope_parameters={"rope_theta": None})


def test_the_zoo_model_is_built_from_layer_types():
    from deeplearning4j_tpu.zoo import HybridLinearTransformer
    zoo = HybridLinearTransformer(dict(_TINY, num_hidden_layers=2),
                                  max_length=64)
    assert zoo.layer_kinds == ["linear_attention"] * 2
    attn = HybridLinearTransformer(_TINY, 64).conf().vertices["attn3"].layer
    assert (attn.has_bias, attn.qk_norm, attn.rope, attn.n_kv_heads) == \
        (False, True, False, 4)
    with pytest.raises(ValueError, match="layer_types"):
        HybridLinearTransformer(dict(_TINY, num_hidden_layers=5), 64)
    with pytest.raises(ValueError, match="unknown kinds"):
        HybridLinearTransformer(
            dict(_TINY, layer_types=["sliding_attention"] * 4), 64)


# ------------------------------------------- SelfAttentionLayer's new fields
def _attention(**kw):
    layer = SelfAttentionLayer(n_out=32, n_heads=4, cache_length=48, **kw)
    params, _ = layer.init(jax.random.PRNGKey(1),
                           InputType.recurrent(32, 16))
    return layer, params


def _plain_attention(p, x, heads, qk_norm, eps=1e-6):
    """x [T, E]: causal softmax attention in float64, no biases."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    x = np.asarray(x, np.float64)
    t, e = x.shape
    d = e // heads

    def proj(name):
        y = x @ p["W" + name]
        if qk_norm and name in "qk":
            y = y / np.sqrt((y * y).mean(-1, keepdims=True) + eps) \
                * p[name + "_norm"]
        return y.reshape(t, heads, d).transpose(1, 0, 2)

    q, k, v = proj("q"), proj("k"), proj("v")
    s = q @ k.transpose(0, 2, 1) / np.sqrt(d)
    s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
    a = np.exp(s - s.max(-1, keepdims=True))
    a /= a.sum(-1, keepdims=True)
    return (a @ v).transpose(1, 0, 2).reshape(t, e) @ p["Wo"]


@pytest.mark.parametrize("stream", [False, True])
def test_no_biases_and_the_qk_norm_against_a_plain_attention(stream):
    layer, params = _attention(has_bias=False, qk_norm=True)
    assert sorted(params) == ["Wk", "Wo", "Wq", "Wv", "k_norm", "q_norm"]
    rng = np.random.default_rng(0)
    params["q_norm"] = jnp.asarray(1 + 0.3 * rng.normal(size=32),
                                   jnp.float32)
    params["k_norm"] = jnp.asarray(1 + 0.3 * rng.normal(size=32),
                                   jnp.float32)
    x = jnp.asarray(rng.normal(size=(1, 32, 20)), jnp.float32)
    y, _ = layer.apply(params, x, {}, stream=stream)
    np.testing.assert_allclose(
        np.asarray(y)[0].T, _plain_attention(params, np.asarray(x)[0].T,
                                             4, True), atol=2e-5)


def test_the_defaults_are_the_layer_as_it_was():
    """No new leaf, the old fields' dictionary loads, and the output is
    bit for bit what the layer's arithmetic before this change gives."""
    from deeplearning4j_tpu.parallel.sequence import blockwise_attention
    layer, params = _attention()
    assert sorted(params) == ["Wk", "Wo", "Wq", "Wv", "bk", "bo", "bq",
                              "bv"]
    assert (layer.has_bias, layer.qk_norm, layer.stream_query_block) == \
        (True, False, None)
    old = {k: v for k, v in layer_to_dict(layer).items()
           if k not in ("has_bias", "qk_norm", "qk_norm_eps",
                        "stream_query_block")}
    assert layer_from_dict(old) == dataclasses.replace(layer)
    rng = np.random.default_rng(1)
    params = {k: jnp.asarray(rng.normal(size=v.shape) * 0.2, jnp.float32)
              for k, v in params.items()}
    x = jnp.asarray(rng.normal(size=(2, 32, 16)), jnp.float32)
    xt = jnp.transpose(x, (0, 2, 1))

    def proj(name):
        y = xt @ params["W" + name] + params["b" + name]
        return y.reshape(2, 16, 4, 8).transpose(0, 2, 1, 3)

    o = blockwise_attention(proj("q"), proj("k"), proj("v"), causal=True,
                            block_size=512, key_mask=None, window=None)
    o = o.transpose(0, 2, 1, 3).reshape(2, 16, 32)
    want = jnp.transpose(o @ params["Wo"] + params["bo"], (0, 2, 1))
    y, _ = layer.apply(params, x, {})
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))


def test_a_prime_in_query_blocks_is_the_prime_at_once():
    whole, params = _attention(has_bias=False, qk_norm=True)
    blocked = dataclasses.replace(whole, stream_query_block=8)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 32, 32)),
                    jnp.float32)
    for pad in (None, jnp.int32(5)):
        a, sa = whole.apply(params, x, {}, stream=True, pad_left=pad)
        b, sb = blocked.apply(params, x, {}, stream=True, pad_left=pad)
        np.testing.assert_allclose(a, b, atol=1e-6)
        np.testing.assert_array_equal(sa["kv_k"], sb["kv_k"])
    # a chunk no wider than a block, or not of whole blocks, goes at once
    short = x[:, :, :6]
    np.testing.assert_array_equal(
        whole.apply(params, short, {}, stream=True)[0],
        blocked.apply(params, short, {}, stream=True)[0])
