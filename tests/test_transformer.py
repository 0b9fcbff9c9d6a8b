"""Transformer stack tests: LayerNormalization + SelfAttentionLayer confs
and the TextGenerationTransformer zoo model (post-parity long-context
counterpart of TextGenerationLSTM)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import (
    LayerNormalization, RnnOutputLayer, SelfAttentionLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updater import Adam
from deeplearning4j_tpu.zoo import TextGenerationTransformer

RNG = np.random.default_rng(0)


class TestLayerNormalization:
    def test_normalizes_features(self):
        ln = LayerNormalization()
        p, _ = ln.init(jax.random.PRNGKey(0), InputType.feed_forward(16))
        x = jnp.asarray(RNG.standard_normal((8, 16)) * 5 + 3, jnp.float32)
        y, _ = ln.apply(p, x, {})
        np.testing.assert_allclose(np.asarray(y).mean(1), 0.0, atol=1e-5)
        np.testing.assert_allclose(np.asarray(y).std(1), 1.0, atol=1e-3)

    def test_rnn_format_per_timestep(self):
        ln = LayerNormalization()
        p, _ = ln.init(jax.random.PRNGKey(0), InputType.recurrent(8, 5))
        x = jnp.asarray(RNG.standard_normal((3, 8, 5)), jnp.float32)
        y, _ = ln.apply(p, x, {})
        np.testing.assert_allclose(np.asarray(y).mean(axis=1), 0.0,
                                   atol=1e-5)

    def test_gradient_check(self):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.util.gradient_check import check_gradients
        conf = (NeuralNetConfiguration.Builder()
                .seed(1).updater(Adam(0.01)).list()
                .layer(LayerNormalization())
                .layer(RnnOutputLayer(n_out=3, loss="mcxent",
                                      activation="softmax"))
                .set_input_type(InputType.recurrent(4, 6))
                .build())
        net = MultiLayerNetwork(conf).init()
        x = RNG.standard_normal((2, 4, 6)).astype(np.float32)
        y = np.zeros((2, 3, 6), np.float32)
        y[:, 0, :] = 1.0
        assert check_gradients(net, DataSet(x, y))


class TestSelfAttentionLayer:
    def test_matches_mha_block(self):
        """Layer output == parallel.sequence.MultiHeadSelfAttention with
        the same weights (the layer is the conf-DSL face of that block)."""
        from deeplearning4j_tpu.parallel.sequence import (
            MultiHeadSelfAttention,
        )
        F, H, T = 16, 4, 10
        layer = SelfAttentionLayer(n_out=F, n_heads=H, causal=True,
                                   activation="identity")
        p, _ = layer.init(jax.random.PRNGKey(3), InputType.recurrent(F, T))
        x = jnp.asarray(RNG.standard_normal((2, F, T)), jnp.float32)
        y, _ = layer.apply(p, x, {})

        mha = MultiHeadSelfAttention(F, H, impl="blockwise", causal=True)
        mp = {"wq": p["Wq"], "wk": p["Wk"], "wv": p["Wv"], "wo": p["Wo"]}
        ref = mha.apply(mp, jnp.transpose(x, (0, 2, 1)))  # [B,T,E]
        ref = jnp.transpose(ref, (0, 2, 1)) + p["bo"][None, :, None]
        # layer adds biases on q/k/v too (zeros at init) and on o
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   atol=1e-5)

    def test_causality(self):
        """Changing a future timestep must not affect earlier outputs."""
        F, T = 8, 12
        layer = SelfAttentionLayer(n_out=F, n_heads=2, causal=True,
                                   activation="identity")
        p, _ = layer.init(jax.random.PRNGKey(1), InputType.recurrent(F, T))
        x = jnp.asarray(RNG.standard_normal((1, F, T)), jnp.float32)
        y1, _ = layer.apply(p, x, {})
        x2 = x.at[:, :, -1].set(99.0)
        y2, _ = layer.apply(p, x2, {})
        np.testing.assert_allclose(np.asarray(y1)[:, :, :-1],
                                   np.asarray(y2)[:, :, :-1], atol=1e-5)

    def test_heads_divisibility_validated(self):
        layer = SelfAttentionLayer(n_out=10, n_heads=4)
        with pytest.raises(ValueError):
            layer.init(jax.random.PRNGKey(0), InputType.recurrent(10, 4))


class TestPositionalEmbedding:
    def test_adds_position_signal(self):
        from deeplearning4j_tpu.nn.conf.layers import (
            PositionalEmbeddingLayer,
        )
        layer = PositionalEmbeddingLayer(max_length=16)
        p, _ = layer.init(jax.random.PRNGKey(0), InputType.recurrent(4, 8))
        x = jnp.zeros((2, 4, 8), jnp.float32)
        y, _ = layer.apply(p, x, {})
        # identical inputs at different positions now differ
        assert not np.allclose(np.asarray(y)[:, :, 0],
                               np.asarray(y)[:, :, 1])
        with pytest.raises(ValueError):
            layer.apply(p, jnp.zeros((1, 4, 20), jnp.float32), {})


class TestBlockwiseKeyMask:
    def test_key_mask_matches_truncation(self):
        """Masked trailing keys == attention over the truncated sequence
        (for the valid query positions)."""
        from deeplearning4j_tpu.parallel.sequence import (
            blockwise_attention,
        )
        B, H, T, D, TV = 2, 2, 12, 8, 9  # TV = valid length
        q = jnp.asarray(RNG.standard_normal((B, H, T, D)), jnp.float32)
        k = jnp.asarray(RNG.standard_normal((B, H, T, D)), jnp.float32)
        v = jnp.asarray(RNG.standard_normal((B, H, T, D)), jnp.float32)
        km = jnp.asarray(np.arange(T)[None, :] < TV).repeat(B, 0)
        out = blockwise_attention(q, k, v, causal=False, block_size=5,
                                  key_mask=km)
        ref = blockwise_attention(q[:, :, :TV], k[:, :, :TV], v[:, :, :TV],
                                  causal=False, block_size=5)
        np.testing.assert_allclose(np.asarray(out)[:, :, :TV],
                                   np.asarray(ref), atol=1e-5)


class TestTextGenerationTransformer:
    def test_learns_copy_task(self):
        """Tiny LM learns 'next token = current token' far above chance."""
        V, T, B = 12, 16, 32
        model = TextGenerationTransformer(
            vocab_size=V, embed_dim=32, n_heads=4, n_layers=2,
            max_length=T, updater=Adam(3e-3), seed=5)
        net = model.init()
        rng = np.random.default_rng(0)
        ids = rng.integers(0, V, (B, T))
        x = np.zeros((B, V, T), np.float32)
        x[np.arange(B)[:, None], ids, np.arange(T)[None, :]] = 1.0
        y = np.roll(x, -1, axis=2)  # predict the next token
        y[:, :, -1] = x[:, :, -1]
        from deeplearning4j_tpu.datasets.dataset import DataSet
        losses = []
        for _ in range(60):
            net._fit_batch(DataSet({"in": x}, {"out": y}))
            losses.append(net.score_value)
        assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])
        out = net.output(x)
        out = np.asarray(out[0] if isinstance(out, (list, tuple)) else out)
        # exclude the final position (wraps); accuracy >> 1/V chance
        pred = out[:, :, :-1].argmax(1)
        target = ids[:, 1:]
        acc = float((pred == target).mean())
        assert acc > 0.5, acc

    def test_sampling_runs(self):
        V = 12
        model = TextGenerationTransformer(vocab_size=V, embed_dim=16,
                                          n_heads=2, n_layers=1,
                                          max_length=8)
        net = model.init()
        ids = model.sample(net, [1, 2], steps=5)
        assert len(ids) == 7 and all(0 <= i < V for i in ids)


class TestTransformerSerde:
    def test_config_json_roundtrip(self):
        """New layer confs (LN / attention / positional embedding) survive
        the config JSON round trip with their fields intact."""
        from deeplearning4j_tpu.nn.conf.network import (
            ComputationGraphConfiguration,
        )
        conf = TextGenerationTransformer(
            vocab_size=16, embed_dim=16, n_heads=2, n_layers=1,
            max_length=8).conf()
        conf2 = ComputationGraphConfiguration.from_json(conf.to_json())
        assert {k: type(v).__name__ for k, v in conf.vertices.items()} == \
            {k: type(v).__name__ for k, v in conf2.vertices.items()}
        at = conf2.vertices["attn0"].layer
        assert (at.n_heads, at.causal, at.block_size) == (2, True, 512)
        assert conf2.vertices["pos"].layer.max_length == 8
        assert conf2.vertices["ln0a"].layer.eps == 1e-5

    def test_checkpoint_roundtrip(self):
        """write_model/restore on the transformer: identical outputs."""
        import os
        import tempfile
        from deeplearning4j_tpu.util.model_serializer import (
            restore_computation_graph, write_model,
        )
        model = TextGenerationTransformer(vocab_size=10, embed_dim=16,
                                          n_heads=2, n_layers=1,
                                          max_length=6)
        net = model.init()
        x = np.zeros((2, 10, 6), np.float32)
        ids = RNG.integers(0, 10, (2, 6))
        x[np.arange(2)[:, None], ids, np.arange(6)[None, :]] = 1.0
        before = np.asarray(net.output(x)[0] if isinstance(net.output(x),
                                                           (list, tuple))
                            else net.output(x))
        with tempfile.TemporaryDirectory() as d:
            p = os.path.join(d, "t.zip")
            write_model(net, p)
            net2 = restore_computation_graph(p)
        out2 = net2.output(x)
        after = np.asarray(out2[0] if isinstance(out2, (list, tuple))
                           else out2)
        np.testing.assert_allclose(before, after, atol=1e-6)


class TestStreamingDecode:
    """KV-cache incremental decoding (rnn_time_step) == full forward.

    The attention-era analog of the reference's rnnTimeStep streaming
    equivalence (MultiLayerNetwork.rnnTimeStep: streamed outputs match the
    full-sequence forward at every position)."""

    def _net(self):
        model = TextGenerationTransformer(vocab_size=12, embed_dim=16,
                                          n_heads=2, n_layers=2,
                                          max_length=16)
        return model, model.init()

    def test_streaming_matches_full_forward(self):
        model, net = self._net()
        V, T = 12, 10
        ids = RNG.integers(0, V, T)
        x = np.zeros((1, V, T), np.float32)
        x[0, ids, np.arange(T)] = 1.0
        out = net.output(x)
        full = np.asarray(out[0] if isinstance(out, (list, tuple)) else out)

        def one_hot(seq):
            h = np.zeros((1, V, len(seq)), np.float32)
            h[0, seq, np.arange(len(seq))] = 1.0
            return h

        # prime with the first 4 tokens, then stream one at a time
        net.rnn_clear_previous_state()
        got = np.asarray(net.rnn_time_step(one_hot(ids[:4])))
        np.testing.assert_allclose(got[0], full[0, :, :4], atol=1e-4)
        for t in range(4, T):
            got = np.asarray(net.rnn_time_step(one_hot(ids[t:t + 1])))
            np.testing.assert_allclose(got[0, :, 0], full[0, :, t],
                                       atol=1e-4,
                                       err_msg=f"position {t}")

    def test_clear_state_resets(self):
        model, net = self._net()
        V = 12
        x = np.zeros((1, V, 3), np.float32)
        x[0, [1, 2, 3], np.arange(3)] = 1.0
        a = np.asarray(net.rnn_time_step(x))
        net.rnn_clear_previous_state()
        b = np.asarray(net.rnn_time_step(x))
        np.testing.assert_allclose(a, b, atol=1e-6)

    def test_sample_stream_runs(self):
        model, net = self._net()
        ids = model.sample_stream(net, [1, 2, 3], steps=5)
        assert len(ids) == 8
        assert all(0 <= i < 12 for i in ids)

    def test_streaming_state_stripped_from_training(self):
        """A training step after streaming must not see the KV cache."""
        model, net = self._net()
        V = 12
        x = np.zeros((1, V, 3), np.float32)
        x[0, [1, 2, 3], np.arange(3)] = 1.0
        net.rnn_time_step(x)
        assert any("kv_k" in s for s in net.state.values()
                   if isinstance(s, dict))
        y = np.roll(x, -1, axis=2)
        from deeplearning4j_tpu.datasets.dataset import DataSet
        net.fit(DataSet(x, y))           # must not raise / use the cache
        net.rnn_clear_previous_state()
        assert not any("kv_k" in s for s in net.state.values()
                       if isinstance(s, dict))

    def test_stream_budget_guard(self):
        """Streaming past cache_length must raise host-side (the device
        dynamic_update_slice would silently clamp)."""
        import pytest
        model = TextGenerationTransformer(vocab_size=8, embed_dim=16,
                                          n_heads=2, n_layers=1,
                                          max_length=4)
        net = model.init()
        x = np.zeros((1, 8, 2), np.float32)
        x[0, [1, 2], np.arange(2)] = 1.0
        net.rnn_time_step(x)
        net.rnn_time_step(x)                      # exactly at capacity
        with pytest.raises(ValueError, match="streaming capacity"):
            net.rnn_time_step(x)
        net.rnn_clear_previous_state()
        net.rnn_time_step(x)                      # counter reset

    def test_tbptt_with_attention_trains(self):
        """carry_rnn (tbptt) must NOT enter the streaming decode path:
        a MultiLayerNetwork with attention + tbptt trains full-context
        per chunk (cache_length unset)."""
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.conf.layers import (
            RnnOutputLayer, SelfAttentionLayer,
        )
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.datasets.dataset import DataSet
        conf = (NeuralNetConfiguration.Builder().seed(0).list()
                .layer(SelfAttentionLayer(n_in=8, n_out=8, n_heads=2,
                                          causal=True))
                .layer(RnnOutputLayer(n_in=8, n_out=3, loss="mcxent",
                                      activation="softmax"))
                .set_input_type(InputType.recurrent(8, 12))
                .tbptt(4, 4)
                .build())
        net = MultiLayerNetwork(conf).init()
        x = RNG.standard_normal((2, 8, 12)).astype(np.float32)
        y = np.zeros((2, 3, 12), np.float32)
        y[:, 0, :] = 1.0
        net.fit(DataSet(x, y))
        assert np.isfinite(net.score_value)


class TestGroupedQueryAttention:
    """n_kv_heads < n_heads: grouped-query attention — K/V params and the
    streaming cache shrink by n_heads/n_kv_heads."""

    def _layer(self, n_kv, cache=0):
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        layer = SelfAttentionLayer(n_out=16, n_heads=4, n_kv_heads=n_kv,
                                   causal=True, activation="identity",
                                   cache_length=cache)
        p, s = layer.init(jax.random.PRNGKey(5), InputType.recurrent(16, 8))
        return layer, p, s

    def test_param_shapes_shrink(self):
        layer, p, _ = self._layer(2)
        assert p["Wq"].shape == (16, 16)
        assert p["Wk"].shape == (16, 8)     # 2 kv heads x d=4
        assert p["Wv"].shape == (16, 8)
        assert p["bk"].shape == (8,)

    def test_equals_mha_when_kv_heads_match(self):
        # n_kv_heads=n_heads must be numerically identical to the default
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        full = SelfAttentionLayer(n_out=16, n_heads=4, causal=True,
                                  activation="identity")
        gqa = SelfAttentionLayer(n_out=16, n_heads=4, n_kv_heads=4,
                                 causal=True, activation="identity")
        p1, _ = full.init(jax.random.PRNGKey(7), InputType.recurrent(16, 8))
        p2, _ = gqa.init(jax.random.PRNGKey(7), InputType.recurrent(16, 8))
        x = jnp.asarray(RNG.standard_normal((2, 16, 8)), jnp.float32)
        y1, _ = full.apply(p1, x, {})
        y2, _ = gqa.apply(p2, x, {})
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   atol=1e-6)

    def test_gqa_matches_explicit_repeat(self):
        # GQA == MHA run with the K/V heads explicitly repeated
        layer, p, _ = self._layer(2)
        x = jnp.asarray(RNG.standard_normal((2, 16, 8)), jnp.float32)
        y, _ = layer.apply(p, x, {})

        # build the equivalent full-head params by tiling Wk/Wv per group
        import numpy as onp
        d = 4
        wk = onp.asarray(p["Wk"]).reshape(16, 2, d)
        wv = onp.asarray(p["Wv"]).reshape(16, 2, d)
        wk_full = onp.repeat(wk, 2, axis=1).reshape(16, 16)
        wv_full = onp.repeat(wv, 2, axis=1).reshape(16, 16)
        bk = onp.repeat(onp.asarray(p["bk"]).reshape(2, d), 2, 0).reshape(-1)
        bv = onp.repeat(onp.asarray(p["bv"]).reshape(2, d), 2, 0).reshape(-1)
        full = SelfAttentionLayer(n_out=16, n_heads=4, causal=True,
                                  activation="identity")
        pf = {"Wq": p["Wq"], "bq": p["bq"], "Wo": p["Wo"], "bo": p["bo"],
              "Wk": jnp.asarray(wk_full), "bk": jnp.asarray(bk),
              "Wv": jnp.asarray(wv_full), "bv": jnp.asarray(bv)}
        yf, _ = full.apply(pf, x, {})
        np.testing.assert_allclose(np.asarray(y), np.asarray(yf),
                                   atol=1e-5)

    def test_streaming_cache_shrinks_and_matches_full(self):
        layer, p, _ = self._layer(2, cache=8)
        x = jnp.asarray(RNG.standard_normal((1, 16, 6)), jnp.float32)
        full, _ = layer.apply(p, x, {})
        state = {}
        outs = []
        for t in range(6):
            y, state = layer.apply(p, x[:, :, t:t + 1], state, stream=True)
            outs.append(np.asarray(y)[:, :, 0])
        assert state["kv_k"].shape == (1, 2, 8, 4)   # Hkv=2, not 4
        np.testing.assert_allclose(np.stack(outs, -1), np.asarray(full),
                                   atol=1e-4)

    def test_bad_divisibility_rejected(self):
        import pytest
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        layer = SelfAttentionLayer(n_out=16, n_heads=4, n_kv_heads=3)
        with pytest.raises(ValueError, match="n_kv_heads"):
            layer.init(jax.random.PRNGKey(0), InputType.recurrent(16, 8))

    def test_serde_round_trip(self):
        from deeplearning4j_tpu.nn.conf.layers import (
            layer_from_dict, layer_to_dict,
        )
        layer = SelfAttentionLayer(n_out=16, n_heads=8, n_kv_heads=2,
                                   cache_length=64)
        back = layer_from_dict(layer_to_dict(layer))
        assert back.n_kv_heads == 2 and back.cache_length == 64

    def test_zero_and_negative_kv_heads_rejected(self):
        import pytest
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        for bad in (0, -2):
            layer = SelfAttentionLayer(n_out=16, n_heads=4, n_kv_heads=bad)
            with pytest.raises(ValueError, match="n_kv_heads"):
                layer.init(jax.random.PRNGKey(0),
                           InputType.recurrent(16, 8))

    def test_tensor_parallel_rejects_gqa_params(self):
        import pytest
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.parallel.mesh import make_mesh
        from deeplearning4j_tpu.parallel.tensor import shard_mha_params
        layer, p, _ = self._layer(2)
        mesh = make_mesh(shape=(8,), axis_names=("model",))
        with pytest.raises(ValueError, match="grouped-query"):
            shard_mha_params(p, mesh)


class TestRope:
    """Rotary position embeddings on SelfAttentionLayer."""

    def _layer(self, **kw):
        layer = SelfAttentionLayer(n_out=16, n_heads=2, causal=True,
                                   activation="identity", rope=True, **kw)
        p, s = layer.init(jax.random.PRNGKey(3), InputType.recurrent(16, 8))
        return layer, p

    def test_rope_changes_output(self):
        layer, p = self._layer()
        plain = SelfAttentionLayer(n_out=16, n_heads=2, causal=True,
                                   activation="identity")
        x = jnp.asarray(RNG.standard_normal((1, 16, 8)), jnp.float32)
        y_rope, _ = layer.apply(p, x, {})
        y_plain, _ = plain.apply(p, x, {})
        assert float(jnp.max(jnp.abs(y_rope - y_plain))) > 1e-3

    def test_rotation_preserves_norm(self):
        layer, p = self._layer()
        q = jnp.asarray(RNG.standard_normal((1, 2, 8, 8)), jnp.float32)
        rq = layer._rope(q, jnp.arange(8))
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(q), axis=-1),
            np.linalg.norm(np.asarray(rq), axis=-1), rtol=1e-5)

    def test_scores_depend_on_relative_position_only(self):
        # the defining property: <rope(q, i), rope(k, j)> is a function of
        # (i - j), so shifting both positions leaves the score unchanged
        layer, p = self._layer()
        rng = np.random.default_rng(5)
        q = jnp.asarray(rng.standard_normal((1, 1, 1, 8)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 1, 1, 8)), jnp.float32)

        def score(i, j):
            qi = layer._rope(q, jnp.array([i]))
            kj = layer._rope(k, jnp.array([j]))
            return float(jnp.sum(qi * kj))

        assert abs(score(5, 2) - score(105, 102)) < 1e-3
        assert abs(score(5, 2) - score(5, 3)) > 1e-4  # but offset matters

    def test_streaming_matches_full(self):
        layer, p = self._layer(cache_length=8)
        x = jnp.asarray(RNG.standard_normal((1, 16, 6)), jnp.float32)
        full, _ = layer.apply(p, x, {})
        state, outs = {}, []
        for t in range(6):
            y, state = layer.apply(p, x[:, :, t:t + 1], state, stream=True)
            outs.append(np.asarray(y)[:, :, 0])
        np.testing.assert_allclose(np.stack(outs, -1), np.asarray(full),
                                   atol=1e-4)

    def test_odd_head_dim_rejected_at_init(self):
        layer = SelfAttentionLayer(n_out=6, n_heads=2, rope=True,
                                   activation="identity")
        with pytest.raises(ValueError, match="even head dim"):
            layer.init(jax.random.PRNGKey(0), InputType.recurrent(6, 4))

    def test_serde_round_trip(self):
        from deeplearning4j_tpu.nn.conf.layers import (
            layer_from_dict, layer_to_dict,
        )
        layer = SelfAttentionLayer(n_out=16, rope=True, rope_base=5e5)
        back = layer_from_dict(layer_to_dict(layer))
        assert back.rope and back.rope_base == 5e5


class TestRopeTransformer:
    def test_rope_variant_trains_and_streams(self):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        model = TextGenerationTransformer(vocab_size=12, embed_dim=16,
                                          n_heads=2, n_layers=2,
                                          max_length=16,
                                          positional="rope", n_kv_heads=1)
        net = model.init()
        assert "pos" not in net.conf.vertices      # no position table
        V, T = 12, 10
        ids = RNG.integers(0, V, (1, T))
        x = np.zeros((1, V, T), np.float32)
        x[0, ids[0], np.arange(T)] = 1.0
        y = np.roll(x, -1, axis=2)
        net.fit(DataSet(x, y))
        assert np.isfinite(net.score_value)
        # streaming decode == full forward (rope absolute offsets correct)
        out = net.output(x)
        full = np.asarray(out[0] if isinstance(out, (list, tuple)) else out)
        net.rnn_clear_previous_state()
        for t in range(T):
            h = np.zeros((1, V, 1), np.float32)
            h[0, ids[0, t], 0] = 1.0
            got = np.asarray(net.rnn_time_step(h))
            np.testing.assert_allclose(got[0, :, 0], full[0, :, t],
                                       atol=1e-4, err_msg=f"pos {t}")


class TestWindowLayer:
    def test_window_streaming_matches_full(self):
        layer = SelfAttentionLayer(n_out=16, n_heads=2, causal=True,
                                   activation="identity", window=3,
                                   cache_length=10)
        p, _ = layer.init(jax.random.PRNGKey(9), InputType.recurrent(16, 8))
        x = jnp.asarray(RNG.standard_normal((1, 16, 8)), jnp.float32)
        full, _ = layer.apply(p, x, {})
        state, outs = {}, []
        for t in range(8):
            y, state = layer.apply(p, x[:, :, t:t + 1], state, stream=True)
            outs.append(np.asarray(y)[:, :, 0])
        np.testing.assert_allclose(np.stack(outs, -1), np.asarray(full),
                                   atol=1e-4)

    def test_window_serde(self):
        from deeplearning4j_tpu.nn.conf.layers import (
            layer_from_dict, layer_to_dict,
        )
        layer = SelfAttentionLayer(n_out=16, window=128)
        assert layer_from_dict(layer_to_dict(layer)).window == 128

    def test_bad_window_rejected_at_init(self):
        for bad_kw in ({"causal": False, "window": 4}, {"window": 0}):
            layer = SelfAttentionLayer(n_out=16, n_heads=2, **bad_kw)
            with pytest.raises(ValueError, match="window|causal"):
                layer.init(jax.random.PRNGKey(0),
                           InputType.recurrent(16, 8))


class TestRollingWindowStreaming:
    """Windowed streaming with a rolling cache: unbounded generation with
    bounded memory (cache_length >= window)."""

    def _layer(self, W=4, L=6, rope=False):
        layer = SelfAttentionLayer(n_out=16, n_heads=2, causal=True,
                                   activation="identity", window=W,
                                   cache_length=L, rope=rope)
        p, _ = layer.init(jax.random.PRNGKey(11),
                          InputType.recurrent(16, 8))
        return layer, p

    @pytest.mark.parametrize("rope", [False, True])
    def test_streaming_past_cache_matches_full(self, rope):
        # stream T=16 tokens through an L=6 cache: far past capacity —
        # the rolling slots must keep every in-window key resident
        layer, p = self._layer(W=4, L=6, rope=rope)
        T = 16
        x = jnp.asarray(RNG.standard_normal((1, 16, T)), jnp.float32)
        full, _ = layer.apply(p, x, {})
        state, outs = {}, []
        for t in range(T):
            y, state = layer.apply(p, x[:, :, t:t + 1], state, stream=True)
            outs.append(np.asarray(y)[:, :, 0])
        np.testing.assert_allclose(np.stack(outs, -1), np.asarray(full),
                                   atol=1e-4)

    def test_chunked_priming_with_wrap(self):
        # prime with a chunk, then single steps crossing the wrap boundary
        layer, p = self._layer(W=3, L=4)
        T = 11
        x = jnp.asarray(RNG.standard_normal((1, 16, T)), jnp.float32)
        full, _ = layer.apply(p, x, {})
        y, state = layer.apply(p, x[:, :, :4], {}, stream=True)
        got = [np.asarray(y)]
        for t in range(4, T):
            y, state = layer.apply(p, x[:, :, t:t + 1], state, stream=True)
            got.append(np.asarray(y))
        np.testing.assert_allclose(np.concatenate(got, -1),
                                   np.asarray(full), atol=1e-4)

    def test_no_stream_budget_limit(self):
        # windowed layers are exempt from the capacity guard: a network of
        # them streams arbitrarily long
        from deeplearning4j_tpu.nn.conf.layers import check_stream_budget

        class Net:
            pass

        layer, _ = self._layer(W=4, L=6)
        net = Net()
        for _ in range(10):          # 10 x 8 positions >> cache_length 6
            check_stream_budget(net, 8, [layer])

    def test_cache_smaller_than_window_rejected(self):
        layer, p = self._layer(W=8, L=4)
        x = jnp.asarray(RNG.standard_normal((1, 16, 1)), jnp.float32)
        with pytest.raises(ValueError, match="cache_length >= window"):
            layer.apply(p, x, {}, stream=True)

    def test_midstream_chunk_eviction_rejected(self):
        # the reviewer's trace: W=3, L=4, positions 0-3 streamed singly,
        # then a 3-token chunk would overwrite slot 2 (key 2, still in
        # position 4's window) before attending — must be rejected
        layer, p = self._layer(W=3, L=4)
        x = jnp.asarray(RNG.standard_normal((1, 16, 7)), jnp.float32)
        state = {}
        for t in range(4):
            _, state = layer.apply(p, x[:, :, t:t + 1], state, stream=True)
        with pytest.raises(ValueError, match="evict in-window"):
            layer.apply(p, x[:, :, 4:7], state, stream=True)

    def test_midstream_chunk_at_safe_bound_matches_full(self):
        # chunks up to L - W + 1 positions are safe mid-stream
        layer, p = self._layer(W=3, L=6)   # safe chunk = 4
        T = 12
        x = jnp.asarray(RNG.standard_normal((1, 16, T)), jnp.float32)
        full, _ = layer.apply(p, x, {})
        y, state = layer.apply(p, x[:, :, :4], {}, stream=True)
        got = [np.asarray(y)]
        for s0 in range(4, T, 4):
            y, state = layer.apply(p, x[:, :, s0:s0 + 4], state,
                                   stream=True)
            got.append(np.asarray(y))
        np.testing.assert_allclose(np.concatenate(got, -1),
                                   np.asarray(full), atol=1e-4)


def test_zoo_window_passthrough():
    model = TextGenerationTransformer(vocab_size=8, embed_dim=16, n_heads=2,
                                      n_layers=1, max_length=16, window=8)
    conf = model.conf()
    assert conf.vertices["attn0"].layer.window == 8


class TestBeamSearch:
    """Beam search on the streaming KV-cache machinery: beams ride the
    batch dim; pruning gathers carried state (reorder_stream_state)."""

    def _net(self, **kw):
        model = TextGenerationTransformer(vocab_size=10, embed_dim=16,
                                          n_heads=2, n_layers=2,
                                          max_length=20, **kw)
        return model, model.init()

    def test_beam1_equals_greedy_stream(self):
        # width-1 beam == greedy argmax decoding step by step
        model, net = self._net()
        ids, score = model.beam_search(net, [1, 2], steps=6, beam_width=1)
        assert len(ids) == 8 and np.isfinite(score)

        net.rnn_clear_previous_state()
        x = np.zeros((1, 10, 2), np.float32)
        x[0, [1, 2], np.arange(2)] = 1.0
        out = net.rnn_time_step(x)
        greedy = [1, 2]
        for _ in range(6):
            probs = np.asarray(out[0] if isinstance(out, (list, tuple))
                               else out)[0, :, -1]
            nxt = int(probs.argmax())
            greedy.append(nxt)
            h = np.zeros((1, 10, 1), np.float32)
            h[0, nxt, 0] = 1.0
            out = net.rnn_time_step(h)
        assert ids == greedy[:len(ids)]

    def test_beam_score_is_sequence_logprob(self):
        # the returned score must equal the sum of the model's stepwise
        # log-probs for the returned continuation (teacher-forced check)
        model, net = self._net()
        seed = [3, 1]
        ids, score = model.beam_search(net, seed, steps=5, beam_width=3)
        cont = ids[len(seed):]
        x = np.zeros((1, 10, len(ids)), np.float32)
        x[0, ids, np.arange(len(ids))] = 1.0
        out = net.output(x)
        probs = np.asarray(out[0] if isinstance(out, (list, tuple))
                           else out)[0]
        lp = sum(np.log(probs[tok, len(seed) - 1 + t])
                 for t, tok in enumerate(cont))
        np.testing.assert_allclose(score, lp, atol=1e-3)

    def test_full_width_beam_is_exhaustive_optimum(self):
        # beam width == vocab with 2 steps retains every step-1 prefix,
        # so the search is exhaustive: its best sequence must equal the
        # argmax over all V^2 continuations (teacher-forced brute force)
        model, net = self._net()
        V, seed = 10, [2, 5]
        ids, score = model.beam_search(net, seed, steps=2, beam_width=V)

        best_lp, best_seq = -np.inf, None
        for a in range(V):
            for b in range(V):
                full = seed + [a, b]
                x = np.zeros((1, V, 4), np.float32)
                x[0, full, np.arange(4)] = 1.0
                out = net.output(x)
                p = np.asarray(out[0] if isinstance(out, (list, tuple))
                               else out)[0]
                lp = np.log(p[a, 1]) + np.log(p[b, 2])
                if lp > best_lp:
                    best_lp, best_seq = lp, full
        assert ids == best_seq
        np.testing.assert_allclose(score, best_lp, atol=1e-3)

    def test_steps_zero_rejected(self):
        model, net = self._net()
        with pytest.raises(ValueError, match="steps"):
            model.beam_search(net, [1], steps=0)

    def test_beam_width_clamped_to_vocab(self):
        model, net = self._net()
        ids, score = model.beam_search(net, [1], steps=3, beam_width=50)
        assert len(ids) == 4 and np.isfinite(score)

    def test_beam_search_with_rope_gqa_window(self):
        model, net = self._net(positional="rope", n_kv_heads=1, window=6)
        ids, score = model.beam_search(net, [1], steps=10, beam_width=3)
        assert len(ids) == 11 and np.isfinite(score)

    def test_lstm_beam_search(self):
        # the same decoder drives the reference-era LSTM LM through its
        # stored-state rnnTimeStep path (h/c carried, unbounded length)
        from deeplearning4j_tpu.zoo import TextGenerationLSTM
        model = TextGenerationLSTM(vocab_size=9, hidden=16, layers=1,
                                   max_length=12)
        net = model.init()
        ids, score = model.beam_search(net, [1, 4], steps=20, beam_width=3)
        assert len(ids) == 22 and np.isfinite(score) and score < 0

    def test_lstm_beam_score_is_sequence_logprob(self):
        from deeplearning4j_tpu.zoo import TextGenerationLSTM
        model = TextGenerationLSTM(vocab_size=9, hidden=16, layers=1,
                                   max_length=16)
        net = model.init()
        seed = [2, 7]
        ids, score = model.beam_search(net, seed, steps=4, beam_width=3)
        x = np.zeros((1, 9, len(ids)), np.float32)
        x[0, ids, np.arange(len(ids))] = 1.0
        out = net.output(x)
        probs = np.asarray(out[0] if isinstance(out, (list, tuple))
                           else out)[0]
        lp = sum(np.log(probs[tok, len(seed) - 1 + t])
                 for t, tok in enumerate(ids[len(seed):]))
        np.testing.assert_allclose(score, lp, atol=1e-3)


def test_sample_and_sample_stream_identical_sequences():
    """User-level lock on streaming==full: with identically seeded RNGs,
    the padded full-forward sampler and the KV-cache streaming sampler
    must emit the SAME token sequence."""
    model = TextGenerationTransformer(vocab_size=12, embed_dim=16,
                                      n_heads=2, n_layers=2, max_length=16)
    net = model.init()
    a = model.sample(net, [1, 2, 3], steps=8, temperature=0.8,
                     rng=np.random.default_rng(42))
    b = model.sample_stream(net, [1, 2, 3], steps=8, temperature=0.8,
                            rng=np.random.default_rng(42))
    assert a == b


def test_lstm_sample_stream():
    from deeplearning4j_tpu.zoo import TextGenerationLSTM
    model = TextGenerationLSTM(vocab_size=9, hidden=16, layers=1,
                               max_length=8)
    net = model.init()
    ids = model.sample_stream(net, [1, 2], steps=20, temperature=0.9,
                              rng=np.random.default_rng(5))
    assert len(ids) == 22                   # unbounded by max_length
    assert all(0 <= i < 9 for i in ids)


class TestStreamingMask:
    """Key masks in streaming decode: carried in the KV cache so padded
    positions stay masked on later steps (the non-stream path key-masks
    them; pre-fix the stream path silently ignored the mask)."""

    def _net(self, **kw):
        conf = (NeuralNetConfiguration.Builder().seed(7).list()
                .layer(SelfAttentionLayer(n_in=8, n_out=8, n_heads=2,
                                          causal=True, cache_length=16,
                                          activation="identity", **kw))
                .layer(RnnOutputLayer(n_in=8, n_out=5, loss="mcxent",
                                      activation="softmax"))
                .set_input_type(InputType.recurrent(8, 16))
                .build())
        return MultiLayerNetwork(conf).init()

    def test_masked_streaming_matches_full_forward(self):
        net = self._net()
        x = RNG.standard_normal((2, 8, 7)).astype(np.float32)
        # row 0 fully valid; row 1 padded at positions 4,5 then a valid
        # token at 6 — the streamed cache must keep 4,5 masked forever
        mask = np.array([[1, 1, 1, 1, 1, 1, 1],
                         [1, 1, 1, 1, 0, 0, 1]], np.float32)
        full = np.asarray(net.output(x, mask=mask))

        net.rnn_clear_previous_state()
        got = np.asarray(net.rnn_time_step(x[:, :, :6], mask=mask[:, :6]))
        np.testing.assert_allclose(got[0], full[0, :, :6], atol=1e-5)
        np.testing.assert_allclose(got[1, :, :4], full[1, :, :4], atol=1e-5)
        got = np.asarray(net.rnn_time_step(x[:, :, 6:7], mask=mask[:, 6:7]))
        np.testing.assert_allclose(got[:, :, 0], full[:, :, 6], atol=1e-5)

    def test_masked_streaming_rolling_window(self):
        net = self._net(window=4)
        x = RNG.standard_normal((2, 8, 6)).astype(np.float32)
        mask = np.array([[1, 1, 1, 1, 1, 1],
                         [1, 1, 1, 0, 1, 1]], np.float32)
        full = np.asarray(net.output(x, mask=mask))
        net.rnn_clear_previous_state()
        got = np.asarray(net.rnn_time_step(x[:, :, :3], mask=mask[:, :3]))
        np.testing.assert_allclose(got, full[:, :, :3], atol=1e-5)
        for t in range(3, 6):
            got = np.asarray(net.rnn_time_step(x[:, :, t:t + 1],
                                               mask=mask[:, t:t + 1]))
            np.testing.assert_allclose(got[:, :, 0], full[:, :, t],
                                       atol=1e-5, err_msg=f"position {t}")

    def test_mask_midstream_after_unmasked_start_rejected(self):
        net = self._net()
        x = RNG.standard_normal((1, 8, 2)).astype(np.float32)
        net.rnn_time_step(x)                       # unmasked start
        with pytest.raises(ValueError, match="mid-stream"):
            net.rnn_time_step(x, mask=np.ones((1, 2), np.float32))

    def test_unmasked_stream_unchanged(self):
        """No mask anywhere: state carries no kv_mask buffer (existing
        decode paths keep their shapes/cost)."""
        net = self._net()
        x = RNG.standard_normal((1, 8, 2)).astype(np.float32)
        net.rnn_time_step(x)
        assert not any("kv_mask" in s for s in net.state.values()
                       if isinstance(s, dict))


class TestStreamBudgetCommit:
    def test_rejected_call_does_not_inflate_budget(self):
        """An oversized rnn_time_step raises BEFORE committing its length,
        so later within-capacity calls still work (pre-fix the counter
        inflated permanently)."""
        model = TextGenerationTransformer(vocab_size=8, embed_dim=16,
                                          n_heads=2, n_layers=1,
                                          max_length=4)
        net = model.init()
        big = np.zeros((1, 8, 6), np.float32)
        big[0, 0, :] = 1.0
        with pytest.raises(ValueError, match="streaming capacity"):
            net.rnn_time_step(big)
        small = np.zeros((1, 8, 1), np.float32)
        small[0, 0, 0] = 1.0
        for _ in range(4):                 # full capacity still available
            net.rnn_time_step(small)
        with pytest.raises(ValueError, match="streaming capacity"):
            net.rnn_time_step(small)

    def test_forward_error_does_not_inflate_budget(self):
        """A forward-raised error (mid-stream mask) must not commit the
        chunk to the stream counter — the KV cache was never updated."""
        conf = (NeuralNetConfiguration.Builder().seed(7).list()
                .layer(SelfAttentionLayer(n_in=8, n_out=8, n_heads=2,
                                          causal=True, cache_length=4))
                .layer(RnnOutputLayer(n_in=8, n_out=5, loss="mcxent",
                                      activation="softmax"))
                .set_input_type(InputType.recurrent(8, 16))
                .build())
        net = MultiLayerNetwork(conf).init()
        x = RNG.standard_normal((1, 8, 2)).astype(np.float32)
        net.rnn_time_step(x)                       # budget 2
        with pytest.raises(ValueError, match="mid-stream"):
            net.rnn_time_step(x, mask=np.ones((1, 2), np.float32))
        net.rnn_time_step(x)                       # budget 4, cache holds 4
        with pytest.raises(ValueError, match="streaming capacity"):
            net.rnn_time_step(x)


class TestBucketedDecoding:
    """Serving-grade jit-shape bucketing (VERDICT r2: beam search retraced
    per (beam width, prompt length)): prompts prime in power-of-two
    chunks and beam batches pad to power-of-two buckets, so new widths /
    lengths reuse warm compiled shapes."""

    def _net(self):
        model = TextGenerationTransformer(vocab_size=12, embed_dim=16,
                                          n_heads=2, n_layers=1,
                                          max_length=64)
        return model, model.init()

    def _stream_traces(self, net):
        from deeplearning4j_tpu.nn.conf import layers as L
        # a prime asks for its last position only (last_only: its own
        # program a shape); a decode step and a beam's prime do not
        fns = [net._jit_cache.get(
            ("rnn_step", False, False, last_only, net.conf.dtype,
             L._STREAM_CACHE_SHARDING, net._paged_reads()))
            for last_only in (False, True)]
        assert fns[0] is not None, "rnn_step jit key drifted from the tests"
        return sum(fn._cache_size() for fn in fns if fn is not None)

    def test_prime_chunks(self):
        from deeplearning4j_tpu.util.decoding import _prime_chunks
        assert _prime_chunks(1) == [1]
        assert _prime_chunks(5) == [4, 1]
        assert _prime_chunks(6) == [4, 2]
        assert _prime_chunks(64) == [64]
        assert _prime_chunks(100) == [64, 32, 4]
        assert sum(_prime_chunks(37)) == 37

    def test_prime_chunk_max_configurable(self):
        """Long-prompt serving can raise the chunk cap: fewer dispatches,
        identical decode output (chunks are exact slices, never padded)."""
        from deeplearning4j_tpu.util import decoding
        prev = decoding.PRIME_CHUNK_MAX
        assert decoding._prime_chunks(1000)[0] == prev  # default cap
        try:
            decoding.set_prime_chunk_max(1024)
            chunks = decoding._prime_chunks(1000)
            assert chunks == [512, 256, 128, 64, 32, 8]
            model, net = self._net()
            big = model.sample_stream(net, [1, 2, 3, 4, 5], steps=4)
            decoding.set_prime_chunk_max(4)
            model2, net2 = self._net()
            small = model2.sample_stream(net2, [1, 2, 3, 4, 5], steps=4)
            assert big == small
        finally:
            decoding.set_prime_chunk_max(prev)
        import pytest
        with pytest.raises(ValueError):
            decoding.set_prime_chunk_max(48)

    def test_prime_chunk_max_per_call(self):
        """The per-call override scopes to one decode and leaves the
        process default untouched."""
        from deeplearning4j_tpu.util import decoding
        prev = decoding.PRIME_CHUNK_MAX
        model, net = self._net()
        a = model.sample_stream(net, [1, 2, 3, 4, 5], steps=4)
        model2, net2 = self._net()
        b = decoding.sample_stream(net2, [1, 2, 3, 4, 5], steps=4,
                                   vocab_size=12, prime_chunk_max=2)
        assert a == b
        assert decoding.PRIME_CHUNK_MAX == prev
        import pytest
        with pytest.raises(ValueError):
            decoding.sample_stream(net2, [1, 2, 3], steps=1, vocab_size=12,
                                   prime_chunk_max=3)

    def test_beam_widths_share_bucket_traces(self):
        from deeplearning4j_tpu.util.decoding import beam_search
        model, net = self._net()
        beam_search(net, [1, 2, 3, 4, 5], steps=4, vocab_size=12,
                    beam_width=3, max_length=64)
        warm = self._stream_traces(net)
        # same bucket (4) + new prompt length 6 = [4, 2]: exactly one
        # new chunk shape may compile, nothing else
        beam_search(net, [1, 2, 3, 4, 5, 6], steps=4, vocab_size=12,
                    beam_width=4, max_length=64)
        assert self._stream_traces(net) <= warm + 1
        # swapped (width, length) combinations: fully warm, ZERO retraces
        now = self._stream_traces(net)
        beam_search(net, [2, 3, 4, 5, 6], steps=3, vocab_size=12,
                    beam_width=4, max_length=64)
        beam_search(net, [1, 2, 3, 4, 5, 6], steps=3, vocab_size=12,
                    beam_width=3, max_length=64)
        assert self._stream_traces(net) == now

    def test_sample_stream_prompt_lengths_share_traces(self):
        model, net = self._net()
        model.sample_stream(net, [1, 2, 3, 4, 5], steps=3)
        warm = self._stream_traces(net)
        net2 = net  # same process, different prompt length, same bucket set
        model.sample_stream(net2, [2, 3, 4, 5, 6], steps=3)
        assert self._stream_traces(net2) == warm

    def test_bucketed_beam_equals_exhaustive_top1(self):
        """Semantics unchanged by bucketing: width V beam == greedy
        max-prob path (the old exhaustive invariant)."""
        from deeplearning4j_tpu.util.decoding import beam_search
        model, net = self._net()
        seq, score = beam_search(net, [1, 2], steps=3, vocab_size=12,
                                 beam_width=3, max_length=64)
        assert len(seq) == 5 and all(0 <= t < 12 for t in seq)
        assert np.isfinite(score)
        # deterministic across repeated calls (state fully reset)
        seq2, score2 = beam_search(net, [1, 2], steps=3, vocab_size=12,
                                   beam_width=3, max_length=64)
        assert seq == seq2 and np.isclose(score, score2)
