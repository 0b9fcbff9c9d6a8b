"""Left-padded single-dispatch priming (rnn_time_step pad_left / packed
accounting): an arbitrary-length prompt primes in ONE dispatch at a
bucketed shape with results identical to unpadded chunked priming.

Covers every streaming cache family: plain attention KV cache, rope +
GQA, rolling windowed cache, the learned positional-embedding offset,
and LSTM h/c carry-through (masked steps pass state unchanged), for both
MultiLayerNetwork and ComputationGraph."""

import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import (
    RnnOutputLayer, SelfAttentionLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updater import Adam
from deeplearning4j_tpu.util import decoding
from deeplearning4j_tpu.zoo import TextGenerationLSTM, TextGenerationTransformer

RNG = np.random.default_rng(7)


def _one_hot(seq, vocab):
    h = np.zeros((1, vocab, len(seq)), np.float32)
    h[0, list(seq), np.arange(len(seq))] = 1.0
    return h


def _prime_then_decode(net, ids, cont, vocab, *, padded):
    """Prime `ids` (padded single dispatch or chunked), then stream the
    `cont` tokens one at a time; returns the list of output arrays
    (primed last position + each decode step's distribution)."""
    net.rnn_clear_previous_state()
    if padded:
        out = decoding._prime_padded(net, ids, vocab)
    else:
        out = decoding._prime(net, ids, vocab)
    outs = [np.asarray(decoding._probs(out))[0, :, -1]]
    for t in cont:
        out = net.rnn_time_step(_one_hot([t], vocab))
        outs.append(np.asarray(decoding._probs(out))[0, :, 0])
    return outs


def _assert_padded_equals_chunked(net, ids, cont, vocab, atol=1e-5):
    a = _prime_then_decode(net, ids, cont, vocab, padded=False)
    b = _prime_then_decode(net, ids, cont, vocab, padded=True)
    assert len(a) == len(b)
    for i, (x, y) in enumerate(zip(a, b)):
        np.testing.assert_allclose(x, y, atol=atol,
                                   err_msg=f"output {i} diverged")


def _attn_net(**attn_kw):
    conf = (NeuralNetConfiguration.Builder()
            .seed(3).updater(Adam(1e-3)).weight_init("xavier").list()
            .layer(SelfAttentionLayer(n_out=16, n_heads=4, causal=True,
                                      activation="identity", **attn_kw))
            .layer(RnnOutputLayer(n_out=8, loss="mcxent",
                                  activation="softmax"))
            .set_input_type(InputType.recurrent(8, 16))
            .build())
    return MultiLayerNetwork(conf).init()


class TestPaddedPrimeMatchesChunked:
    def test_transformer_learned_positional(self):
        """CG path + PositionalEmbeddingLayer offset accounting."""
        model = TextGenerationTransformer(vocab_size=12, embed_dim=16,
                                          n_heads=2, n_layers=2,
                                          max_length=16)
        net = model.init()
        # prompt 5 -> bucket 8 (3 pads); decode 4 tokens
        _assert_padded_equals_chunked(net, [1, 2, 3, 4, 5], [6, 7, 2, 9],
                                      12, atol=1e-4)

    def test_attention_plain_cache(self):
        net = _attn_net(cache_length=16)
        _assert_padded_equals_chunked(net, [1, 2, 3], [4, 5, 6], 8)

    def test_attention_rope_gqa(self):
        net = _attn_net(cache_length=16, rope=True, n_kv_heads=2)
        _assert_padded_equals_chunked(net, [1, 2, 3, 4, 5], [6, 7], 8)

    def test_attention_rolling_window(self):
        """Windowed rolling cache: pads must consume neither slots nor
        absolute positions (continuation crosses the wrap boundary)."""
        net = _attn_net(cache_length=8, window=4)
        _assert_padded_equals_chunked(net, [1, 2, 3, 4, 5],
                                      [6, 7, 1, 2, 3, 4], 8)

    def test_lstm_stack(self):
        """Masked pad steps pass h/c through unchanged."""
        model = TextGenerationLSTM(vocab_size=10, hidden=12, layers=2,
                                   max_length=20)
        net = model.init()
        _assert_padded_equals_chunked(net, [1, 2, 3, 4, 5], [6, 7, 8], 10)

    def test_pad_left_zero_matches_plain(self):
        """pad_left=0 is a full-width chunk through the padded fn."""
        net = _attn_net(cache_length=16)
        ids = [1, 2, 3, 4]
        net.rnn_clear_previous_state()
        a = np.asarray(net.rnn_time_step(_one_hot(ids, 8)))
        net.rnn_clear_previous_state()
        b = np.asarray(net.rnn_time_step(_one_hot(ids, 8), pad_left=0))
        np.testing.assert_allclose(a, b, atol=1e-6)


class TestPaddedPrimeAccounting:
    def test_budget_counts_only_real_tokens(self):
        """Pads are free: a 5-token prompt in an 8-bucket consumes 5
        positions of a 8-capacity cache, leaving room for 3 more."""
        net = _attn_net(cache_length=8)
        x = _one_hot([0] * 3 + [1, 2, 3, 4, 5], 8)
        x[:, :, :3] = 0.0
        net.rnn_time_step(x, pad_left=3)
        assert net._stream_pos == 5
        for t in (6, 7, 1):                      # fills to exactly 8
            net.rnn_time_step(_one_hot([t], 8))
        with pytest.raises(ValueError, match="streaming capacity"):
            net.rnn_time_step(_one_hot([2], 8))

    def test_pad_and_mask_mutually_exclusive(self):
        net = _attn_net(cache_length=8)
        x = _one_hot([1, 2], 8)
        with pytest.raises(ValueError, match="mutually exclusive"):
            net.rnn_time_step(x, mask=np.ones((1, 2)), pad_left=1)

    def test_pad_out_of_range_rejected(self):
        net = _attn_net(cache_length=8)
        x = _one_hot([1, 2], 8)
        with pytest.raises(ValueError, match="out of range"):
            net.rnn_time_step(x, pad_left=2)
        with pytest.raises(ValueError, match="out of range"):
            net.rnn_time_step(x, pad_left=-1)

    def test_packed_after_masked_stream_rejected(self):
        """A packed chunk after masked streaming would leave kv_mask
        unset for its slots — must raise, not corrupt."""
        net = _attn_net(cache_length=8)
        net.rnn_time_step(_one_hot([1, 2], 8), mask=np.ones((1, 2)))
        with pytest.raises(ValueError, match="packed"):
            net.rnn_time_step(_one_hot([0, 3], 8), pad_left=1)

    def test_graph_multi_input_rejected(self):
        """pad_left needs a single streamed input."""
        model = TextGenerationTransformer(vocab_size=8, embed_dim=16,
                                          n_heads=2, n_layers=1,
                                          max_length=8)
        net = model.init()
        with pytest.raises(ValueError, match="single-input"):
            net.rnn_time_step({"in": _one_hot([1], 8),
                               "in2": _one_hot([2], 8)}, pad_left=0)


class TestPaddedPrimeServing:
    def _net(self):
        model = TextGenerationTransformer(vocab_size=12, embed_dim=16,
                                          n_heads=2, n_layers=1,
                                          max_length=64)
        return model, model.init()

    def _padded_traces(self, net):
        from deeplearning4j_tpu.nn.conf import layers as L
        fn = net._jit_cache.get(("rnn_step", True, False, True,
                                 net.conf.dtype,
                                 L._STREAM_CACHE_SHARDING,
                                 net._paged_reads()))
        assert fn is not None, "rnn_step jit key drifted from the tests"
        return fn._cache_size()

    def test_one_trace_per_bucket(self):
        """Different prompt lengths in one bucket share ONE compiled
        shape; a longer prompt adds exactly its new bucket."""
        model, net = self._net()
        model.sample_stream(net, [1, 2, 3], steps=2, prime_padded=True)
        warm = self._padded_traces(net)
        model.sample_stream(net, [1, 2, 3, 4], steps=2, prime_padded=True)
        assert self._padded_traces(net) == warm      # same bucket 4
        model.sample_stream(net, [1, 2, 3, 4, 5], steps=2,
                            prime_padded=True)
        assert self._padded_traces(net) == warm + 1  # bucket 8 compiles

    def test_beam_padded_equals_chunked(self):
        model, net = self._net()
        a = model.beam_search(net, [1, 2, 3, 4, 5], steps=4, beam_width=3)
        b = model.beam_search(net, [1, 2, 3, 4, 5], steps=4, beam_width=3,
                              prime_padded=True)
        assert a[0] == b[0]
        np.testing.assert_allclose(a[1], b[1], atol=1e-4)

    def test_bucket_capped_at_capacity(self):
        """A prompt whose pow2 bucket exceeds the smallest streaming
        capacity pads exactly to that capacity instead."""
        net = _attn_net(cache_length=6)
        ids = [1, 2, 3, 4, 5]                        # bucket 8 > cap 6
        a = _prime_then_decode(net, ids, [6], 8, padded=False)
        b = _prime_then_decode(net, ids, [6], 8, padded=True)
        for x, y in zip(a, b):
            np.testing.assert_allclose(x, y, atol=1e-5)

    def test_bucket_cap_applies_to_graphs(self):
        """The capacity cap must see a ComputationGraph's vertex-wrapped
        layers: a 17-token prompt in a max_length=24 transformer would
        otherwise round to bucket 32 and trip the positional-table
        capacity check that the prompt itself satisfies."""
        model = TextGenerationTransformer(vocab_size=10, embed_dim=16,
                                          n_heads=2, n_layers=1,
                                          max_length=24)
        net = model.init()
        ids = list(RNG.integers(0, 10, 17))
        a = _prime_then_decode(net, ids, [3, 4], 10, padded=False)
        b = _prime_then_decode(net, ids, [3, 4], 10, padded=True)
        for x, y in zip(a, b):
            np.testing.assert_allclose(x, y, atol=1e-4)

    def test_prompt_longer_than_capacity_falls_back_to_chunked(self):
        """Rolling-window streams accept prompts longer than the cache
        (chunked priming is unbounded); padded priming must fall back to
        chunks rather than raise on an oversized bucket."""
        net = _attn_net(cache_length=8, window=4)
        ids = list(RNG.integers(0, 8, 10))           # 10 > cache 8
        a = _prime_then_decode(net, ids, [3, 4], 8, padded=False)
        b = _prime_then_decode(net, ids, [3, 4], 8, padded=True)
        for x, y in zip(a, b):
            np.testing.assert_allclose(x, y, atol=1e-5)
