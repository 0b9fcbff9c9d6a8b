"""Serving-matrix composition (VERDICT r3 task 4): batched speculative
decoding with per-row acceptance, and beam search over [prompts x beams].

The bars set by the verdict: batched x speculative == per-prompt
speculative exactly (any draft kind, greedy), batched beam == per-prompt
beam, both trace-stable across bucket shapes.
"""

import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.layers import rewind_stream_state
from deeplearning4j_tpu.util import decoding
from deeplearning4j_tpu.zoo import TextGenerationTransformer

PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [2, 4]]


def _tfm(layers=1, embed=16, seed=12345, cache=64, positional="rope",
         vocab=12, window=None):
    return TextGenerationTransformer(vocab_size=vocab, embed_dim=embed,
                                     n_heads=2, n_layers=layers,
                                     max_length=cache, seed=seed,
                                     positional=positional, window=window)


class TestPerRowRewind:
    """The layer primitive batched speculation builds on: per-row rewind
    promotes kv_pos to a [N] vector; each row's stream then behaves as if
    only its own rejected tokens were never fed."""

    def test_per_row_rewind_equals_per_row_never_fed(self):
        model = _tfm()
        a = model.init()
        V = 12
        x = np.zeros((2, V, 3), np.float32)
        seqs = [[1, 2, 3], [4, 5, 6]]
        for b, s in enumerate(seqs):
            x[b, s, np.arange(3)] = 1.0
        a.rnn_time_step(x)
        # feed 3 more to both rows, then rewind row0 by 2, row1 by 1
        x2 = np.zeros((2, V, 3), np.float32)
        for b, s in enumerate([[7, 8, 9], [10, 1, 2]]):
            x2[b, s, np.arange(3)] = 1.0
        a.rnn_time_step(x2)
        rewind_stream_state(a, np.asarray([2, 1]))
        x3 = np.zeros((2, V, 2), np.float32)
        for b, s in enumerate([[3, 4], [5, 6]]):
            x3[b, s, np.arange(2)] = 1.0
        got = np.asarray(a.rnn_time_step(x3))

        # row references: single-row streams that never saw the rejects
        for b, (kept, nxt) in enumerate([([7], [3, 4]),
                                         ([10, 1], [5, 6])]):
            r = model.init()
            h = np.zeros((1, V, 3), np.float32)
            h[0, seqs[b], np.arange(3)] = 1.0
            r.rnn_time_step(h)
            hk = np.zeros((1, V, len(kept)), np.float32)
            hk[0, kept, np.arange(len(kept))] = 1.0
            r.rnn_time_step(hk)
            hn = np.zeros((1, V, 2), np.float32)
            hn[0, nxt, np.arange(2)] = 1.0
            want = np.asarray(r.rnn_time_step(hn))
            np.testing.assert_allclose(got[b], want[0], atol=1e-5)

    def test_per_row_rewind_rejects_learned_positions(self):
        model = _tfm(positional="learned")
        net = model.init()
        x = np.zeros((2, 12, 3), np.float32)
        x[:, 1, :] = 1.0
        net.rnn_time_step(x)
        with pytest.raises(ValueError, match="attention-only"):
            rewind_stream_state(net, np.asarray([1, 0]))

    def test_reorder_gathers_vector_kv_pos(self):
        model = _tfm()
        net = model.init()
        x = np.zeros((2, 12, 3), np.float32)
        x[0, 1, :] = 1.0
        x[1, 2, :] = 1.0
        net.rnn_time_step(x)
        rewind_stream_state(net, np.asarray([2, 0]))
        from deeplearning4j_tpu.nn.conf.layers import reorder_stream_state
        reorder_stream_state(net, np.asarray([1, 1]))
        for s in net.state.values():
            if isinstance(s, dict) and "kv_pos" in s:
                np.testing.assert_array_equal(np.asarray(s["kv_pos"]),
                                              [3, 3])


class TestBatchedSpeculative:
    @pytest.mark.parametrize("n_prompts", [1, 3, 4])
    def test_prompt_lookup_greedy_equals_per_prompt(self, n_prompts):
        """Batched x speculative == per-prompt speculative, draft-free
        prompt-lookup, greedy, mixed-length prompts."""
        model = _tfm(layers=2, embed=32, seed=3)
        net = model.init()
        prompts = [p * 3 for p in PROMPTS[:n_prompts]]  # repetitive: hits
        want = []
        for p in prompts:
            net.rnn_clear_previous_state()
            want.append(decoding.speculative_sample(
                net, decoding.prompt_lookup_proposer(2), p, steps=8,
                vocab_size=12, gamma=3, top_k=1,
                rng=np.random.default_rng(0)))
        got = decoding.speculative_sample_batch(
            net, decoding.prompt_lookup_proposer(2), prompts, steps=8,
            vocab_size=12, gamma=3, top_k=1)
        assert got == want

    def test_model_draft_greedy_equals_per_prompt(self):
        """Batched x speculative == per-prompt speculative with a MODEL
        draft (unrelated smaller net), greedy."""
        target = _tfm(layers=2, embed=32, seed=1)
        draft = _tfm(layers=1, embed=16, seed=999)
        tnet, dnet = target.init(), draft.init()
        prompts = PROMPTS[:3]
        want = []
        for b, p in enumerate(prompts):
            want.append(decoding.speculative_sample(
                tnet, dnet, p, steps=8, vocab_size=12, gamma=3, top_k=1,
                rng=np.random.default_rng(b)))
        got = decoding.speculative_sample_batch(
            tnet, dnet, prompts, steps=8, vocab_size=12, gamma=3,
            top_k=1, rngs=[np.random.default_rng(b)
                           for b in range(len(prompts))])
        assert got == want

    @pytest.mark.parametrize("n_prompts", [1, 3])
    def test_windowed_prompt_lookup_greedy_equals_per_prompt(
            self, n_prompts):
        """Per-row rolling-cache writes (VERDICT r4 task 7): batched x
        speculative == per-prompt speculative on a WINDOWED rope net —
        each row writes its own modular slots and kv_abs promotes to
        [N, L] after the first per-row rewind."""
        model = _tfm(layers=2, embed=32, seed=3, window=6, cache=64)
        net = model.init()
        prompts = [p * 3 for p in PROMPTS[:n_prompts]]
        want = []
        for p in prompts:
            net.rnn_clear_previous_state()
            want.append(decoding.speculative_sample(
                net, decoding.prompt_lookup_proposer(2), p, steps=8,
                vocab_size=12, gamma=3, top_k=1,
                rng=np.random.default_rng(0)))
        got = decoding.speculative_sample_batch(
            net, decoding.prompt_lookup_proposer(2), prompts, steps=8,
            vocab_size=12, gamma=3, top_k=1)
        assert got == want

    def test_windowed_model_draft_greedy_equals_per_prompt(self):
        """Same bar with a MODEL draft that is itself windowed (both
        nets run per-row rolling-cache rewinds every round)."""
        target = _tfm(layers=2, embed=32, seed=1, window=6, cache=64)
        draft = _tfm(layers=1, embed=16, seed=999, window=5, cache=64)
        tnet, dnet = target.init(), draft.init()
        prompts = PROMPTS[:3]
        want = []
        for b, p in enumerate(prompts):
            tnet.rnn_clear_previous_state()
            dnet.rnn_clear_previous_state()
            want.append(decoding.speculative_sample(
                tnet, dnet, p, steps=8, vocab_size=12, gamma=3, top_k=1,
                rng=np.random.default_rng(b)))
        got = decoding.speculative_sample_batch(
            tnet, dnet, prompts, steps=8, vocab_size=12, gamma=3,
            top_k=1, rngs=[np.random.default_rng(b)
                           for b in range(len(prompts))])
        assert got == want

    def test_one_verify_dispatch_per_round(self):
        """The whole batch's round costs ONE target forward (the point
        of the composition): identical draft == always-accept, so B
        prompts x steps tokens cost prime + ceil(steps/(gamma+1))
        verifies — regardless of B."""
        model = _tfm(layers=1, embed=16, seed=7, cache=64)
        tnet, dnet = model.init(), model.init()
        calls = {"n": 0}
        orig = type(tnet).rnn_time_step

        def counting(self, *a, **k):
            if self is tnet:
                calls["n"] += 1
            return orig(self, *a, **k)

        type(tnet).rnn_time_step = counting
        try:
            prompts = [[1, 2, 1, 2, 1], [3, 4, 3, 4, 3], [5, 6, 5, 6, 5],
                       [7, 8, 7, 8, 7]]
            out = decoding.speculative_sample_batch(
                tnet, dnet, prompts, steps=8, vocab_size=12, gamma=3,
                top_k=1)
        finally:
            type(tnet).rnn_time_step = orig
        assert all(len(o) == 13 for o in out)
        # identical models + greedy => every proposal accepted: 8 new
        # tokens per row in ceil(8/(3+1)) = 2 rounds => 1 batched prime
        # + 2 verifies. Per-prompt speculative costs 4x that; per-prompt
        # plain decode 4 x (1 + 8).
        assert calls["n"] == 1 + 2, calls["n"]

    def test_stop_tokens_per_row(self):
        """A row hitting EOS freezes; others continue to their budget."""
        model = _tfm(layers=1, embed=16, seed=11)
        net = model.init()

        def stop_proposer(ids, gamma):
            # rows whose context starts with 9 propose the stop token
            return [0] if ids[0] == 9 else [5] * gamma

        out = decoding.speculative_sample_batch(
            net, stop_proposer, [[9, 1], [1, 2, 3]], steps=6,
            vocab_size=12, gamma=2, top_k=1, stop_tokens=(0,))
        # row 0: stops when 0 is accepted (kept as final id)
        assert 0 in out[0][2:] or len(out[0]) == 8
        if 0 in out[0][2:]:
            assert out[0][-1] == 0 and len(out[0]) <= 8
        assert len(out[1]) == 9          # row 1 unaffected
        assert 0 not in out[1][3:] or out[1][-1] == 0

    def test_trace_stable_across_bucket_shapes(self):
        """Different prompt mixes sharing the same buckets (row bucket
        4, prompt-column bucket 4, chunk 1+gamma) add NO new jit traces
        on the second call — serving reuses warm compiled shapes."""
        model = _tfm(layers=1, embed=16, seed=5)
        net = model.init()
        draft = decoding.prompt_lookup_proposer(2)
        decoding.speculative_sample_batch(
            net, draft, [[1, 2, 1, 2], [3, 4, 3, 4], [5, 6, 5, 6]],
            steps=4, vocab_size=12, gamma=3, top_k=1)

        def traces():
            return sum(f._cache_size() for f in net._jit_cache.values())

        warm = traces()
        decoding.speculative_sample_batch(
            net, draft,
            [[2, 3, 2, 3], [4, 5, 4, 5], [6, 7, 6, 7], [1, 5, 1, 5]],
            steps=4, vocab_size=12, gamma=3, top_k=1)
        assert traces() == warm, "second mix retraced despite same buckets"


class TestBudgetTracking:
    def test_budget_counter_tracks_true_max_row_position(self):
        """Per-row rewinds keep the scalar budget counter at the TRUE
        max row position, even when rounds alternate which row rewinds
        (review regression: min-subtraction drifted the counter upward
        and tripped check_stream_budget spuriously)."""
        model = _tfm(layers=1, embed=16, seed=7, cache=64)
        net = model.init()
        V = 12
        x = np.zeros((2, V, 4), np.float32)
        x[:, 1, :] = 1.0
        net.rnn_time_step(x)                       # both rows at 4
        true_rows = np.array([4, 4])
        rng = np.random.default_rng(0)
        chunk = np.zeros((2, V, 4), np.float32)
        chunk[:, 2, :] = 1.0
        for r in range(8):
            net.rnn_time_step(chunk)               # +4 each row
            true_rows += 4
            # alternate: one row keeps everything, the other rewinds all
            amounts = np.array([4, 0]) if r % 2 == 0 else np.array([0, 4])
            rewind_stream_state(net, amounts)
            true_rows -= amounts
            pos_map = getattr(net, "_stream_pos_map", None)
            tracked = (max(pos_map.values()) if pos_map
                       else net._stream_pos)
            assert tracked == true_rows.max(), \
                f"round {r}: tracked {tracked} != true {true_rows.max()}"
        # both rows well inside the 64 cache: more streaming still works
        net.rnn_time_step(chunk)

    def test_windowed_small_cache_rejected_at_entry(self):
        """A rolling cache without rewind headroom (cache_length <
        window + gamma + 1) still fails fast — per-row writes don't
        change the eviction arithmetic."""
        net = _tfm(layers=1, embed=16, seed=3, window=8, cache=10).init()
        with pytest.raises(ValueError, match="rolling cache"):
            decoding.speculative_sample_batch(
                net, decoding.prompt_lookup_proposer(2), [[1, 2]],
                steps=4, vocab_size=12, gamma=2, top_k=1)

    def test_learned_pos_rejected_at_entry(self):
        model = _tfm(layers=1, embed=16, seed=3, positional="learned")
        net = model.init()
        with pytest.raises(ValueError, match="attention-only"):
            decoding.speculative_sample_batch(
                net, decoding.prompt_lookup_proposer(2), [[1, 2]],
                steps=4, vocab_size=12, gamma=2, top_k=1)

    def test_learned_pos_model_draft_rejected_at_entry(self):
        target = _tfm(layers=1, embed=16, seed=3)
        draft = _tfm(layers=1, embed=16, seed=4, positional="learned")
        with pytest.raises(ValueError, match="attention-only"):
            decoding.speculative_sample_batch(
                target.init(), draft.init(), [[1, 2]], steps=4,
                vocab_size=12, gamma=2, top_k=1)


class TestBatchedBeam:
    @pytest.mark.parametrize("n_prompts,width", [(1, 3), (3, 3), (4, 2)])
    def test_equals_per_prompt_beam(self, n_prompts, width):
        model = _tfm(layers=2, embed=32, seed=2)
        net = model.init()
        prompts = PROMPTS[:n_prompts]
        want = []
        for p in prompts:
            want.append(decoding.beam_search(net, p, steps=6,
                                             vocab_size=12,
                                             beam_width=width))
        got = decoding.beam_search_batch(net, prompts, steps=6,
                                         vocab_size=12, beam_width=width)
        for (gs, gsc), (ws, wsc) in zip(got, want):
            assert gs == ws
            assert gsc == pytest.approx(wsc, abs=1e-4)

    def test_eos_semantics_match(self):
        model = _tfm(layers=1, embed=16, seed=8)
        net = model.init()
        prompts = [[1, 2, 3], [4, 5, 6]]
        stops = (0, 2)
        want = [decoding.beam_search(net, p, steps=8, vocab_size=12,
                                     beam_width=3, stop_tokens=stops)
                for p in prompts]
        got = decoding.beam_search_batch(net, prompts, steps=8,
                                         vocab_size=12, beam_width=3,
                                         stop_tokens=stops)
        for (gs, gsc), (ws, wsc) in zip(got, want):
            assert gs == ws
            assert gsc == pytest.approx(wsc, abs=1e-4)

    def test_one_dispatch_per_step(self):
        model = _tfm(layers=1, embed=16, seed=4)
        net = model.init()
        calls = {"n": 0}
        orig = type(net).rnn_time_step

        def counting(self, *a, **k):
            calls["n"] += 1
            return orig(self, *a, **k)

        type(net).rnn_time_step = counting
        try:
            decoding.beam_search_batch(net, PROMPTS, steps=5,
                                       vocab_size=12, beam_width=3)
        finally:
            type(net).rnn_time_step = orig
        # 1 batched prime + (steps-1) decode dispatches, regardless of
        # the 4 prompts (per-prompt beam would cost 4x)
        assert calls["n"] == 1 + 4, calls["n"]


class TestTransformerWrappers:
    def test_zoo_entry_points(self):
        model = _tfm(layers=1, embed=16, seed=6)
        net = model.init()
        outs = model.speculative_sample_batch(
            net, decoding.prompt_lookup_proposer(2),
            [[1, 2, 1, 2], [3, 4, 3, 4]], steps=4, gamma=2, top_k=1)
        assert len(outs) == 2 and all(len(o) == 8 for o in outs)
        beams = model.beam_search_batch(net, [[1, 2], [3, 4]], steps=4,
                                        beam_width=2)
        assert len(beams) == 2
        for seq, score in beams:
            assert len(seq) == 6 and np.isfinite(score)


class TestSpeculativeBeam:
    """The last serving-matrix edge: beam x speculation. Bar: output
    EQUALS plain beam_search (sequence AND score) in every regime, and
    target dispatches never exceed plain beam's (+1 worst case)."""

    def _count_dispatches(self, net):
        calls = [0]
        orig = net.rnn_time_step

        def counting(*a, **k):
            calls[0] += 1
            return orig(*a, **k)

        net.rnn_time_step = counting
        return calls, lambda: setattr(net, "rnn_time_step", orig)

    @pytest.mark.parametrize("width,gamma", [(1, 2), (3, 3), (4, 2)])
    def test_equals_plain_beam(self, width, gamma):
        model = _tfm(layers=2, embed=32, seed=3)
        net = model.init()
        seed = [1, 2, 3, 1, 2, 3, 1, 2]          # repetitive: hits
        want = decoding.beam_search(net, seed, steps=8, vocab_size=12,
                                    beam_width=width)
        net.rnn_clear_previous_state()
        got = decoding.speculative_beam_search(
            net, decoding.prompt_lookup_proposer(2), seed, steps=8,
            vocab_size=12, beam_width=width, gamma=gamma)
        assert got[0] == want[0]
        assert got[1] == pytest.approx(want[1], rel=1e-6)

    def test_equals_plain_beam_with_stops(self):
        model = _tfm(layers=1, embed=16, seed=9)
        net = model.init()
        seed = [4, 5, 4, 5, 4]
        for stop in ([7], [0, 3]):
            want = decoding.beam_search(net, seed, steps=10,
                                        vocab_size=12, beam_width=3,
                                        stop_tokens=stop)
            net.rnn_clear_previous_state()
            got = decoding.speculative_beam_search(
                net, decoding.prompt_lookup_proposer(2), seed, steps=10,
                vocab_size=12, beam_width=3, gamma=3, stop_tokens=stop)
            assert got[0] == want[0]
            assert got[1] == pytest.approx(want[1], rel=1e-6)

    def test_equals_plain_beam_windowed(self):
        """Composes with rolling caches: the over-consumed tail rewind
        is uniform, which windowed attention supports."""
        model = _tfm(layers=1, embed=16, seed=5, window=6, cache=64)
        net = model.init()
        seed = [1, 2, 1, 2, 1, 2]
        want = decoding.beam_search(net, seed, steps=8, vocab_size=12,
                                    beam_width=3)
        net.rnn_clear_previous_state()
        got = decoding.speculative_beam_search(
            net, decoding.prompt_lookup_proposer(2), seed, steps=8,
            vocab_size=12, beam_width=3, gamma=3)
        assert got[0] == want[0]
        assert got[1] == pytest.approx(want[1], rel=1e-6)

    def test_dispatch_count_never_worse_untrained(self):
        """An untrained net gives ~zero acceptance — the degenerate
        regime must still never cost more dispatches than plain beam."""
        model = _tfm(layers=1, embed=16, seed=7)
        net = model.init()
        seed = [1, 2, 3] * 4
        calls, restore = self._count_dispatches(net)
        got_plain = decoding.beam_search(net, seed, steps=9,
                                         vocab_size=12, beam_width=2)
        plain = calls[0]
        calls[0] = 0
        net.rnn_clear_previous_state()
        got = decoding.speculative_beam_search(
            net, decoding.prompt_lookup_proposer(2), seed, steps=9,
            vocab_size=12, beam_width=2, gamma=3)
        spec = calls[0]
        restore()
        assert got[0] == got_plain[0]
        assert spec <= plain + 1

    class _OracleNet:
        """Stateless markov 'net': the distribution depends only on the
        last fed token, so rewind/reorder are no-ops and the dispatch
        math of the round loop can be pinned DETERMINISTICALLY. Two
        peaky attractors (A: 2→3→4→2, B: 5→6→7→5) branch from token 1 —
        beam 0 rides A, beam 1 rides B, each extends itself, so every
        drafted step accepts. Acceptance requires identity parents:
        that holds because each attractor's 2nd choice (~0.011) scores
        far below the other beam's 1st (~0.9) against a ~0.2 branch gap.
        """

        V = 10
        _NEXT = {2: 3, 3: 4, 4: 2, 5: 6, 6: 7, 7: 5}

        def __init__(self):
            import types
            self.state = {}
            self.conf = types.SimpleNamespace(vertices={})
            self.calls = 0

        def rnn_clear_previous_state(self):
            pass

        def _dist(self, tok):
            d = np.full(self.V, 1e-6, np.float32)
            if tok == 1:
                d[2], d[5] = 0.55, 0.45
            else:
                nxt = self._NEXT.get(tok, 0)
                d[:] = 0.1 / (self.V - 1)
                d[nxt] = 0.9
            return d / d.sum()

        def rnn_time_step(self, x, **kw):
            self.calls += 1
            x = np.asarray(x)
            n, _, t = x.shape
            toks = x.argmax(axis=1)
            out = np.zeros((n, self.V, t), np.float32)
            for r in range(n):
                for c in range(t):
                    out[r, :, c] = self._dist(int(toks[r, c]))
            return out

        def oracle_draft(self, ids, gamma):
            out, tok = [], ids[-1]
            for _ in range(gamma):
                tok = self._NEXT.get(tok, 0)
                out.append(tok)
            return out

    def test_oracle_dispatch_math_pinned(self):
        """With a perfect per-beam draft every round commits gamma+1
        tokens for ONE verify dispatch — the exact round arithmetic,
        pinned without float noise. Plain beam pays one per step."""
        net = self._OracleNet()
        want = decoding.beam_search(net, [1], steps=13,
                                    vocab_size=net.V, beam_width=2)
        plain = net.calls
        net2 = self._OracleNet()
        got = decoding.speculative_beam_search(
            net2, net2.oracle_draft, [1], steps=13,
            vocab_size=net2.V, beam_width=2, gamma=3)
        assert got[0] == want[0]
        assert got[1] == pytest.approx(want[1], rel=1e-6)
        # plain: prime + 12 feeds; spec: prime + 1 first-expansion-free
        # round structure: 12 remaining tokens / (gamma+1) = 3 verifies
        assert plain == 13
        assert net2.calls == 4

    def test_dispatch_win_on_two_attractor_model(self):
        """End-to-end on a real trained net: two memorized continuations
        branch from a shared prefix, beam 0 rides one and beam 1 the
        other, each confidently self-extends — drafted rounds accept
        and the target runs strictly fewer times than one-per-step,
        output still equal to plain beam."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
        V, L = 12, 36
        model = _tfm(layers=1, embed=32, seed=0, vocab=V, cache=96)
        net = model.init()
        prefix = [1, 1, 1]
        conts = ([2, 3, 4] * 12, [7, 8, 9] * 12)
        x = np.zeros((2, V, L), np.float32)
        y = np.zeros((2, V, L), np.float32)
        for b, cont in enumerate(conts):
            seq = (prefix + cont)[:L + 1]
            x[b, seq[:-1], np.arange(L)] = 1.0
            y[b, seq[1:], np.arange(L)] = 1.0
        ds = DataSet(x, y)
        for _ in range(120):
            net.fit(ds)
        # seed ENDS AT THE BRANCH POINT: the first expansion puts beam 0
        # on attractor A and beam 1 on attractor B, and from then on
        # each confidently extends itself (identity parents). Early
        # rounds have no lookup hits (no repetition laid down yet) and
        # cost one dispatch each, exactly like plain beam; once both
        # beams have a period in their ids, drafted rounds accept.
        seed = list(prefix)
        calls, restore = self._count_dispatches(net)
        net.rnn_clear_previous_state()
        got_plain = decoding.beam_search(net, seed, steps=15,
                                         vocab_size=V, beam_width=2)
        plain = calls[0]
        calls[0] = 0
        net.rnn_clear_previous_state()
        got = decoding.speculative_beam_search(
            net, decoding.prompt_lookup_proposer(2), seed, steps=15,
            vocab_size=V, beam_width=2, gamma=3)
        spec = calls[0]
        restore()
        assert got[0] == got_plain[0]
        assert got[1] == pytest.approx(got_plain[1], rel=1e-6)
        assert spec < plain, (spec, plain)

    def test_model_draft_equals_plain_beam(self):
        """A streaming-net draft (beam-synchronized greedy stream)
        yields the same plain-beam output — the draft only changes how
        proposals are made, never what is committed."""
        target = _tfm(layers=2, embed=32, seed=1)
        draft = _tfm(layers=1, embed=16, seed=999)
        tnet, dnet = target.init(), draft.init()
        seed = [1, 2, 3, 1, 2, 3]
        want = decoding.beam_search(tnet, seed, steps=8, vocab_size=12,
                                    beam_width=3)
        tnet.rnn_clear_previous_state()
        got = decoding.speculative_beam_search(
            tnet, dnet, seed, steps=8, vocab_size=12, beam_width=3,
            gamma=3)
        assert got[0] == want[0]
        assert got[1] == pytest.approx(want[1], rel=1e-6)

    def test_model_draft_windowed_equals_plain_beam(self):
        """Model draft + windowed target: both streams rewind the
        rolling caches uniformly each round."""
        target = _tfm(layers=1, embed=32, seed=4, window=6, cache=64)
        draft = _tfm(layers=1, embed=16, seed=99, window=5, cache=64)
        tnet, dnet = target.init(), draft.init()
        seed = [2, 4, 2, 4, 2]
        want = decoding.beam_search(tnet, seed, steps=8, vocab_size=12,
                                    beam_width=2)
        tnet.rnn_clear_previous_state()
        got = decoding.speculative_beam_search(
            tnet, dnet, seed, steps=8, vocab_size=12, beam_width=2,
            gamma=3)
        assert got[0] == want[0]
        assert got[1] == pytest.approx(want[1], rel=1e-6)

    def test_draft_must_be_net_or_callable(self):
        model = _tfm(layers=1, embed=16, seed=3)
        net = model.init()
        with pytest.raises(TypeError, match="streaming net"):
            decoding.speculative_beam_search(
                net, 42, [1, 2], steps=4, vocab_size=12)


# ---------------------------------------------------------------------
# one input seam: every decoder feeds a net the way the net asks
# ---------------------------------------------------------------------
def _decoder_pair(seed, embed=16, vocab=12, cache=64):
    """One rope decoder twice over the same weights: fed token ids
    (``SequenceEmbeddingLayer``: ids in, every position out) and fed
    the one-hot block (a kernel-1 convolution holding the same table).
    A lookup and a one-hot product give the same rows bit for bit."""
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (
        Convolution1DLayer, RnnOutputLayer, SelfAttentionLayer,
        SequenceEmbeddingLayer)
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    def build(embedding):
        conf = (NeuralNetConfiguration.Builder().seed(seed)
                .weight_init("xavier")
                .graph_builder().add_inputs("in")
                .set_input_types(InputType.recurrent(vocab, cache))
                .add_layer("embed", embedding, "in")
                .add_layer("attn", SelfAttentionLayer(
                    n_out=embed, n_heads=2, causal=True,
                    cache_length=cache, rope=True,
                    activation="identity"), "embed")
                .add_layer("out", RnnOutputLayer(
                    n_out=vocab, loss="mcxent", activation="softmax"),
                    "attn")
                .set_outputs("out").build())
        return ComputationGraph(conf).init()

    ids = build(SequenceEmbeddingLayer(n_out=embed))
    hot = build(Convolution1DLayer(
        n_out=embed, kernel=1, convolution_mode="same",
        activation="identity", has_bias=False))
    hot.params = {**ids.params, "embed": {
        "W": ids.params["embed"]["W"].T[:, :, None]}}   # [E, V, 1]
    assert decoding.takes_ids(ids) and not decoding.takes_ids(hot)
    return ids, hot


def _greedy(net, prompt, steps, V=12):
    return decoding.sample_stream(net, prompt, steps, V, top_k=1,
                                  rng=np.random.default_rng(0))


class TestDecodersFeedIds:
    """The five decoders that built their one-hot themselves, on a net
    that takes ids: each holds the equality it holds on one-hot nets
    (greedy speculation == plain greedy; beam width 1 == greedy), here
    against the one-hot twin of the same weights."""

    @pytest.fixture(scope="class")
    def nets(self):
        return _decoder_pair(seed=7), _decoder_pair(seed=8, embed=8)

    @pytest.mark.parametrize("decoder", [
        "speculative_sample", "speculative_sample_batch", "beam_search",
        "beam_search_batch", "speculative_beam_search"])
    def test_decoder_on_an_ids_net_equals_its_one_hot_twin(self, nets,
                                                           decoder):
        (ids, hot), (draft_ids, draft_hot) = nets
        V, steps = 12, 6
        want = [_greedy(hot, p, steps) for p in PROMPTS]
        if decoder == "speculative_sample":
            got = [decoding.speculative_sample(
                ids, draft_ids, p, steps, V, gamma=3, top_k=1,
                rng=np.random.default_rng(0)) for p in PROMPTS]
        elif decoder == "speculative_sample_batch":
            got = decoding.speculative_sample_batch(
                ids, draft_ids, PROMPTS, steps, V, gamma=3, top_k=1)
        elif decoder == "beam_search":
            got = [decoding.beam_search(ids, p, steps, V,
                                        beam_width=1)[0]
                   for p in PROMPTS]
        elif decoder == "beam_search_batch":
            got = [seq for seq, _ in decoding.beam_search_batch(
                ids, PROMPTS, steps, V, beam_width=1)]
        else:
            got = [decoding.speculative_beam_search(
                ids, draft_ids, p, steps, V, beam_width=1,
                gamma=3)[0] for p in PROMPTS]
            # and at a real width, the twin's beam search exactly
            wide = decoding.speculative_beam_search(
                ids, draft_ids, PROMPTS[0], steps, V, beam_width=3,
                gamma=2)
            ref = decoding.beam_search(hot, PROMPTS[0], steps, V,
                                       beam_width=3)
            assert wide[0] == ref[0]
            assert wide[1] == pytest.approx(ref[1], abs=1e-5)
        assert got == want
