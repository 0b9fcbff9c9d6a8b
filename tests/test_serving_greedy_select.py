"""The greedy rule (util/decoding.py, module docstring) and the serving
engine's use of it: a ``top_k == 1`` row takes the lowest-index maximum
of the distribution the program returned and consumes no random numbers;
the engine takes that index from one on-device argmax and fetches the
``[S, V]`` block only in a cycle where some row samples; the serving
loop parks briefly for a finished caller's next request. Small rope
transformer on the CPU, against one-shot ``sample_stream`` and hand
counts."""

import copy
import time

import numpy as np
import pytest

from deeplearning4j_tpu import monitoring
from deeplearning4j_tpu.monitoring import runtime
from deeplearning4j_tpu.monitoring.metrics import MetricsRegistry
from deeplearning4j_tpu.resilience import chaos
from deeplearning4j_tpu.serving import (
    EngineSupervisor, GenerationEngine, PagedKVConfig)
from deeplearning4j_tpu.serving.engine import HANDOFF_WAIT_S
from deeplearning4j_tpu.serving.health import (
    SERVING_BLOCK_FETCHES, SERVING_SAMPLE_ROWS)
from deeplearning4j_tpu.util.decoding import (
    ArgmaxRow, draw, filter_probs, greedy_ids, sample_stream, selects_one,
    step_greedy, step_tokens)
from deeplearning4j_tpu.zoo import TextGenerationTransformer

V = 12
S = 4
SAMPLED = dict(temperature=0.8, top_k=5)
GREEDY_PROMPTS = ([1, 2, 3], [4, 5], [6, 7, 8, 9, 1], [2])
SAMPLED_PROMPTS = ([3, 1], [5, 4, 2, 6])


@pytest.fixture(scope="module")
def net():
    return TextGenerationTransformer(
        vocab_size=V, embed_dim=16, n_heads=2, n_layers=2, max_length=32,
        positional="rope").init()


def _paged(net, **kw):
    return GenerationEngine(net, V, slots=S,
                            paging=PagedKVConfig(page_size=4), **kw)


def _one_shot(net, prompt, steps, rng=None, **params):
    ids = sample_stream(net, prompt, steps, V, rng=rng, prime_padded=True,
                        **params)
    net.rnn_clear_previous_state()
    return ids


def _state(rng):
    return copy.deepcopy(rng.bit_generator.state)


# ---------------------------------------------------------------------
# (a) the rule itself
# ---------------------------------------------------------------------
class TestTheRule:
    @pytest.mark.parametrize("temperature", [0.05, 1.0, 7.5])
    @pytest.mark.parametrize("top_p", [None, 0.3])
    def test_draw_is_argmax_and_leaves_the_generator_alone(
            self, temperature, top_p):
        rows = np.random.default_rng(5).dirichlet(np.ones(97), size=16)
        rng = np.random.default_rng(11)
        before = _state(rng)
        for row in rows.astype(np.float32):
            assert draw(row, temperature, rng, top_k=1, top_p=top_p) \
                == int(np.argmax(row))
        assert draw(rows, temperature, rng, top_k=1, top_p=top_p) \
            == [int(i) for i in rows.argmax(axis=1)]
        assert _state(rng) == before

    @pytest.mark.parametrize("temperature", [0.05, 1.0, 7.5])
    def test_an_exact_tie_goes_to_the_lowest_index(self, temperature):
        row = np.full(64, 0.25 / 61, np.float32)
        row[[40, 9, 23]] = 0.25                  # three equal maxima
        rng = np.random.default_rng(3)
        before = _state(rng)
        assert draw(row, temperature, rng, top_k=1) == 9
        assert draw(np.stack([row, row[::-1]]), temperature, rng,
                    top_k=1) == [9, 63 - 40]
        assert _state(rng) == before
        # the filtered distribution the acceptance walk reads agrees
        p = filter_probs(row, temperature, top_k=1)
        assert p[9] == 1.0 and p.sum() == 1.0

    def test_near_ties_that_rescaling_would_merge_keep_their_order(self):
        """At a high temperature two neighbours round to one value after
        log / divide / exp; the rule reads the distribution as handed
        in, so the larger still wins, wherever it sits."""
        row = np.full(8, 0.1, np.float32)
        row[5] = np.nextafter(np.float32(0.15), np.float32(1))
        row[2] = 0.15
        assert draw(row, 1e4, None, top_k=1) == 5
        assert filter_probs(row, 1e4, top_k=1)[5] == 1.0

    def test_per_row_top_k_skips_the_generator_on_its_greedy_rows(self):
        rows = np.random.default_rng(8).dirichlet(np.ones(V), size=3)
        rng, alone = np.random.default_rng(4), np.random.default_rng(4)
        got = draw(rows, 1.0, rng, top_k=np.array([1, 5, 1]))
        assert got[0] == rows[0].argmax() and got[2] == rows[2].argmax()
        assert got[1] == draw(rows[1], 1.0, alone, top_k=5)
        assert _state(rng) == _state(alone)      # one draw consumed

    @pytest.mark.parametrize("top_k, greedy", [
        (1, True), (np.int64(1), True), (None, False), (2, False),
        (np.array([1, 1]), False)])
    def test_selects_one(self, top_k, greedy):
        assert selects_one(top_k) is greedy

    def test_an_argmax_row_answers_a_greedy_draw_and_no_other(self):
        row = ArgmaxRow(np.int32(7), V)
        assert len(row) == V
        assert draw(row, 0.4, None, top_k=1, top_p=0.2) == 7
        with pytest.raises(ValueError, match="top_k=1"):
            draw(row, 1.0, np.random.default_rng(0), top_k=5)

    def test_the_device_gives_the_index_numpy_gives(self):
        out = np.random.default_rng(2).random((S, V, 3)).astype(np.float32)
        out[1, [7, 2], -1] = 2.0                 # a tie at the last step
        ids = np.asarray(greedy_ids(out))
        assert ids.dtype == np.int32
        assert list(ids) == list(out[:, :, -1].argmax(axis=1))
        assert ids[1] == 2

    def test_step_greedy_is_step_tokens_plus_the_ids(self, net):
        net.rnn_clear_previous_state()
        probs = step_tokens(net, [1, 2], V)
        net.rnn_clear_previous_state()
        ids, none = step_greedy(net, [1, 2], V)
        net.rnn_clear_previous_state()
        ids2, block = step_greedy(net, [1, 2], V, block=True)
        net.rnn_clear_previous_state()
        assert none is None
        assert np.array_equal(block, probs)
        assert list(ids) == list(ids2) == list(probs.argmax(axis=1))


# ---------------------------------------------------------------------
# (b) an all-greedy engine: ids only
# ---------------------------------------------------------------------
class TestAllGreedy:
    @pytest.mark.parametrize("paged", [True, False],
                             ids=["paged", "slot-arena"])
    def test_same_ids_as_sample_stream_and_only_ids_fetched(self, net,
                                                            paged):
        want = [_one_shot(net, p, 6, top_k=1) for p in GREEDY_PROMPTS]
        reg = MetricsRegistry()
        eng = (_paged(net, registry=reg) if paged
               else GenerationEngine(net, V, slots=S, registry=reg))
        rngs = [np.random.default_rng(i) for i in range(len(want))]
        at_submit = [_state(r) for r in rngs]
        hs = [eng.submit(p, 6, top_k=1, temperature=0.3 + i, rng=r)
              for i, (p, r) in enumerate(zip(GREEDY_PROMPTS, rngs))]
        d2h = []
        while eng.step():
            d2h.append(eng.health()["host_io"]["decode"]["d2h_bytes"])
        assert [h.result(timeout=0) for h in hs] == want
        assert [_state(r) for r in rngs] == at_submit
        h = eng.health()
        decoded = sum(len(w) - len(p) - 1          # the seat drew the first
                      for w, p in zip(want, GREEDY_PROMPTS))
        assert h["sample"] == {"greedy_rows": decoded, "drawn_rows": 0,
                               "block_fetches": 0}
        assert h["decode_dispatch"]["rows"] == decoded
        # 4 bytes a slot a cycle, and nothing else
        dispatches = h["decode_dispatch"]["count"]
        assert d2h[-1] == 4 * S * dispatches
        assert set(np.diff([0] + d2h)) <= {0, 4 * S}
        lab = dict(model=eng.label)
        rows = reg.get(SERVING_SAMPLE_ROWS)
        assert rows.value(kind="greedy", **lab) == decoded
        assert rows.value(kind="drawn", **lab) == 0
        assert reg.get(SERVING_BLOCK_FETCHES).value(**lab) == 0


# ---------------------------------------------------------------------
# (c) a mixed arena: sampled rows draw from the block, greedy rows don't
# ---------------------------------------------------------------------
class TestMixedArena:
    @pytest.mark.parametrize("paged", [True, False],
                             ids=["paged", "slot-arena"])
    def test_every_request_gets_what_it_gets_alone(self, net, paged):
        steps = 7
        greedy_want = [_one_shot(net, p, steps, top_k=1)
                       for p in GREEDY_PROMPTS[:2]]
        alone = [np.random.default_rng(100 + i)
                 for i in range(len(SAMPLED_PROMPTS))]
        sampled_want = [_one_shot(net, p, steps, rng=r, **SAMPLED)
                        for p, r in zip(SAMPLED_PROMPTS, alone)]
        eng = _paged(net) if paged else GenerationEngine(net, V, slots=S)
        rngs = [np.random.default_rng(100 + i)
                for i in range(len(SAMPLED_PROMPTS))]
        g_rng = np.random.default_rng(9)
        at_submit = _state(g_rng)
        # interleaved, so greedy and sampled rows share every cycle
        hs = [eng.submit(GREEDY_PROMPTS[0], steps, top_k=1, rng=g_rng),
              eng.submit(SAMPLED_PROMPTS[0], steps, rng=rngs[0], **SAMPLED),
              eng.submit(GREEDY_PROMPTS[1], steps, top_k=1, rng=g_rng),
              eng.submit(SAMPLED_PROMPTS[1], steps, rng=rngs[1], **SAMPLED)]
        eng.run_until_idle()
        got = [h.result(timeout=0) for h in hs]
        assert [got[0], got[2]] == greedy_want
        assert [got[1], got[3]] == sampled_want
        assert [_state(r) for r in rngs] == [_state(r) for r in alone]
        assert _state(g_rng) == at_submit
        h = eng.health()
        per_request = steps - 1                    # the seat drew the first
        assert h["sample"]["greedy_rows"] == 2 * per_request
        assert h["sample"]["drawn_rows"] == 2 * per_request
        fetches = h["sample"]["block_fetches"]
        assert 0 < fetches <= h["decode_dispatch"]["count"]
        assert h["host_io"]["decode"]["d2h_bytes"] == \
            4 * S * h["decode_dispatch"]["count"] + fetches * S * V * 4

    def test_the_block_stops_coming_once_the_sampling_rows_are_gone(
            self, net):
        eng = _paged(net)
        hs = [eng.submit([1, 2, 3], 9, top_k=1),
              eng.submit([4, 5], 3, rng=np.random.default_rng(1),
                         **SAMPLED)]
        eng.run_until_idle()
        assert all(h.done and h.error is None for h in hs)
        h = eng.health()
        assert h["sample"] == {"greedy_rows": 8, "drawn_rows": 2,
                               "block_fetches": 2}
        assert h["decode_dispatch"]["count"] == 8


# ---------------------------------------------------------------------
# (d) a supervisor rebuild in the middle of a greedy request
# ---------------------------------------------------------------------
class TestRebuild:
    @pytest.mark.parametrize("fault_at", [1, 3])
    def test_a_greedy_request_resumes_to_the_same_ids(self, net, fault_at):
        want = [_one_shot(net, p, 6, top_k=1) for p in GREEDY_PROMPTS[:2]]
        sup = EngineSupervisor()
        eng = _paged(net, supervisor=sup,
                     decode_chaos=chaos.FaultBurstInjector(n=fault_at, k=1))
        rng = np.random.default_rng(21)
        at_submit = _state(rng)
        hs = [eng.submit(p, 6, top_k=1, rng=rng)
              for p in GREEDY_PROMPTS[:2]]
        eng.run_until_idle()
        assert sup.rebuilds == 1
        assert [h.result(timeout=0) for h in hs] == want
        assert _state(rng) == at_submit
        assert eng.health()["sample"]["drawn_rows"] == 0
        assert eng.health()["sample"]["block_fetches"] == 0


# ---------------------------------------------------------------------
# (e) nothing compiles after warmup, whatever the mix
# ---------------------------------------------------------------------
class TestNoRecompiles:
    @pytest.mark.parametrize("paged", [True, False],
                             ids=["paged", "slot-arena"])
    def test_greedy_then_mixed_then_greedy_again(self, net, paged):
        monitoring.ensure_started()
        compiles = monitoring.global_registry().get(runtime.COMPILE_COUNTER)
        eng = _paged(net) if paged else GenerationEngine(net, V, slots=S)
        eng.warmup(max_prompt_len=8)
        warm = compiles.total()
        assert eng.health()["sample"]["drawn_rows"] == 0

        def run(kinds):
            hs = [eng.submit([1 + i, 2, 3][:1 + i % 3], 4,
                             rng=np.random.default_rng(i),
                             **(SAMPLED if k == "s" else dict(top_k=1)))
                  for i, k in enumerate(kinds)]
            eng.run_until_idle()
            assert all(h.done and h.error is None for h in hs)
            return eng.health()["sample"]["block_fetches"]

        assert run("ggg") == 0
        mixed = run("gsgs")
        assert mixed > 0
        assert run("gg") == mixed
        assert compiles.total() == warm


# ---------------------------------------------------------------------
# the serving loop's handoff: with selection off the host, the next
# cycle's admission check comes sooner than a finished caller's next
# request, so the loop parks for it after a cycle that freed a slot
# ---------------------------------------------------------------------
class TestRetirementHandoff:
    def _waits(self, eng, monkeypatch):
        seen, real = [], eng._pending.wait

        def wait(timeout):
            seen.append((timeout, eng._pending.depth()))
            return real(timeout)
        monkeypatch.setattr(eng._pending, "wait", wait)
        return seen

    def test_the_loop_parks_once_after_a_cycle_that_freed_a_slot(
            self, net, monkeypatch):
        eng = _paged(net)
        eng.warmup(max_prompt_len=4)
        seen = self._waits(eng, monkeypatch)
        eng.start()
        try:
            h = eng.submit([1, 2, 3], 4, top_k=1)
            assert len(h.result(timeout=60)) == 7
            t0 = time.monotonic()
            while not any(t == HANDOFF_WAIT_S for t, _ in seen):
                assert time.monotonic() - t0 < 10
                time.sleep(0.005)
        finally:
            eng.shutdown()
        handoffs = [d for t, d in seen if t == HANDOFF_WAIT_S]
        assert handoffs == [0]        # one retirement, nothing queued

    def test_a_request_that_lands_in_the_handoff_is_seated_at_once(
            self, net, monkeypatch):
        """The caller's next request, sent from the wait itself: the
        cycle that follows admits it — no decode cycle runs with the
        slot empty."""
        eng = GenerationEngine(net, V, slots=1)
        eng.warmup(max_prompt_len=4)
        real, second = eng._pending.wait, []

        def wait(timeout):
            if timeout == HANDOFF_WAIT_S and not second:
                second.append((eng.submit([4, 5], 3, top_k=1),
                               eng.health()["decode_dispatch"]["count"]))
            return real(timeout)
        monkeypatch.setattr(eng._pending, "wait", wait)
        eng.start()
        try:
            assert len(eng.submit([1, 2, 3], 3, top_k=1)
                       .result(timeout=60)) == 6
            t0 = time.monotonic()
            while not second:
                assert time.monotonic() - t0 < 10
                time.sleep(0.005)
            h, dispatches = second[0]
            assert len(h.result(timeout=60)) == 5
        finally:
            eng.shutdown()
        # 2 decode cycles for its 3 tokens, and none before it was seated
        assert eng.health()["decode_dispatch"]["count"] == dispatches + 2

    def test_manual_stepping_never_parks(self, net, monkeypatch):
        eng = _paged(net)
        seen = self._waits(eng, monkeypatch)
        hs = [eng.submit(p, 3, top_k=1) for p in GREEDY_PROMPTS]
        eng.run_until_idle()
        assert all(h.done for h in hs) and seen == []
