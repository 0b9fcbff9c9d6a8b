"""The serving engine's cycle as named phases (serving/engine.py,
"Phases"): a flat sequence of ``monitoring`` spans while a cycle has
work, none on an idle poll, and counts at the same boundaries in
``health()`` and the registry — scripted runs on a small rope transformer
against hand counts; one real profiler trace on the CPU."""

import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu import monitoring
from deeplearning4j_tpu.monitoring import runtime, tracing
from deeplearning4j_tpu.monitoring.metrics import MetricsRegistry
from deeplearning4j_tpu.monitoring.tracing import (
    current_path, next_phase, phases, span, span_histogram)
from deeplearning4j_tpu.resilience import chaos
from deeplearning4j_tpu.serving import (
    EngineSupervisor, GenerationEngine, PagedKVConfig, SpeculationConfig)
from deeplearning4j_tpu.serving.engine import PHASES
from deeplearning4j_tpu.serving.health import (
    SERVING_DECODE_ROWS, SERVING_HOST_IO_BYTES, SERVING_PREFILL_TOKENS)
from deeplearning4j_tpu.serving.request import GenerationStream
from deeplearning4j_tpu.util.decoding import (
    prompt_lookup_proposer, step_tokens)
from deeplearning4j_tpu.zoo import TextGenerationTransformer

V = 12
SYS = [7, 3, 9, 1, 4, 2, 8, 5]            # two full pages of 4
ADMISSION = ("engine.admit", "prefill.input", "prefill.forward",
             "prefill.fetch", "engine.seat")
DECODE = ("decode.input", "decode.forward", "decode.fetch",
          "engine.sample")
ENGINE_SPANS = ("engine.reap",) + ADMISSION + DECODE
assert ENGINE_SPANS == PHASES             # the engine's one table of names


@pytest.fixture(scope="module")
def net():
    return TextGenerationTransformer(
        vocab_size=V, embed_dim=16, n_heads=2, n_layers=2, max_length=32,
        positional="rope").init()


def _counts():
    h = span_histogram()
    return {k[0]: h.count(span=k[0]) for k in h.label_values()}


def _delta(before):
    return {k: v - before.get(k, 0) for k, v in _counts().items()
            if v - before.get(k, 0)}


class _Paths:
    """Records ``current_path()`` right after every span opens."""

    def __enter__(self):
        self.seen, self._enter = [], span.__enter__
        seen, enter = self.seen, self._enter

        def recording(s):
            out = enter(s)
            seen.append((threading.get_ident(), current_path()))
            return out
        span.__enter__ = recording
        return self

    def __exit__(self, *exc):
        span.__enter__ = self._enter

    def names(self):
        return [p for _, p in self.seen]


def _greedy(eng, prompt, steps):
    return eng.submit(prompt, steps=steps, top_k=1,
                      rng=np.random.default_rng(0))


# ---------------------------------------------------------------------
# the flat sequence itself (monitoring.phases / next_phase)
# ---------------------------------------------------------------------
class TestPhaseSequence:
    def test_one_span_open_at_a_time_and_the_last_is_closed(self):
        before = _counts()
        with phases():
            assert current_path() == ""       # no span before the first
            next_phase("t.a")
            assert current_path() == "t.a"
            next_phase("t.b")
            assert current_path() == "t.b"
            next_phase("t.b")                 # already open: nothing
            next_phase("t.c")
            assert current_path() == "t.c"
        assert current_path() == ""
        assert _delta(before) == {"t.a": 1, "t.b": 1, "t.c": 1}

    def test_outside_a_sequence_next_phase_does_nothing(self):
        before = _counts()
        next_phase("decode.input")
        assert current_path() == "" and _delta(before) == {}
        with phases():                        # a block that finds no work
            assert current_path() == ""
        assert _delta(before) == {}

    def test_reentered_it_leaves_closing_to_the_owner(self):
        @phases()
        def callee():
            next_phase("t.inner")
            assert current_path() == "t.inner"
            next_phase("t.late")

        before = _counts()
        with phases():
            next_phase("t.outer")
            callee()
            assert current_path() == "t.late"       # still one, still open
        assert current_path() == ""
        callee()                                    # alone: opens, closes
        assert current_path() == ""
        assert _delta(before) == {"t.outer": 1, "t.inner": 2, "t.late": 2}

    def test_an_exception_closes_the_open_phase_and_counts_it(self):
        before = _counts()
        errors = monitoring.global_registry().counter(
            tracing.SPAN_ERRORS, "", ("span",))
        e0 = errors.value(span="t.b")
        with pytest.raises(ValueError):
            with phases():
                next_phase("t.a")
                next_phase("t.b")
                raise ValueError("boom")
        assert current_path() == ""
        assert _delta(before) == {"t.a": 1, "t.b": 1}
        assert errors.value(span="t.b") == e0 + 1

    def test_disabled_spans_open_no_sequence(self):
        before = _counts()
        tracing.set_enabled(False)
        try:
            with phases():
                next_phase("engine.admit")
                assert current_path() == ""
        finally:
            tracing.set_enabled(True)
        assert _delta(before) == {}

    def test_a_span_observes_through_a_child_resolved_once(self):
        reg = MetricsRegistry()
        with span("x", reg):
            pass
        child = tracing._children[reg]["x"]
        calls = []
        real = reg.histogram
        reg.histogram = lambda *a, **k: calls.append(a) or real(*a, **k)
        for _ in range(3):
            with span("x", reg):
                pass
        tracing.record_span("x", 0.5, reg)
        assert calls == []                    # no get-or-create re-entry
        assert tracing._children[reg]["x"] is child
        assert span_histogram(reg).count(span="x") == 5


# ---------------------------------------------------------------------
# a scripted run against hand counts
# ---------------------------------------------------------------------
class TestScriptedRun:
    """slots=2, pages of 4. Cycle 1 admits A (5 tokens -> bucket 8) and
    B (SYS + [1]: 9 -> 16) and decodes both; A wants 3 tokens, B 4. Then
    C (SYS + [2, 2]: 8 from the prefix cache, 2 fed -> bucket 2) takes
    A's slot."""

    def _run(self, net):
        eng = GenerationEngine(net, V, slots=2,
                               paging=PagedKVConfig(page_size=4))
        before, h0 = _counts(), eng.health()
        with _Paths() as paths:
            assert eng.step() is False        # an idle poll
            idle = _delta(before)
            a = _greedy(eng, [1, 2, 3, 4, 5], 3)
            b = _greedy(eng, SYS + [1], 4)
            cycles = eng.run_until_idle()
            c = _greedy(eng, SYS + [2, 2], 3)
            cycles += eng.run_until_idle()
        for h in (a, b, c):
            h.result(timeout=0)
        return eng, idle, _delta(before), h0, eng.health(), paths, cycles

    def test_an_idle_poll_records_no_span(self, net):
        _, idle, *_ = self._run(net)
        assert idle == {}

    def test_a_poll_that_can_admit_nothing_records_no_span(self, net):
        """Queued, but not admissible, and no row seated: the poll does
        no work, so it opens no sequence."""
        eng = GenerationEngine(net, V, slots=2,
                               paging=PagedKVConfig(page_size=4))
        h = _greedy(eng, [1, 2, 3], 2)
        eng._pages_admissible = lambda req: False
        before = _counts()
        assert eng.step() is False
        assert _delta(before) == {} and current_path() == ""
        del eng._pages_admissible
        eng.run_until_idle()
        assert h.done

    def test_span_names_and_counts(self, net):
        _, _, d, h0, h1, _, cycles = self._run(net)
        assert set(d) == set(ENGINE_SPANS)
        # A: first token at the prime, 2 decode cycles; B: 3; C: 2 more
        assert cycles == 5
        for name in DECODE:
            assert d[name] == cycles, name
        for name in ADMISSION:
            assert d[name] == 3, name         # one per request popped
        # the sequence opens where the poll finds work: with a row
        # seated at `engine.reap`, else at the first request popped
        # (the cycles that admit A and C start with no row seated)
        assert d["engine.reap"] == cycles - 2

    def test_counts_equal_the_health_deltas(self, net):
        eng, _, d, h0, h1, _, _ = self._run(net)
        assert d["decode.fetch"] == d["decode.forward"] == \
            h1["decode_dispatch"]["count"] - h0["decode_dispatch"]["count"]
        assert d["prefill.forward"] == d["prefill.fetch"] == \
            eng._admissions == 3

    def test_no_two_engine_spans_are_ever_open_at_once(self, net):
        *_, paths, _ = self._run(net)
        names = paths.names()
        assert names and all("/" not in p for p in names)
        assert set(names) == set(ENGINE_SPANS)
        assert current_path() == ""

    def test_the_cycle_runs_in_the_order_the_table_gives(self, net):
        *_, paths, _ = self._run(net)
        names = paths.names()
        first = names[:names.index("engine.sample") + 1]
        assert first == list(ADMISSION) * 2 + list(DECODE)
        # the next cycle finds both rows seated
        assert names[len(first)] == "engine.reap"

    def test_rows_summed_over_dispatches(self, net):
        _, _, _, h0, h1, _, _ = self._run(net)
        # A and B together twice, B alone once, then C twice
        assert h1["decode_dispatch"]["rows"] == 2 + 2 + 1 + 1 + 1
        assert h0["decode_dispatch"]["rows"] == 0

    def test_prefill_tokens_with_a_prefix_hit(self, net):
        _, _, _, _, h1, _, _ = self._run(net)
        assert h1["prefill"] == {"fed_tokens": 5 + 9 + 2,
                                 "bucket_tokens": 8 + 16 + 2}
        # what the prefix cache served instead has its counter already
        assert h1["prefix_cache"]["reused_tokens"] == 8

    def test_the_four_byte_counters(self, net):
        _, _, _, _, h1, _, cycles = self._run(net)
        f32 = i32 = 4
        assert h1["host_io"] == {
            # a zoo transformer takes ids
            "input_form": "ids",
            # [S, 1] int32 ids up; every row is greedy, so the [S] int32
            # ids come down and the [S, V, 1] block stays put
            "decode": {"h2d_bytes": cycles * 2 * i32,
                       "d2h_bytes": cycles * 2 * i32},
            # [1, P] int32 up and, since a prime asks for its last
            # position only, [1, V] float32 down per prime: three primes,
            # one position each ([1, V, P] was (8 + 16 + 2) positions)
            "prefill": {"h2d_bytes": (8 + 16 + 2) * i32,
                        "d2h_bytes": 3 * V * f32,
                        "results": 3, "result_positions": 3}}

    def test_the_registry_reads_the_same_counts_at_scrape_time(self, net):
        reg = MetricsRegistry()
        eng = GenerationEngine(net, V, slots=2, registry=reg,
                               name="engine:ph",
                               paging=PagedKVConfig(page_size=4))
        _greedy(eng, SYS + [1], 3)
        eng.run_until_idle()
        _greedy(eng, SYS + [2, 2], 2)
        eng.run_until_idle()
        h = eng.health()
        lab = dict(model=eng.label)
        assert reg.get(SERVING_DECODE_ROWS).value(**lab) == \
            h["decode_dispatch"]["rows"] == 3
        tokens = reg.get(SERVING_PREFILL_TOKENS)
        for kind, key in (("fed", "fed_tokens"),
                          ("bucket", "bucket_tokens")):
            assert tokens.value(kind=kind, **lab) == h["prefill"][key] > 0
        io = reg.get(SERVING_HOST_IO_BYTES)
        for phase in ("decode", "prefill"):
            for d in ("h2d", "d2h"):
                assert io.value(phase=phase, direction=d, **lab) == \
                    h["host_io"][phase][d + "_bytes"] > 0
        # one store: the exposition reads the engine's own totals
        rows = reg.snapshot()[SERVING_DECODE_ROWS]
        assert rows["type"] == "counter"
        assert [s["value"] for s in rows["samples"]] == [3.0]


class TestOtherPaths:
    def test_chunked_priming_alternates_per_chunk_and_fetches_once(
            self, net):
        """``prime_padded=False``: 5 tokens prime as chunks of 4 + 1 —
        input and forward once a chunk (no chunk is built before the last
        was sent), one fetch per prime, and the widths dispatched are
        the tokens fed."""
        eng = GenerationEngine(net, V, slots=2, prime_padded=False)
        before = _counts()
        with _Paths() as paths:
            _greedy(eng, [1, 2, 3, 4, 5], 2)
            eng.run_until_idle()
        d = _delta(before)
        assert d["prefill.input"] == d["prefill.forward"] == 2
        assert d["prefill.fetch"] == eng._admissions == 1
        assert all("/" not in p for p in paths.names())
        h = eng.health()
        assert h["prefill"]["fed_tokens"] == \
            h["prefill"]["bucket_tokens"] == 5
        assert h["host_io"]["prefill"] == {"h2d_bytes": 5 * 4,
                                           "d2h_bytes": 1 * V * 4,
                                           "results": 1,
                                           "result_positions": 1}

    def test_a_speculative_engine_runs_the_same_phases(self, net):
        eng = GenerationEngine(
            net, V, slots=2, paging=PagedKVConfig(page_size=4),
            speculation=SpeculationConfig(
                draft=prompt_lookup_proposer(2), gamma=2))
        before, h0 = _counts(), eng.health()
        with _Paths() as paths:
            hs = [_greedy(eng, [1, 2, 1, 2, 1], 6),
                  _greedy(eng, [3, 4, 5], 4)]
            cycles = eng.run_until_idle()
        d, h1 = _delta(before), eng.health()
        assert all(h.done for h in hs)
        assert set(d) == set(ENGINE_SPANS)
        assert all("/" not in p for p in paths.names())
        n = h1["decode_dispatch"]["count"] - h0["decode_dispatch"]["count"]
        assert d["decode.fetch"] == d["engine.sample"] == n == cycles
        # the verify chunk is [S, 1 + gamma] wide: int32 ids up, the
        # float32 distributions of every position down
        assert h1["host_io"]["decode"] == {
            "h2d_bytes": n * 2 * 3 * 4, "d2h_bytes": n * 2 * V * 3 * 4}

    def test_a_rebuild_inside_a_cycle_stays_flat(self, net):
        """A decode fault mid-cycle: the supervisor re-primes both
        survivors inside the same cycle's sequence — more prefill phases,
        never one inside another."""
        eng = GenerationEngine(
            net, V, slots=2, supervisor=EngineSupervisor(),
            decode_chaos=chaos.FaultBurstInjector(n=2, k=1))
        before = _counts()
        with _Paths() as paths:
            hs = [_greedy(eng, [1, 2, 3], 5), _greedy(eng, [4, 5], 5)]
            eng.run_until_idle()
        assert all(h.done and h.error is None for h in hs)
        assert all("/" not in p for p in paths.names())
        assert current_path() == ""
        d = _delta(before)
        assert d["prefill.forward"] == d["prefill.fetch"] == 4

    def test_a_dispatch_the_cycle_does_not_watch_opens_no_span(self, net):
        """``step_tokens`` without the engine's `io` — a draft net on the
        cycling thread — stays inside the phase that is open: the
        ``decode.forward`` count stays the number of decode cycles."""
        net.rnn_clear_previous_state()
        before = _counts()
        try:
            with phases():
                next_phase("decode.input")
                step_tokens(net, [1], V)
                assert current_path() == "decode.input"
        finally:
            net.rnn_clear_previous_state()
        assert _delta(before) == {"decode.input": 1}

    def test_an_admission_outside_a_cycle_opens_its_own_sequence(self, net):
        """``_admit_one`` is reached without ``step()`` too (the
        prefill agent, ledger re-admission): there it opens, and closes,
        the admission phases itself."""
        eng = GenerationEngine(net, V, slots=2)
        req_handle = _greedy(eng, [1, 2, 3], 3)
        req = eng._pending.pop()
        before = _counts()
        with eng._lock:
            eng._admit_one(req, 0)
        assert current_path() == ""
        d = _delta(before)
        assert [d.get(n) for n in ADMISSION] == [1] * 5
        eng.run_until_idle()
        assert req_handle.done

    def test_the_engine_thread_names_its_own_cycle(self, net):
        eng = GenerationEngine(net, V, slots=2)
        eng.warmup(max_prompt_len=4)
        before = _counts()
        with _Paths() as paths:
            eng.start()
            try:
                time.sleep(0.1)               # idle polls: no span
                assert _delta(before) == {}
                h = _greedy(eng, [1, 2, 3], 3)
                assert len(h.result(timeout=60)) == 6
            finally:
                eng.shutdown()
        threads = {t for t, _ in paths.seen}
        assert len(threads) == 1 and threading.get_ident() not in threads
        assert set(paths.names()) == set(ENGINE_SPANS)


class TestNoCompilesWithSpansOn:
    def test_warm_traffic_compiles_nothing(self, net):
        monitoring.ensure_started()
        compiles = monitoring.global_registry().get(runtime.COMPILE_COUNTER)
        eng = GenerationEngine(net, V, slots=2,
                               paging=PagedKVConfig(page_size=4))
        eng.warmup(max_prompt_len=16)
        warm, before = compiles.total(), _counts()
        hs = [_greedy(eng, SYS + [i], 3 + i) for i in range(1, 5)]
        hs.append(_greedy(eng, [1, 2, 3], 2))
        eng.run_until_idle()
        assert all(h.done for h in hs)
        assert _delta(before)["engine.sample"] > 0    # spans were on
        assert compiles.total() == warm


class TestProfilerTrace:
    def test_every_span_reaches_the_trace_the_benchmark_reads(
            self, net, tmp_path):
        """A real ``jax.profiler`` trace of the engine on the CPU, read
        with the benchmark's own reduction: every phase is a host event
        under its name, and on one thread no two overlap."""
        import jax
        from benchmark.metrics import _spans
        from benchmark.xplane import Trace
        eng = GenerationEngine(net, V, slots=2,
                               paging=PagedKVConfig(page_size=4))
        eng.warmup(max_prompt_len=16)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            hs = [_greedy(eng, SYS + [1], 3), _greedy(eng, [1, 2], 4)]
            eng.run_until_idle()
        finally:
            jax.profiler.stop_trace()
        assert all(h.done for h in hs)
        host = Trace.from_dir(str(tmp_path)).host
        assert set(ENGINE_SPANS) == set(_spans.PROGRAM_SPANS)
        ours = sorted((a, b, n) for n, a, b in host if n in ENGINE_SPANS)
        assert {n for _, _, n in ours} == set(ENGINE_SPANS)
        for (_, end, name), (start, _, nxt) in zip(ours, ours[1:]):
            assert start >= end - 1e-9, (name, nxt)


def test_n_generated_counts_without_copying():
    h = GenerationStream([5, 6, 7])
    assert h.n_generated == 0
    h._push(3)
    h.relay_token(4)
    assert h.n_generated == 2 == len(h.ids) - len(h.prompt)
    h._finish("length")
    assert h.n_generated == 2
