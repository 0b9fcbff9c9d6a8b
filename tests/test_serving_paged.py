"""Serving engine v2 (paged KV arena + prefix cache + in-engine
speculation): bit-exactness vs one-shot sample_stream and vs the slot
arena, token-budget admission (incl. the oversized-request submit
rejection), page lifecycle/eviction, chaos page exhaustion, telemetry,
and the zero-retraces-after-warmup guard with every mode on."""

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import monitoring
from deeplearning4j_tpu.monitoring import runtime
from deeplearning4j_tpu.monitoring.metrics import MetricsRegistry
from deeplearning4j_tpu.nn.conf import layers as conf_layers
from deeplearning4j_tpu.resilience import chaos
from deeplearning4j_tpu.serving import (
    GenerationEngine, PagedKVConfig, SpeculationConfig)
from deeplearning4j_tpu.serving.health import (
    SERVING_KV_PAGES_TOTAL, SERVING_KV_PAGES_USED, SERVING_PREFIX_HITS,
    SERVING_PREFIX_MISSES, SERVING_SPEC_ACCEPTANCE)
from deeplearning4j_tpu.util.decoding import prompt_lookup_proposer
from deeplearning4j_tpu.zoo import (
    TextGenerationLSTM, TextGenerationTransformer)

V = 12
PROMPTS = [[1, 2, 3, 4, 5], [6, 7], [8, 9, 10, 1], [2, 4, 6], [3],
           [5, 5, 9]]
SYS = [7, 3, 9, 1, 4, 2, 8, 5]          # a ps=4 / ps=8 aligned prefix


@pytest.fixture(scope="module")
def rope_model():
    return TextGenerationTransformer(vocab_size=V, embed_dim=16,
                                     n_heads=2, n_layers=2,
                                     max_length=32, positional="rope")


@pytest.fixture(scope="module")
def rope_net(rope_model):
    return rope_model.init()


def drain(engine, handles):
    engine.run_until_idle()
    return [h.result(timeout=0) for h in handles]


def run_trace(net, prompts, steps=6, stagger=True, **engine_kw):
    """Submit `prompts` (staggered: one step between arrivals) and drain;
    returns (engine, outputs). Every request gets rng default_rng(i)."""
    eng = GenerationEngine(net, V, **engine_kw)
    hs = []
    for i, p in enumerate(prompts):
        hs.append(eng.submit(p, steps=steps,
                             rng=np.random.default_rng(i),
                             **getattr(run_trace, "submit_kw", {})))
        if stagger:
            eng.step()
    return eng, drain(eng, hs)


# ---------------------------------------------------------------------
# parity: paged arena == one-shot sample_stream == slot arena
# ---------------------------------------------------------------------
class TestPagedParity:
    def test_greedy_staggered_matches_one_shot(self, rope_model,
                                               rope_net):
        """Mixed-length prompts through 2 slots over a small page pool
        (pages are freed and re-allocated across retirements) — every
        request bit-equal to its one-shot sample_stream run."""
        eng = GenerationEngine(rope_net, V, slots=2,
                               paging=PagedKVConfig(page_size=4))
        hs = []
        for i, p in enumerate(PROMPTS[:2]):
            hs.append(eng.submit(p, steps=7, top_k=1,
                                 rng=np.random.default_rng(i)))
        eng.step()
        eng.step()
        for i, p in enumerate(PROMPTS[2:], start=2):
            hs.append(eng.submit(p, steps=7, top_k=1,
                                 rng=np.random.default_rng(i)))
            eng.step()
        got = drain(eng, hs)
        for i, p in enumerate(PROMPTS):
            want = rope_model.sample_stream(
                rope_net, p, steps=7, top_k=1,
                rng=np.random.default_rng(i))
            assert got[i] == want, p
        # retirement freed every slot page; only cached blocks remain
        assert eng.page_pool.used_count() == len(eng.prefix_cache)

    def test_sampled_mixed_configs_match_one_shot(self, rope_model,
                                                  rope_net):
        cfgs = [dict(temperature=0.7, top_k=3),
                dict(temperature=1.2, top_p=0.9),
                dict(top_k=1),
                dict(temperature=0.9)]
        eng = GenerationEngine(rope_net, V, slots=4,
                               paging=PagedKVConfig(page_size=4))
        hs = [eng.submit([1 + i, 2, 3], steps=6,
                         rng=np.random.default_rng(10 + i), **c)
              for i, c in enumerate(cfgs)]
        got = drain(eng, hs)
        for i, c in enumerate(cfgs):
            want = rope_model.sample_stream(
                rope_net, [1 + i, 2, 3], steps=6,
                rng=np.random.default_rng(10 + i), **c)
            assert got[i] == want, c

    def test_paged_equals_slot_arena_bitwise(self, rope_net):
        """The paged gather/scatter round trip is invisible: same
        staggered sampled trace through both arenas, identical ids."""
        kw = dict(steps=6, stagger=True)
        _, slot_out = run_trace(rope_net, PROMPTS, slots=2, **kw)
        _, paged_out = run_trace(rope_net, PROMPTS, slots=2,
                                 paging=PagedKVConfig(page_size=4), **kw)
        assert paged_out == slot_out

    def test_chunked_prime_matches_too(self, rope_model, rope_net):
        eng = GenerationEngine(rope_net, V, slots=2, prime_padded=False,
                               paging=PagedKVConfig(page_size=4))
        hs = [eng.submit(p, steps=4, top_k=1,
                         rng=np.random.default_rng(i))
              for i, p in enumerate(PROMPTS[:3])]
        got = drain(eng, hs)
        for i, p in enumerate(PROMPTS[:3]):
            assert got[i] == rope_model.sample_stream(
                rope_net, p, steps=4, top_k=1,
                rng=np.random.default_rng(i))


# ---------------------------------------------------------------------
# prefix cache
# ---------------------------------------------------------------------
class TestPrefixCache:
    def test_hit_miss_accounting(self, rope_net):
        reg = MetricsRegistry()
        prompts = [SYS + [t] for t in (2, 5, 9)] + [[9, 9, 2]]
        eng = GenerationEngine(rope_net, V, slots=4, registry=reg,
                               name="engine:pfx",
                               paging=PagedKVConfig(page_size=4))
        hs = [eng.submit(p, steps=4, top_k=1,
                         rng=np.random.default_rng(i))
              for i, p in enumerate(prompts)]
        drain(eng, hs)
        # first SYS request misses and caches 2 full blocks; the next
        # two hit them; the unrelated prompt misses
        assert eng.prefix_cache.hits == 2
        assert eng.prefix_cache.misses == 2
        assert eng.prefix_cache.reused_tokens == 2 * len(SYS)
        snap = reg.snapshot_compact()
        assert snap[SERVING_PREFIX_HITS + "{model=engine:pfx}"] == 2
        assert snap[SERVING_PREFIX_MISSES + "{model=engine:pfx}"] == 2

    def test_cache_on_off_bit_exact(self, rope_net):
        """Shared AND non-shared prompts, greedy and sampled: cache-on
        outputs equal cache-off outputs bit for bit."""
        prompts = [SYS + [t] for t in (2, 5)] + [[4, 1], SYS + [9, 9]]
        for extra in (dict(), dict(temperature=0.8, top_p=0.95)):
            run_trace.submit_kw = extra
            try:
                _, off = run_trace(
                    rope_net, prompts, slots=3,
                    paging=PagedKVConfig(page_size=4,
                                         prefix_cache=False))
                eng, on = run_trace(
                    rope_net, prompts, slots=3,
                    paging=PagedKVConfig(page_size=4))
            finally:
                run_trace.submit_kw = {}
            assert on == off, extra
            assert eng.prefix_cache.hits >= 2

    def test_eviction_under_page_pressure(self, rope_model, rope_net):
        """With the pool nearly consumed by cached blocks, a new
        admission evicts LRU unmapped entries instead of head-blocking
        forever — and its output is still exact."""
        ref = rope_model.sample_stream(rope_net, [5, 3] * 6, steps=8,
                                       top_k=1,
                                       rng=np.random.default_rng(0))
        eng = GenerationEngine(
            rope_net, V, slots=2,
            paging=PagedKVConfig(page_size=4, total_pages=10))
        seeds = [[1 + i] * 9 for i in range(3)]   # 2 full blocks each
        hs = [eng.submit(p, steps=2, top_k=1) for p in seeds]
        drain(eng, hs)
        assert len(eng.prefix_cache) == 6         # 3 x 2 cached blocks
        h = eng.submit([5, 3] * 6, steps=8, top_k=1,
                       rng=np.random.default_rng(0))   # needs 5 of the
        eng.run_until_idle()                           # 4 free pages
        assert h.result(timeout=0) == ref
        # one LRU block was evicted to fit it; its own 3 full blocks
        # were then cached: 6 - 1 + 3
        assert len(eng.prefix_cache) == 8

    def test_lru_survivors_still_hit(self, rope_net):
        """Eviction keeps recently used chains: after pressure, a
        repeat of the most recent seed still hits."""
        eng = GenerationEngine(
            rope_net, V, slots=2,
            paging=PagedKVConfig(page_size=4, total_pages=12))
        a, b = [1] * 9, [2] * 9
        drain(eng, [eng.submit(p, steps=2, top_k=1) for p in (a, b)])
        drain(eng, [eng.submit(b, steps=2, top_k=1)])   # touch b
        h = eng.submit([5, 3] * 6, steps=8, top_k=1)    # forces eviction
        eng.run_until_idle()
        h.result(timeout=0)
        hits0 = eng.prefix_cache.hits
        drain(eng, [eng.submit(b + [7], steps=2, top_k=1)])
        assert eng.prefix_cache.hits > hits0

    def test_recurrent_state_rejects_prefix_cache(self):
        lstm = TextGenerationLSTM(vocab_size=10, hidden=12, layers=1,
                                  max_length=40).init()
        with pytest.raises(ValueError, match="pages"):
            GenerationEngine(lstm, 10, slots=2,
                             paging=PagedKVConfig(page_size=4))


# ---------------------------------------------------------------------
# token-budget admission (satellite: admission-time capacity bugfix)
# ---------------------------------------------------------------------
class TestPagedAdmission:
    def test_oversized_request_rejected_at_submit(self, rope_net):
        """A request whose prompt + steps can NEVER fit the page budget
        fails at submit — it is not admitted and retired mid-stream."""
        eng = GenerationEngine(
            rope_net, V, slots=2,
            paging=PagedKVConfig(page_size=4, total_pages=4))
        with pytest.raises(ValueError, match="never"):
            eng.submit([1, 2, 3, 4], steps=20, top_k=1)
        # an in-budget request on the same engine still serves
        h = eng.submit([1, 2, 3], steps=4, top_k=1)
        eng.run_until_idle()
        assert h.finish_reason == "length"

    def test_token_budget_admits_beyond_worst_case(self, rope_net):
        """Short requests hold few pages: a pool sized for TWO
        worst-case streams runs FOUR short requests concurrently."""
        eng = GenerationEngine(
            rope_net, V, slots=4,
            paging=PagedKVConfig(page_size=4, total_pages=16))
        hs = [eng.submit([1 + i, 2], steps=6, top_k=1,
                         rng=np.random.default_rng(i))
              for i in range(4)]
        eng.step()
        assert eng.active_slots() == 4     # all admitted immediately
        drain(eng, hs)

    def test_head_blocks_until_pages_free(self, rope_net):
        """A request needing more pages than are free queues (head-of-
        line) and admits as soon as retirement frees them."""
        eng = GenerationEngine(
            rope_net, V, slots=2,
            paging=PagedKVConfig(page_size=4, total_pages=8,
                                 prefix_cache=False))
        big = eng.submit([1] * 12, steps=8, top_k=1)    # 5 pages
        eng.step()
        big2 = eng.submit([2] * 12, steps=8, top_k=1)   # queues: 5 > 3
        eng.step()
        assert eng.active_slots() == 1
        assert eng.queue_depth() == 1
        drain(eng, [big, big2])
        assert big.finish_reason == "length"
        assert big2.finish_reason == "length"

    def test_pages_free_immediately_on_retirement(self, rope_net):
        eng = GenerationEngine(
            rope_net, V, slots=2,
            paging=PagedKVConfig(page_size=4, prefix_cache=False))
        h = eng.submit([1, 2, 3, 4, 5], steps=4, top_k=1)
        eng.step()
        assert eng.page_pool.used_count() > 0
        drain(eng, [h])
        assert eng.page_pool.used_count() == 0

    def test_pure_recurrent_net_rejects_paging(self):
        lstm = TextGenerationLSTM(vocab_size=10, hidden=12, layers=1,
                                  max_length=40).init()
        with pytest.raises(ValueError, match="paged"):
            GenerationEngine(lstm, 10, slots=2,
                             paging=PagedKVConfig(page_size=4,
                                                  prefix_cache=False))

    def test_windowed_cache_rejects_paging(self):
        net = TextGenerationTransformer(
            vocab_size=V, embed_dim=16, n_heads=2, n_layers=1,
            max_length=64, positional="rope", window=8).init()
        with pytest.raises(ValueError, match="rolling"):
            GenerationEngine(net, V, slots=2,
                             paging=PagedKVConfig(page_size=4))


# ---------------------------------------------------------------------
# in-engine speculation
# ---------------------------------------------------------------------
class TestSpeculation:
    def spec(self, gamma=3):
        return SpeculationConfig(draft=prompt_lookup_proposer(2),
                                 gamma=gamma)

    def test_greedy_matches_one_shot(self, rope_model, rope_net):
        """Greedy speculative outputs are the argmax chain regardless
        of acceptance pattern — bit-identical to plain sample_stream,
        on both arenas."""
        prompts = [p * 3 for p in PROMPTS[:4]]   # repetition: real hits
        ref = [rope_model.sample_stream(rope_net, p, steps=8, top_k=1,
                                        rng=np.random.default_rng(i))
               for i, p in enumerate(prompts)]
        for paging in (None, PagedKVConfig(page_size=4)):
            run_trace.submit_kw = dict(top_k=1)
            try:
                eng, got = run_trace(rope_net, prompts, steps=8,
                                     slots=2, paging=paging,
                                     speculation=self.spec())
            finally:
                run_trace.submit_kw = {}
            assert got == ref, paging
            assert eng._dispatches > 0

    def test_sampled_identical_across_arenas(self, rope_net):
        """Sampled speculation preserves the target distribution; the
        drawn SEQUENCE is additionally pinned identical across slot /
        paged / paged+prefix arenas (same per-request rngs)."""
        prompts = [p * 2 for p in PROMPTS[:3]]
        run_trace.submit_kw = dict(temperature=0.9, top_p=0.9)
        try:
            outs = [run_trace(rope_net, prompts, steps=6, slots=2,
                              paging=pg, speculation=self.spec())[1]
                    for pg in (None,
                               PagedKVConfig(page_size=4,
                                             prefix_cache=False),
                               PagedKVConfig(page_size=4))]
        finally:
            run_trace.submit_kw = {}
        assert outs[0] == outs[1] == outs[2]

    def test_stop_tokens_cut_like_one_shot(self, rope_model, rope_net):
        ref0 = rope_model.sample_stream(rope_net, PROMPTS[0] * 3,
                                        steps=10, top_k=1,
                                        rng=np.random.default_rng(0))
        stop = ref0[len(PROMPTS[0] * 3) + 1]
        eng = GenerationEngine(rope_net, V, slots=2,
                               speculation=self.spec())
        hs = [eng.submit(p * 3, steps=10, top_k=1, stop_tokens=(stop,),
                         rng=np.random.default_rng(i))
              for i, p in enumerate(PROMPTS[:2])]
        got = drain(eng, hs)
        for i, p in enumerate(PROMPTS[:2]):
            assert got[i] == rope_model.sample_stream(
                rope_net, p * 3, steps=10, top_k=1, stop_tokens=(stop,),
                rng=np.random.default_rng(i))

    def test_acceptance_telemetry(self, rope_net):
        reg = MetricsRegistry()
        eng = GenerationEngine(rope_net, V, slots=2, registry=reg,
                               name="engine:spec",
                               speculation=self.spec())
        hs = [eng.submit([1, 2] * 6, steps=8, top_k=1)]
        drain(eng, hs)
        snap = reg.snapshot_compact()
        hist = snap[SERVING_SPEC_ACCEPTANCE + "{model=engine:spec}"]
        assert hist["count"] > 0
        # a periodic prompt + prompt-lookup drafting must accept > 0
        assert hist["sum"] > 0

    def test_headroom_enforced_at_submit(self, rope_net):
        eng = GenerationEngine(rope_net, V, slots=2,
                               speculation=self.spec(gamma=4))
        with pytest.raises(ValueError, match="headroom"):
            eng.submit([1, 2, 3], steps=29, top_k=1)   # 32 = cap > 29
        h = eng.submit([1, 2, 3], steps=20, top_k=1)
        eng.run_until_idle()
        assert h.finish_reason == "length"

    def test_lstm_rejects_speculation(self):
        lstm = TextGenerationLSTM(vocab_size=10, hidden=12, layers=1,
                                  max_length=40).init()
        with pytest.raises(ValueError, match="rewound|recurrent"):
            GenerationEngine(lstm, 10, slots=2, speculation=self.spec())

    def test_model_draft_rejected(self, rope_net):
        with pytest.raises(TypeError, match="proposer"):
            SpeculationConfig(draft=rope_net, gamma=2)


# ---------------------------------------------------------------------
# chaos: page exhaustion degrades gracefully (satellite)
# ---------------------------------------------------------------------
class TestPageExhaustionChaos:
    def test_seized_pool_blocks_admissions_not_streams(self, rope_model,
                                                       rope_net):
        """Free pages vanish mid-flight (chaos seize at dispatch 1):
        active requests complete bit-identically to an unperturbed run;
        a request needing the seized capacity stays queued — even after
        the actives retire and return THEIR pages — until release()."""
        refs = [rope_model.sample_stream(rope_net, p, steps=6, top_k=1,
                                         rng=np.random.default_rng(i))
                for i, p in enumerate(PROMPTS[:2])]
        ref_late = rope_model.sample_stream(
            rope_net, [4, 5, 6], steps=21, top_k=1,
            rng=np.random.default_rng(9))
        eng = GenerationEngine(
            rope_net, V, slots=3,
            paging=PagedKVConfig(page_size=4, total_pages=6,
                                 prefix_cache=False))
        inj = chaos.PageExhaustionInjector(eng.page_pool, n=1,
                                           free_target=0)
        eng._decode_chaos = inj
        hs = [eng.submit(p, steps=6, top_k=1,
                         rng=np.random.default_rng(i))
              for i, p in enumerate(PROMPTS[:2])]   # 3 + 2 of 6 pages
        eng.step()
        eng.step()                        # injector fires: free -> 0
        assert eng.page_pool.free_count() == 0
        late = eng.submit([4, 5, 6], steps=21, top_k=1,
                          rng=np.random.default_rng(9))   # needs all 6
        eng.step()
        assert eng.queue_depth() == 1     # head-blocked, not admitted
        got = drain(eng, hs)              # actives unaffected
        assert got == refs
        assert not late.done              # still starved after drain
        inj.release()
        eng.run_until_idle()
        assert late.result(timeout=0) == ref_late


# ---------------------------------------------------------------------
# telemetry: page gauges ride the registry
# ---------------------------------------------------------------------
class TestPagedTelemetry:
    def test_page_gauges(self, rope_net):
        reg = MetricsRegistry()
        eng = GenerationEngine(
            rope_net, V, slots=2, registry=reg, name="engine:pg",
            paging=PagedKVConfig(page_size=4, total_pages=12,
                                 prefix_cache=False))
        h = eng.submit([1, 2, 3, 4, 5], steps=6, top_k=1)
        eng.step()
        snap = reg.snapshot_compact()
        assert snap[SERVING_KV_PAGES_TOTAL + "{model=engine:pg}"] == 12
        assert snap[SERVING_KV_PAGES_USED + "{model=engine:pg}"] > 0
        drain(eng, [h])
        snap = reg.snapshot_compact()
        assert snap[SERVING_KV_PAGES_USED + "{model=engine:pg}"] == 0


# ---------------------------------------------------------------------
# acceptance: zero retraces after warmup, every mode on
# ---------------------------------------------------------------------
def _compile_total():
    c = monitoring.global_registry().get(runtime.COMPILE_COUNTER)
    return 0.0 if c is None else c.total()


class TestNoRetracePagedAfterWarmup:
    def test_staggered_paged_spec_prefix_traffic_compiles_nothing(self):
        """After warmup(), staggered mixed-length admissions — some
        sharing a system prompt (prefix hits), all speculating, pages
        recycling through retirements — hit only warm shapes."""
        monitoring.ensure_started()
        model = TextGenerationTransformer(vocab_size=V, embed_dim=16,
                                          n_heads=2, n_layers=1,
                                          max_length=64,
                                          positional="rope")
        net = model.init()
        eng = GenerationEngine(
            net, V, slots=4, paging=PagedKVConfig(page_size=8),
            speculation=SpeculationConfig(
                draft=prompt_lookup_proposer(2), gamma=3))
        eng.warmup(max_prompt_len=16)
        warm = _compile_total()
        rng = np.random.default_rng(0)
        hs = []
        for i in range(12):
            n = int(rng.integers(1, 16))
            p = (SYS + list(rng.integers(1, V, n - 8))
                 if i % 2 and n > 8 else list(rng.integers(1, V, n)))
            hs.append(eng.submit(p, steps=int(rng.integers(2, 10)),
                                 top_k=1, rng=np.random.default_rng(i)))
            eng.step()
        eng.run_until_idle()
        assert all(h.done for h in hs)
        assert eng.prefix_cache.hits > 0      # the hit path really ran
        assert _compile_total() == warm, (
            "paged/speculative serving retraced after warmup")


# ---------------------------------------------------------------------
# the append's form: same bytes at the same places as the scatter over
# page and row alone, which it replaced (PR 35)
# ---------------------------------------------------------------------
def _append_page_and_row(pool, page, off, rows):
    """The form the layer had: a [Hkv, D] window a token."""
    return pool.at[page, :, off, :].set(rows)


class TestPagedAppendForm:
    """``_stream_attend_paged`` run twice over the same state, once with
    ``_paged_append`` and once with the old scatter in its place: the
    pools (and the int8 sidecars) come out byte for byte the same, and
    every page no row appends to is as it was."""
    PS, HKV, D, L, N_BLK, PAGES = 4, 2, 8, 32, 8, 24

    def layer(self):
        return conf_layers.SelfAttentionLayer(
            n_in=32, n_out=32, n_heads=4, n_kv_heads=self.HKV, rope=True,
            cache_length=self.L)

    def state(self, kv_dtype, pos, table, prime=False):
        rng = np.random.default_rng(3)
        shape = (self.PAGES, self.HKV, self.PS, self.D)
        st = {"kv_pos": jnp.asarray(pos, jnp.int32),
              "kv_page_table": jnp.asarray(table, jnp.int32)}
        for name in ("k", "v"):
            if kv_dtype == "int8":
                st["kv_page_" + name] = jnp.asarray(
                    rng.integers(-127, 128, shape), jnp.int8)
                st["kv_page_scale_" + name] = jnp.asarray(
                    2.0 ** rng.integers(-6, -2, shape[:2]), jnp.float32)
            else:
                st["kv_page_" + name] = jnp.asarray(
                    rng.normal(size=shape), jnp.bfloat16)
        if prime:
            st["kv_page_prime"] = jnp.zeros((), jnp.int32)
        return st

    def both_forms(self, monkeypatch, st, t, pad_left=None):
        rng = np.random.default_rng(4)
        n = st["kv_pos"].shape[0]
        q = jnp.asarray(rng.normal(size=(n, 4, t, self.D)), jnp.bfloat16)
        k, v = (jnp.asarray(rng.normal(size=(n, self.HKV, t, self.D)),
                            jnp.bfloat16) for _ in range(2))
        layer = self.layer()
        _, new = layer._stream_attend_paged(q, k, v, dict(st),
                                            pad_left=pad_left)
        monkeypatch.setattr(conf_layers, "_paged_append",
                            _append_page_and_row)
        _, old = layer._stream_attend_paged(q, k, v, dict(st),
                                            pad_left=pad_left)
        return new, old

    def check(self, st, new, old, written):
        """Same bytes off the null page (where masked rows collide and
        nothing reads), and no page but ``written`` touched."""
        rest = np.setdiff1d(np.arange(1, self.PAGES), written)
        for key in sorted(k for k in st if k.startswith("kv_page_")
                          and k not in ("kv_page_table", "kv_page_prime")):
            a, b, was = (np.asarray(x[key]) for x in (new, old, st))
            assert a.dtype == b.dtype == was.dtype, key
            assert a[1:].tobytes() == b[1:].tobytes(), key
            assert a[rest].tobytes() == was[rest].tobytes(), key
        touched = np.asarray(new["kv_page_k"]) != np.asarray(st["kv_page_k"])
        assert touched[np.asarray(written)].any()
        assert np.array_equal(np.asarray(new["kv_pos"]),
                              np.asarray(old["kv_pos"]))

    @pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
    @pytest.mark.parametrize("t", [1, 5])
    def test_decode_and_verify_chunks_write_the_same_bytes(
            self, monkeypatch, kv_dtype, t):
        """Rows 0 and 1 share the read-only prefix page 9 and append in
        pages of their own; row 2 is idle (an empty table: the null
        page); row 3 runs past its capacity inside the chunk."""
        table = np.zeros((4, self.N_BLK), np.int32)
        table[0, :4] = [9, 2, 3, 4]
        table[1, :4] = [9, 5, 6, 7]
        table[3, :] = np.arange(10, 18)
        pos = [6, 4, 0, self.L - 2]
        st = self.state(kv_dtype, pos, table)
        new, old = self.both_forms(monkeypatch, st, t)
        written = {1: [2, 5, 17], 5: [2, 3, 5, 6, 17]}[t]
        self.check(st, new, old, written)
        for out in (new, old):                  # the shared page is read-only
            assert np.asarray(out["kv_page_k"])[9].tobytes() == \
                np.asarray(st["kv_page_k"])[9].tobytes()

    @pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
    def test_a_left_padded_prime_chunk_writes_the_same_bytes(
            self, monkeypatch, kv_dtype):
        """Prime through the pool, one row: 3 pads and the 4 positions
        of a prefix hit (page 9) go to the null page, the 5 fresh ones
        to pages 2 and 3."""
        table = np.zeros((1, self.N_BLK), np.int32)
        table[0, :3] = [9, 2, 3]
        st = self.state(kv_dtype, [4], table, prime=True)
        new, old = self.both_forms(monkeypatch, st, 8,
                                   pad_left=jnp.asarray(3, jnp.int32))
        self.check(st, new, old, [2, 3])
        assert int(new["kv_pos"][0]) == 9

    def test_the_helper_alone_writes_the_null_page_too(self):
        """No two rows collide here, so the null page compares as well."""
        rng = np.random.default_rng(5)
        pool = jnp.asarray(rng.normal(size=(6, 3, 4, 8)), jnp.bfloat16)
        page = jnp.asarray([[0, 0], [2, 3], [5, 5]], jnp.int32)
        off = jnp.asarray([[1, 2], [3, 0], [0, 1]], jnp.int32)
        rows = jnp.asarray(rng.normal(size=(3, 2, 3, 8)), jnp.bfloat16)
        a = np.asarray(conf_layers._paged_append(pool, page, off, rows))
        b = np.asarray(_append_page_and_row(pool, page, off, rows))
        assert a.tobytes() == b.tobytes()
        assert a[0, 1, 2].tobytes() == np.asarray(rows)[0, 1, 1].tobytes()
        assert a[[1, 4]].tobytes() == np.asarray(pool)[[1, 4]].tobytes()
