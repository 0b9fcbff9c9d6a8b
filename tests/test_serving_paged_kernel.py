"""Direct paged decode (serving/paged_kernel.py + the engine fast
path): the paged-attention kernel vs the dense-gather reference, the
chooser's truth table, engine bit-exactness vs one-shot / slot arena on
BOTH reads (XLA fallback and interpret-mode Pallas kernel) — greedy and
sampled, prefix cache with shared blocks, in-engine speculation — plus
the cached-table invariants, the KV-traffic telemetry, supervisor
recovery re-entering the direct path, two engines of different reads in
one process, and the zero-retraces-after-warmup guard with the kernel
path enabled."""

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import monitoring
from deeplearning4j_tpu.monitoring import runtime
from deeplearning4j_tpu.monitoring.metrics import MetricsRegistry
from deeplearning4j_tpu.resilience import chaos
from deeplearning4j_tpu.serving import (
    EngineSupervisor, GenerationEngine, PagedKVConfig, SpeculationConfig)
from deeplearning4j_tpu.serving.health import (
    SERVING_DISPATCH_LATENCY, SERVING_KV_BYTES_MOVED)
from deeplearning4j_tpu.serving.paged_kernel import (
    choose_paged_read, pages_per_step, paged_attention,
    paged_attention_supported, paged_ref_attention)
from deeplearning4j_tpu.util.decoding import prompt_lookup_proposer
from deeplearning4j_tpu.zoo import TextGenerationTransformer

V = 12
PROMPTS = [[1, 2, 3, 4, 5], [6, 7], [8, 9, 10, 1], [2, 4, 6], [3],
           [5, 5, 9]]

#: the two direct-decode impls under test on CPU: the XLA fallback and
#: the Pallas kernel in interpret mode (same kernel code path the TPU
#: compiles — the pallas_attention testing contract)
DIRECT_IMPLS = [
    pytest.param(dict(decode_impl="xla"), id="xla"),
    pytest.param(dict(decode_impl="pallas", kernel_interpret=True),
                 id="pallas-interpret"),
]


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


def run_trace(net, prompts, steps=6, stagger=True, submit_kw=None,
              **engine_kw):
    eng = GenerationEngine(net, V, **engine_kw)
    hs = []
    for i, p in enumerate(prompts):
        hs.append(eng.submit(p, steps=steps,
                             rng=np.random.default_rng(i),
                             **(submit_kw or {})))
        if stagger:
            eng.step()
    return eng, drain(eng, hs)


# ---------------------------------------------------------------------
# the kernel itself vs the dense-gather reference
# ---------------------------------------------------------------------
def _paged_case(S=3, hkv=2, reps=2, qw=3, d=8, ps=4, nb=5, seed=0):
    rng = np.random.default_rng(seed)
    P = S * nb + 1
    rw = reps * qw
    q = jnp.asarray(rng.normal(size=(S, hkv, rw, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, hkv, ps, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, hkv, ps, d)), jnp.float32)
    # distinct pages per row (page 0 reserved null)
    table = jnp.asarray(
        rng.permutation(np.arange(1, P))[:S * nb].reshape(S, nb),
        jnp.int32)
    lengths = jnp.asarray(
        rng.integers(qw, nb * ps + 1, S), jnp.int32)
    return q, kp, vp, table, lengths


class TestPagedKernel:
    @pytest.mark.parametrize("qw", [1, 3, 5])
    def test_matches_reference(self, qw):
        """Query widths 1 (plain decode), 1+gamma (speculative verify):
        the online-softmax kernel equals the dense-gather softmax."""
        q, kp, vp, table, lengths = _paged_case(qw=qw)
        out = paged_attention(q, kp, vp, table, lengths,
                              query_width=qw, interpret=True)
        ref = paged_ref_attention(q, kp, vp, table, lengths,
                                  query_width=qw)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_dead_blocks_skipped_null_page_invisible(self):
        """Rows shorter than their table: blocks past the length map to
        junk pages — poison them and the output must not change (the
        pl.when skip + causal mask keep them invisible)."""
        q, kp, vp, table, lengths = _paged_case(qw=1)
        lengths = jnp.asarray([2, 5, 9], jnp.int32)   # nb*ps = 20
        out = paged_attention(q, kp, vp, table, lengths,
                              query_width=1, interpret=True)
        # NaN-poison every page beyond each row's live blocks
        poison_k, poison_v = np.array(kp), np.array(vp)
        tbl = np.asarray(table)
        live = set()
        ps = kp.shape[2]
        for s, ln in enumerate(np.asarray(lengths)):
            for b in range(-(-int(ln) // ps)):
                live.add(int(tbl[s, b]))
        for p in range(kp.shape[0]):
            if p not in live:
                poison_k[p] = np.nan
                poison_v[p] = np.nan
        out_p = paged_attention(jnp.asarray(q), jnp.asarray(poison_k),
                                jnp.asarray(poison_v), table, lengths,
                                query_width=1, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out_p))

    def test_shared_prefix_page_reads(self):
        """Two rows mapping the SAME physical page (prefix sharing) read
        identical bytes through their own tables."""
        q, kp, vp, table, lengths = _paged_case(S=2, qw=1, nb=3)
        tbl = np.array(table)
        tbl[1, 0] = tbl[0, 0]                 # share block 0
        lengths = jnp.asarray([9, 9], jnp.int32)
        q = jnp.asarray(np.broadcast_to(np.asarray(q[:1]), q.shape))
        out = paged_attention(q, kp, vp, jnp.asarray(tbl), lengths,
                              query_width=1, interpret=True)
        ref = paged_ref_attention(q, kp, vp, jnp.asarray(tbl), lengths,
                                  query_width=1)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_query_width_must_divide_rows(self):
        q, kp, vp, table, lengths = _paged_case(qw=3)
        with pytest.raises(ValueError, match="query_width"):
            paged_attention(q, kp, vp, table, lengths, query_width=4,
                            interpret=True)

    def test_supported_gate(self):
        assert paged_attention_supported((100, 2, 16, 128), 1)
        assert paged_attention_supported((100, 2, 8, 128), 4)
        assert paged_attention_supported((100, 2, 24, 256), 1)
        # a page copy's slice must sit on the pool's (8, 128) tiling
        assert not paged_attention_supported((100, 2, 8, 64), 4)
        assert not paged_attention_supported((100, 2, 16, 48), 1)
        assert not paged_attention_supported((100, 2, 6, 128), 1)
        assert not paged_attention_supported((100, 2, 12, 128), 1)
        assert not paged_attention_supported((100, 2, 16), 1)


# ---------------------------------------------------------------------
# the grouped walk: one grid step copies and scores G table entries of
# a row, for every kv head (interpret mode, against the reference)
# ---------------------------------------------------------------------
def _grouped_case(S, nb, ps, lengths, *, hkv=2, reps=2, qw=1, d=8,
                  dtype=jnp.float32, seed=0):
    """A pool whose dead table entries route to the null page, as the
    engine's tables do; the null page and every page no row maps are
    NaN: no byte of them may reach arithmetic."""
    rng = np.random.default_rng(seed)
    P = S * nb + 1
    q = jnp.asarray(rng.normal(size=(S, hkv, reps * qw, d)), dtype)
    kp = rng.normal(size=(P, hkv, ps, d))
    vp = rng.normal(size=(P, hkv, ps, d))
    table = rng.permutation(np.arange(1, P)).reshape(S, nb)
    lengths = np.asarray(lengths)
    live = {0}
    for s, ln in enumerate(lengths):
        n_live = -(-int(ln) // ps)
        table[s, n_live:] = 0
        live.update(int(p) for p in table[s, :n_live])
    live.discard(0)
    dead = [p for p in range(P) if p not in live]
    return (q, jnp.asarray(kp, dtype), jnp.asarray(vp, dtype),
            jnp.asarray(table, jnp.int32),
            jnp.asarray(lengths, jnp.int32), dead)


def _assert_walk_matches(q, kp, vp, table, lengths, dead, qw, tol):
    ref = paged_ref_attention(q, kp, vp, table, lengths, query_width=qw)
    poison = jnp.asarray(dead, jnp.int32)
    out = paged_attention(q, kp.at[poison].set(jnp.nan),
                          vp.at[poison].set(jnp.nan), table, lengths,
                          query_width=qw, interpret=True)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


class TestGroupedWalk:
    @pytest.mark.parametrize("nb,ps,want", [
        (256, 16, 32),     # the benchmark's table: 512 keys a step
        (5, 4, 5),         # a table narrower than a group
        (200, 4, 128),     # small pages: still 512 keys
        (64, 32, 16),
        (64, 1024, 1),     # a page wider than a group: one a step
    ])
    def test_pages_per_step_follows_the_shape(self, nb, ps, want):
        assert pages_per_step((9, 2, ps, 128), nb, 2) == want

    def test_pages_per_step_keeps_inside_vmem(self):
        """Two slots of K and of V pages stay inside the budget: a wide
        multi-head pool gets fewer pages a step, never none."""
        g = pages_per_step((9, 32, 16, 256), 256, 4)
        assert 1 <= g < 32
        assert 4 * g * 32 * 16 * 256 * 4 <= 4 * 1024 * 1024
        assert pages_per_step((9, 64, 64, 512), 256, 4) == 1

    @pytest.mark.parametrize("nb", [3, 5, 130, 200],
                             ids=lambda n: f"nb{n}")
    def test_table_width_not_a_multiple_of_the_group(self, nb):
        """n_max smaller than G (one short group), and n_max that
        leaves a ragged last group (G = 128 at ps = 4): rows that end
        in the first, a middle and the ragged group, and a full one."""
        ps = 4
        assert pages_per_step((1, 2, ps, 8), nb, 4) == min(nb, 128)
        assert nb < 128 or nb % 128
        full = nb * ps
        case = _grouped_case(4, nb, ps, [1, full // 2 + 1, full - 1,
                                         full])
        _assert_walk_matches(*case, qw=1, tol=2e-5)

    @pytest.mark.parametrize("qw", [1, 3])
    @pytest.mark.parametrize("edge", [-1, 0, 1], ids=["under", "at",
                                                      "over"])
    def test_row_lengths_at_a_group_edge(self, edge, qw):
        """Rows k·G·ps − 1, k·G·ps and k·G·ps + 1 keys long (k = 1, 2)
        beside a row of one chunk: the last live group is full, one key
        into the next, or one key short."""
        ps = 4
        G = pages_per_step((1, 2, ps, 8), 10 ** 6, 4)
        nb = 2 * G + 2
        assert pages_per_step((1, 2, ps, 8), nb, 4) == G
        lengths = [G * ps + edge, 2 * G * ps + edge, qw]
        case = _grouped_case(3, nb, ps, lengths, qw=qw)
        _assert_walk_matches(*case, qw=qw, tol=2e-5)

    def test_dead_pages_inside_a_partly_live_group_are_never_read(self):
        """A group of 5 table entries of which 1 to 4 hold keys: the
        others route to the NaN-poisoned null page and must reach
        neither the scores nor the PV product (0 × NaN is NaN)."""
        case = _grouped_case(4, 5, 4, [1, 6, 11, 16])
        assert pages_per_step(case[1].shape, 5, 4) == 5
        _assert_walk_matches(*case, qw=1, tol=2e-5)

    def test_stale_buffer_rows_never_reach_the_pv_product(self):
        """Keys past a row's length inside its last live page are
        copied with the page; NaN there must stay out of the output
        like the pages that were never copied (V is selected on the
        key's position, the scores masked)."""
        q, kp, vp, table, lengths, dead = _grouped_case(
            3, 5, 4, [1, 6, 11])
        ref = paged_ref_attention(q, kp, vp, table, lengths,
                                  query_width=1)
        kp, vp = np.array(kp), np.array(vp)
        for s, ln in enumerate(np.asarray(lengths)):
            page = int(table[s, (int(ln) - 1) // 4])
            kp[page, :, int(ln) % 4:] = np.nan
            vp[page, :, int(ln) % 4:] = np.nan
        out = paged_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                              table, lengths, query_width=1,
                              interpret=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_two_rows_share_pages_inside_one_group(self):
        """Prefix sharing: two rows map the same two physical pages in
        their first group and read identical bytes through their own
        tables; their tails differ."""
        q, kp, vp, table, lengths, dead = _grouped_case(
            2, 5, 4, [13, 18])
        tbl = np.array(table)
        dead = sorted(set(dead) | {int(p) for p in tbl[1, :2]})
        tbl[1, :2] = tbl[0, :2]
        _assert_walk_matches(q, kp, vp, jnp.asarray(tbl), lengths, dead,
                             qw=1, tol=2e-5)

    @pytest.mark.parametrize("qw", [1, 3])
    def test_bf16_pool_at_the_benchmarks_shape(self, qw):
        """Hkv 2, 12 query heads a kv head, 16-row pages, D 128, a
        table 40 entries wide: G resolves to 32 as in the chat cell (a
        full group and a ragged one), bfloat16 in and out."""
        nb, ps = 40, 16
        case = _grouped_case(3, nb, ps, [qw, 505, nb * ps], reps=12,
                             qw=qw, d=128, dtype=jnp.bfloat16)
        assert pages_per_step(case[1].shape, 256, 2) == 32
        assert pages_per_step(case[1].shape, nb, 2) == 32
        out = paged_attention(*case[:5], query_width=qw, interpret=True)
        assert out.dtype == jnp.bfloat16
        assert out.shape == case[0].shape
        _assert_walk_matches(*case, qw=qw, tol=2e-2)


# ---------------------------------------------------------------------
# the chooser: which code reads the pool, from what can be observed
# ---------------------------------------------------------------------
PLAIN = ("kv_k", "kv_v")
LATENT = ("kv_c", "kv_r", "kv_i")
TILED = (64, 2, 16, 128)        # passes the kernel's gate
NARROW = (64, 2, 4, 8)          # head dim off the 128 lanes
BIG_INT8 = (1025, 2, 16, 128)   # int8 scale sidecars outgrow SMEM


class TestChoosePagedRead:
    @pytest.mark.parametrize(
        "backend, shapes, leaves, kv_dtype, asked, interpret, want", [
            # auto: the kernel iff a TPU and every pool passes the gate
            ("tpu", [TILED], PLAIN, "bf16", "auto", False,
             ("pallas", False)),
            ("tpu", [NARROW], PLAIN, "bf16", "auto", False,
             ("xla", False)),
            ("tpu", [TILED, NARROW], PLAIN, "bf16", "auto", False,
             ("xla", False)),
            ("cpu", [TILED], PLAIN, "bf16", "auto", False,
             ("xla", False)),
            ("tpu", [TILED], PLAIN, "int8", "auto", False,
             ("pallas", False)),
            ("tpu", [BIG_INT8], PLAIN, "int8", "auto", False,
             ("xla", False)),
            ("tpu", [BIG_INT8], PLAIN, "bf16", "auto", False,
             ("pallas", False)),
            # an explicit value is taken, whatever the gate says
            ("cpu", [NARROW], PLAIN, "bf16", "pallas", True,
             ("pallas", True)),
            ("tpu", [NARROW], PLAIN, "bf16", "pallas", False,
             ("pallas", False)),
            ("tpu", [TILED], PLAIN, "bf16", "xla", False,
             ("xla", False)),
            # interpret is the kernel's: it says nothing of the gather
            ("cpu", [TILED], PLAIN, "bf16", "xla", True,
             ("xla", False)),
            # leaves no kernel reads: the layers' own paged form
            ("tpu", (), LATENT, "bf16", "auto", False, ("xla", False)),
            ("tpu", (), LATENT + PLAIN, "bf16", "xla", True,
             ("xla", False)),
        ])
    def test_truth_table(self, backend, shapes, leaves, kv_dtype, asked,
                         interpret, want):
        assert choose_paged_read(
            leaves, shapes, kv_dtype=kv_dtype, decode_impl=asked,
            kernel_interpret=interpret, backend=backend) == want

    @pytest.mark.parametrize("kv_dtype, asked", [("bf16", "pallas"),
                                                 ("int8", "auto")])
    def test_kernel_or_int8_asked_of_other_leaves_is_an_error(
            self, kv_dtype, asked):
        with pytest.raises(ValueError, match="keys and values only"):
            choose_paged_read(LATENT, (), kv_dtype=kv_dtype,
                              decode_impl=asked, kernel_interpret=False,
                              backend="tpu")

    def test_auto_on_a_cpu_engine_is_xla(self, rope_net):
        eng = GenerationEngine(rope_net, V, slots=2,
                               paging=PagedKVConfig(page_size=8))
        assert eng.health()["kv_traffic"]["decode_path"] == "direct-xla"
        # no layer selects: no selected read to name
        assert "selected_read" not in eng.health()["kv_traffic"]
        assert set(rope_net._paged_reads()) == {("xla", False)}


# ---------------------------------------------------------------------
# engine bit-exactness with the direct path on (both impls)
# ---------------------------------------------------------------------
class TestDirectParity:
    @pytest.mark.parametrize("impl", DIRECT_IMPLS)
    def test_greedy_staggered_matches_one_shot(self, rope_model,
                                               rope_net, impl):
        eng, got = run_trace(
            rope_net, PROMPTS, steps=7, slots=2,
            submit_kw=dict(top_k=1),
            paging=PagedKVConfig(page_size=4, **impl))
        for i, p in enumerate(PROMPTS):
            want = rope_model.sample_stream(
                rope_net, p, steps=7, top_k=1,
                rng=np.random.default_rng(i))
            assert got[i] == want, p
        assert eng.health()["kv_traffic"]["decode_path"] == \
            "direct-" + impl["decode_impl"]

    @pytest.mark.parametrize("impl", DIRECT_IMPLS)
    def test_health_reports_kernel_pages_per_step(self, rope_net, impl):
        """The G the live engine's kernel resolved (8 table entries a
        row here, all in one step); 0 off the kernel path."""
        eng = GenerationEngine(
            rope_net, V, slots=2,
            paging=PagedKVConfig(page_size=4, **impl))
        assert pages_per_step((eng.page_pool.total_pages, 2, 4, 8),
                              32 // 4, 4) == 8
        assert eng.health()["kv_traffic"]["kernel_pages_per_step"] == \
            (8 if impl["decode_impl"] == "pallas" else 0)

    @pytest.mark.parametrize("impl", DIRECT_IMPLS)
    def test_sampled_mixed_configs_match_one_shot(self, rope_model,
                                                  rope_net, impl):
        cfgs = [dict(temperature=0.7, top_k=3),
                dict(temperature=1.2, top_p=0.9),
                dict(top_k=1),
                dict(temperature=0.9)]
        eng = GenerationEngine(
            rope_net, V, slots=4,
            paging=PagedKVConfig(page_size=4, **impl))
        hs = [eng.submit([1 + i, 2, 3], steps=6,
                         rng=np.random.default_rng(10 + i), **c)
              for i, c in enumerate(cfgs)]
        got = drain(eng, hs)
        for i, c in enumerate(cfgs):
            want = rope_model.sample_stream(
                rope_net, [1 + i, 2, 3], steps=6,
                rng=np.random.default_rng(10 + i), **c)
            assert got[i] == want, c

    @pytest.mark.parametrize("impl", DIRECT_IMPLS)
    def test_prefix_cache_shared_blocks(self, rope_model, rope_net,
                                        impl):
        """Shared full leading blocks: later requests map cached pages
        read-only, prime only their suffix, and still stream bit-equal
        to one-shot — appends never touch a shared page (block-aligned
        copy-on-extend)."""
        shared = [3, 1, 2, 0] * 2              # two full ps=4 blocks
        prompts = [shared + [5], shared + [7, 8], shared + [9],
                   [6, 6]]
        eng, got = run_trace(
            rope_net, prompts, steps=6, slots=2,
            submit_kw=dict(top_k=1),
            paging=PagedKVConfig(page_size=4, **impl))
        assert eng.prefix_cache.hits > 0
        for i, p in enumerate(prompts):
            want = rope_model.sample_stream(
                rope_net, p, steps=6, top_k=1,
                rng=np.random.default_rng(i))
            assert got[i] == want, p

    @pytest.mark.parametrize("impl", DIRECT_IMPLS)
    def test_speculation_on_direct_path(self, rope_model, rope_net,
                                        impl):
        """In-engine speculation over the direct path: the widened
        [S, V, 1+gamma] verify runs the same paged append/attend at
        width 1+gamma, per-row rewind drops rejected positions, and
        greedy outputs stay bit-equal to plain sample_stream."""
        prompts = [[1, 2, 3, 1, 2], [4, 5, 4, 5], [7, 8, 7]]
        eng, got = run_trace(
            rope_net, prompts, steps=8, slots=3,
            submit_kw=dict(top_k=1),
            paging=PagedKVConfig(page_size=4, **impl),
            speculation=SpeculationConfig(
                draft=prompt_lookup_proposer(2), gamma=2))
        for i, p in enumerate(prompts):
            want = rope_model.sample_stream(
                rope_net, p, steps=8, top_k=1,
                rng=np.random.default_rng(i))
            assert got[i] == want, p

    def test_sampled_identical_across_slot_direct_kernel(self, rope_net):
        """One sampled trace, three arenas: slot, direct-xla,
        direct-kernel — identical token streams (the engine draws on
        the host from distributions that agree to float precision)."""
        kw = dict(steps=6, stagger=True, slots=2,
                  submit_kw=dict(temperature=1.1, top_p=0.9))
        _, slot = run_trace(rope_net, PROMPTS, **kw)
        _, xla = run_trace(
            rope_net, PROMPTS,
            paging=PagedKVConfig(page_size=4, decode_impl="xla"), **kw)
        _, kern = run_trace(
            rope_net, PROMPTS,
            paging=PagedKVConfig(page_size=4, decode_impl="pallas",
                                 kernel_interpret=True), **kw)
        assert xla == slot
        assert kern == slot


# ---------------------------------------------------------------------
# cached tables: rebuilt only on mutation, never per step
# ---------------------------------------------------------------------
class TestTableCache:
    def test_cache_stable_across_steps_invalidated_on_mutation(
            self, rope_net):
        eng = GenerationEngine(rope_net, V, slots=2,
                               paging=PagedKVConfig(page_size=4))
        h = eng.submit([1, 2, 3], steps=6, top_k=1,
                       rng=np.random.default_rng(0))
        eng.step()                       # admit (mutation) + decode
        t_np = eng._tables_cache
        t_layer = eng._tables_layer_cache
        assert t_np is not None and t_layer is not None
        eng.step()                       # pure decode: nothing rebuilt
        assert eng._tables_cache is t_np
        assert eng._tables_layer_cache is t_layer
        eng.step()
        assert eng._tables_cache is t_np
        drain(eng, [h])                  # retirement invalidates
        assert eng._tables_cache is None


# ---------------------------------------------------------------------
# KV-traffic telemetry: what a decode step moves, as a number
# ---------------------------------------------------------------------
class TestKVTraffic:
    def _steady_step_bytes(self, net, paging, slots=2):
        """Admit one request, then measure ONE steady-state decode
        step's bytes (no admission/retirement in the measured step)."""
        eng = GenerationEngine(net, V, slots=slots, paging=paging)
        h = eng.submit([1, 2, 3], steps=8, top_k=1,
                       rng=np.random.default_rng(0))
        eng.step()                           # admission + first decode
        before = eng._kv_bytes_total
        eng.step()                           # pure decode
        per_step = eng._kv_bytes_total - before
        eng.shutdown()
        return per_step, eng

    def test_direct_drops_per_step_bytes(self, rope_net):
        """No full-arena round trip in the steady-state step: per-step
        KV bytes stand at O(active read + one-token write), under the
        2·S·L·tok_bytes a gather → dispatch → scatter would move."""
        xla, ex = self._steady_step_bytes(
            rope_net, PagedKVConfig(page_size=4, decode_impl="xla"))
        kern, ek = self._steady_step_bytes(
            rope_net, PagedKVConfig(page_size=4, decode_impl="pallas",
                                    kernel_interpret=True))
        # tok_bytes: per-position KV bytes summed over leaves
        tok = ex._tok_bytes
        S, L = ex.slots, ex._L
        roundtrip = 2 * S * L * tok
        assert xla == S * L * tok + S * 1 * tok
        # one active row at position 4 (3 prompt + 1 drawn): one live
        # page-rounded read + the all-rows one-token append
        assert kern == 8 * tok + S * 1 * tok
        assert kern < xla < roundtrip

    def test_counter_and_histogram_registered(self, rope_net):
        reg = MetricsRegistry()
        eng = GenerationEngine(
            rope_net, V, slots=2, registry=reg, name="engine:kvt",
            paging=PagedKVConfig(page_size=4))
        h = eng.submit([1, 2, 3], steps=4, top_k=1,
                       rng=np.random.default_rng(0))
        drain(eng, [h])
        snap = reg.snapshot_compact()
        assert snap[SERVING_KV_BYTES_MOVED + "{model=engine:kvt}"] > 0
        # prompt 3 + steps 4 → 1 prefill token + 3 decode dispatches
        lat = snap[SERVING_DISPATCH_LATENCY + "{model=engine:kvt}"]
        assert lat["count"] >= 3
        assert eng.health()["kv_traffic"]["bytes_moved_total"] == \
            snap[SERVING_KV_BYTES_MOVED + "{model=engine:kvt}"]

    def test_slot_arena_observes_latency_only(self, rope_net):
        reg = MetricsRegistry()
        eng = GenerationEngine(rope_net, V, slots=2, registry=reg,
                               name="engine:slot_lat")
        h = eng.submit([1, 2], steps=3, top_k=1,
                       rng=np.random.default_rng(0))
        drain(eng, [h])
        snap = reg.snapshot_compact()
        # prompt 2 + steps 3 → 1 prefill token + 2 decode dispatches
        assert snap[SERVING_DISPATCH_LATENCY +
                    "{model=engine:slot_lat}"]["count"] >= 2
        assert "kv_traffic" not in eng.health()


# ---------------------------------------------------------------------
# supervisor recovery re-enters the direct path
# ---------------------------------------------------------------------
class TestDirectRecovery:
    @pytest.mark.parametrize("impl", DIRECT_IMPLS)
    def test_rebuild_reenters_direct_path_bit_identical(self, rope_net,
                                                        impl):
        shared = [3, 1, 2, 0] * 2
        prompts = [shared + [5], shared + [7, 8], [9, 9]]
        cfg = dict(paging=PagedKVConfig(page_size=4, **impl))
        base = GenerationEngine(rope_net, V, slots=2, **cfg)
        hs = [base.submit(p, steps=5, top_k=1,
                          rng=np.random.default_rng(i))
              for i, p in enumerate(prompts)]
        want = drain(base, hs)
        sup = EngineSupervisor()
        eng = GenerationEngine(
            rope_net, V, slots=2, supervisor=sup,
            decode_chaos=chaos.FaultBurstInjector(n=3, k=1), **cfg)
        hs = [eng.submit(p, steps=5, top_k=1,
                         rng=np.random.default_rng(i))
              for i, p in enumerate(prompts)]
        got = drain(eng, hs)
        assert got == want
        assert eng.is_healthy() and sup.rebuilds == 1
        # the rebuilt engine is still on the direct path, fresh pool
        assert eng.health()["kv_traffic"]["decode_path"] == \
            "direct-" + impl["decode_impl"]
        assert eng.page_pool.used_count() == len(eng.prefix_cache)


# ---------------------------------------------------------------------
# zero retraces after warmup with the kernel path enabled
# ---------------------------------------------------------------------
def _compile_total():
    c = monitoring.global_registry().get(runtime.COMPILE_COUNTER)
    return 0.0 if c is None else c.total()


class TestNoRetraceDirectAfterWarmup:
    @pytest.mark.parametrize("impl", DIRECT_IMPLS)
    def test_direct_path_compiles_nothing_after_warmup(self, impl):
        monitoring.ensure_started()
        model = TextGenerationTransformer(vocab_size=V, embed_dim=16,
                                          n_heads=2, n_layers=1,
                                          max_length=64,
                                          positional="rope")
        net = model.init()
        eng = GenerationEngine(
            net, V, slots=4,
            paging=PagedKVConfig(page_size=8, **impl),
            speculation=SpeculationConfig(
                draft=prompt_lookup_proposer(2), gamma=3))
        eng.warmup(max_prompt_len=16)
        warm = _compile_total()
        SYS = [7, 3, 9, 1, 4, 2, 8, 5]
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
        assert eng.prefix_cache.hits > 0
        assert _compile_total() == warm, (
            "direct paged decode retraced after warmup")


# ---------------------------------------------------------------------
# review-finding regression pins
# ---------------------------------------------------------------------
class TestReviewRegressions:
    def test_retired_row_kv_pos_reset_on_next_dispatch(self, rope_net):
        """A retirement leaves the freed row's DEVICE kv_pos coasting
        (+1 per dispatch); the next direct install must zero it so a
        once-long idle slot doesn't defeat the kernel's dead-block
        skip (and the modeled bytes) forever."""
        eng = GenerationEngine(rope_net, V, slots=2,
                               paging=PagedKVConfig(page_size=4))
        h1 = eng.submit([1, 2, 3, 4, 5, 6], steps=3, top_k=1,
                        rng=np.random.default_rng(0))
        h2 = eng.submit([7, 8], steps=8, top_k=1,
                        rng=np.random.default_rng(1))
        eng.run_until_idle()           # h1 retires first; h2 continues
        assert h1.done and h2.done
        n0 = eng._paged_keys[0][0]
        pos = np.asarray(eng.net.state[n0]["kv_pos"])
        # both rows retired by the drain: every free row's position was
        # reset by the last post-retirement install (not still coasting
        # at prompt+steps+idle-dispatches)
        assert (pos <= max(len(h2._ids), len(h1._ids))).all()
        h3 = eng.submit([9], steps=2, top_k=1,
                        rng=np.random.default_rng(2))
        eng.step()                     # install zeroes free rows
        pos = np.asarray(eng.net.state[n0]["kv_pos"])
        free = [s for s, r in enumerate(eng._slots) if r is None]
        assert all(pos[s] <= 2 for s in free)   # reset, then <= width
        eng.run_until_idle()
        assert h3.result(timeout=0)

    def test_retry_policy_disables_donation(self, rope_net):
        """decode_retry + donated direct dispatches are incompatible (a
        retried attempt would re-run against consumed buffers): the
        engine must resolve donation off when a retry policy rides."""
        from deeplearning4j_tpu.resilience.retry import RetryPolicy
        eng = GenerationEngine(
            rope_net, V, slots=2, paging=PagedKVConfig(page_size=4),
            decode_retry=RetryPolicy(max_attempts=2))
        assert eng._donate is False
        eng2 = GenerationEngine(rope_net, V, slots=2,
                                paging=PagedKVConfig(page_size=4))
        assert eng2._donate is True
        # and the retried-dispatch exactness contract still holds: a
        # chaos fault (fires before any state mutates) retries to
        # bit-identical output
        want = [GenerationEngine(rope_net, V, slots=2,
                                 paging=PagedKVConfig(page_size=4))]
        base = want[0].submit([1, 2, 3], steps=5, top_k=1,
                              rng=np.random.default_rng(0))
        want[0].run_until_idle()
        eng3 = GenerationEngine(
            rope_net, V, slots=2, paging=PagedKVConfig(page_size=4),
            decode_retry=RetryPolicy(max_attempts=3, base_delay=0.0,
                                     jitter=0.0,
                                     retry_on=(chaos.InjectedFault,)),
            decode_chaos=chaos.FaultBurstInjector(n=1, k=1))
        h = eng3.submit([1, 2, 3], steps=5, top_k=1,
                        rng=np.random.default_rng(0))
        eng3.run_until_idle()
        assert h.result(timeout=0) == base.result(timeout=0)

    @pytest.mark.parametrize("first", ["pallas", "xla"])
    def test_two_engines_keep_their_own_read(self, first):
        """The read belongs to the net an engine serves, not to the
        process: a kernel-path engine and an XLA-path engine on two
        nets, constructed in either order, each report their own
        ``decode_path``, and the one that was warm compiles nothing on
        the dispatches it runs after the other was built and served
        (a process-wide switch retraced it onto the other's read)."""
        monitoring.ensure_started()
        impls = {"pallas": dict(decode_impl="pallas",
                                kernel_interpret=True),
                 "xla": dict(decode_impl="xla")}
        second = "xla" if first == "pallas" else "pallas"

        def build(impl):
            net = TextGenerationTransformer(
                vocab_size=V, embed_dim=16, n_heads=2, n_layers=1,
                max_length=32, positional="rope").init()
            eng = GenerationEngine(
                net, V, slots=2,
                paging=PagedKVConfig(page_size=4, **impls[impl]))
            eng.warmup(max_prompt_len=8)
            return eng

        def serve(eng, seed):
            h = eng.submit([1, 2, 3], steps=5, top_k=1,
                           rng=np.random.default_rng(seed))
            return drain(eng, [h])[0]

        a = build(first)
        want = serve(a, 0)
        b = build(second)
        assert serve(b, 0) == want        # both reads, same tokens
        warm = _compile_total()
        assert serve(a, 1) == serve(b, 1)
        assert _compile_total() == warm, (
            "an engine's read changed under it: its net retraced")
        for eng, impl in ((a, first), (b, second)):
            assert eng.health()["kv_traffic"]["decode_path"] == \
                "direct-" + impl
            assert set(eng.net._paged_reads()) == \
                {(impl, impl == "pallas")}


# ---------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------
class TestConfig:
    def test_bad_decode_impl_rejected(self):
        with pytest.raises(ValueError, match="decode_impl"):
            PagedKVConfig(decode_impl="cuda")
