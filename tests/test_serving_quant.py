"""int8 KV page pool (serving/quant.py + PagedKVConfig(kv_dtype="int8")):
quantization primitives and their exactness contracts, the accuracy
ENVELOPE vs bf16 (greedy-divergence margin + attention-output MAE — pinned
bounds, never bit-parity), bitwise pins where int8 must be exact
against ITSELF (prefix hit == miss, supervisor rebuild, fleet
migration, speculation on/off, run-to-run), the capacity-doubling
admission math under a byte budget, the exact per-dispatch byte model
on both decode impls (int8 <= 0.55x bf16), chaos page exhaustion on a
quantized pool, and the zero-retrace guard with int8 + prefix cache +
speculation stacked."""

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import monitoring
from deeplearning4j_tpu.monitoring import runtime
from deeplearning4j_tpu.resilience import chaos
from deeplearning4j_tpu.serving import (
    EngineSupervisor, GenerationEngine, PagedKVConfig, SpeculationConfig)
from deeplearning4j_tpu.serving.paged_kernel import (
    paged_attention, paged_attention_supported, paged_ref_attention)
from deeplearning4j_tpu.serving import quant
from deeplearning4j_tpu.serving.quant import (
    KV_DTYPES, dequantize, kv_page_bytes, pool_leaves, pow2ceil,
    quantize)
from deeplearning4j_tpu.util.decoding import prompt_lookup_proposer
from deeplearning4j_tpu.zoo import TextGenerationTransformer

V = 12
PROMPTS = [[1, 2, 3, 4, 5], [6, 7], [8, 9, 10, 1], [2, 4, 6], [3],
           [5, 5, 9]]

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


def int8_cfg(**kw):
    return PagedKVConfig(page_size=4, kv_dtype="int8", **kw)


# ---------------------------------------------------------------------
# quantization primitives: the exactness the bitwise pins stand on
# ---------------------------------------------------------------------
class TestQuantPrimitives:
    def test_pow2ceil_exact(self):
        x = jnp.asarray([0.0, 1e-30, 0.3, 0.5, 1.0, 1.5, 2.0, 3.0,
                         100.0, 1024.0])
        got = np.asarray(pow2ceil(x))
        for xi, gi in zip(np.asarray(x), got):
            if xi == 0:
                assert gi == 0.0
                continue
            # a true power of two, >= x, and minimal (half is < x)
            m, e = np.frexp(gi)
            assert m == 0.5, (xi, gi)
            assert gi >= xi and gi / 2 < xi, (xi, gi)

    def test_roundtrip_bounded_by_half_sigma(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(64, 128)) * 3, jnp.float32)
        sigma = pow2ceil(jnp.max(jnp.abs(x)) / 127.0)
        back = dequantize(quantize(x, sigma), sigma)
        err = np.abs(np.asarray(back) - np.asarray(x))
        assert (err <= float(sigma) / 2 + 1e-7).all()

    def test_zero_sigma_quantizes_to_zero(self):
        x = jnp.zeros((4, 8), jnp.float32)
        q = quantize(x, pow2ceil(jnp.max(jnp.abs(x)) / 127.0))
        assert (np.asarray(q) == 0).all()
        # and a nonzero input under sigma=0 (an all-zero page base)
        # must not divide by zero
        q2 = quantize(jnp.ones((4, 8)), jnp.zeros(()))
        assert (np.asarray(q2) == 0).all()

    def test_dequant_exact_in_bf16(self):
        """|q| <= 127 times a power of two is exactly representable in
        bf16 (7 mantissa bits) — the reason a bf16-native net's reads
        are bit-stable across dispatches."""
        q = jnp.arange(-127, 128, dtype=jnp.int8)
        for sig in (0.25, 1.0, 8.0):
            f32 = dequantize(q, sig, jnp.float32)
            b16 = dequantize(q, sig, jnp.bfloat16)
            np.testing.assert_array_equal(
                np.asarray(f32), np.asarray(b16.astype(jnp.float32)))

    def test_kv_page_bytes_and_pool_leaves(self):
        # one layer, Hkv=2, D=8, ps=4: int8 page = 2*(2*4*8*1 + 2*4)
        assert kv_page_bytes([(2, 8)], 4, "int8", "float32") == \
            2 * (2 * 4 * 8 + 2 * 4)
        assert kv_page_bytes([(2, 8)], 4, "bf16", "float32") == \
            2 * (2 * 4 * 8 * 4)
        assert kv_page_bytes([(2, 8)], 4, "bf16", "bfloat16") == \
            2 * (2 * 4 * 8 * 2)
        pools, scales = pool_leaves(5, 4, [(2, 8), (2, 8)])
        assert len(pools) == len(scales) == 4      # k and v per layer
        assert all(p.shape == (5, 2, 4, 8) and p.dtype == jnp.int8
                   for p in pools)
        assert all(s.shape == (5, 2) and s.dtype == jnp.float32
                   for s in scales)

    def test_kv_dtypes_vocabulary(self):
        assert KV_DTYPES == ("bf16", "int8")


# ---------------------------------------------------------------------
# the two readers over an int8 pool: envelope vs exact, kernel vs ref
# ---------------------------------------------------------------------
def _quantized_case(seed=0, S=3, hkv=2, reps=2, qw=1, d=8, ps=4, nb=5):
    rng = np.random.default_rng(seed)
    P = S * nb + 1
    rw = reps * qw
    q = jnp.asarray(rng.normal(size=(S, hkv, rw, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, hkv, ps, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, hkv, ps, d)), jnp.float32)
    table = jnp.asarray(
        rng.permutation(np.arange(1, P))[:S * nb].reshape(S, nb),
        jnp.int32)
    lengths = jnp.asarray(rng.integers(qw, nb * ps + 1, S), jnp.int32)
    # per-(page, head) quantization, the pool scheme
    ks = pow2ceil(jnp.max(jnp.abs(kp), axis=(2, 3)) / 127.0)
    vs = pow2ceil(jnp.max(jnp.abs(vp), axis=(2, 3)) / 127.0)
    kq = quantize(kp, ks[:, :, None, None])
    vq = quantize(vp, vs[:, :, None, None])
    return q, kp, vp, kq, vq, ks, vs, table, lengths


#: the error pinned for the int8 read, on the attention output of unit
#: normal queries, keys and values: on average, and at worst
READ_MAE, READ_MAX_ERR = 0.02, 0.1


class TestQuantReaders:
    def test_ref_attention_mae_envelope(self):
        """The accuracy contract is an ENVELOPE: int8 pools through the
        dense-gather reference stay within a pinned MAE of the exact
        pools — and are NOT bit-identical (the quantization is real)."""
        (q, kp, vp, kq, vq, ks, vs, table,
         lengths) = _quantized_case()
        exact = paged_ref_attention(q, kp, vp, table, lengths,
                                    query_width=1)
        kd = dequantize(kq, ks[:, :, None, None])
        vd = dequantize(vq, vs[:, :, None, None])
        quant = paged_ref_attention(q, kd, vd, table, lengths,
                                    query_width=1)
        diff = np.abs(np.asarray(exact) - np.asarray(quant))
        assert diff.mean() <= READ_MAE
        assert diff.max() <= READ_MAX_ERR
        assert diff.max() > 0          # a real quantizer, not a no-op

    @pytest.mark.parametrize("qw", [1, 3])
    def test_kernel_matches_dequantized_reference(self, qw):
        """The int8 kernel IS dequant(int8) attention: per-page scales
        commute with both dots, so its output equals the reference run
        on the dequantized pools (float tolerance, both widths)."""
        (q, _, _, kq, vq, ks, vs, table,
         lengths) = _quantized_case(qw=qw)
        out = paged_attention(q, kq, vq, table, lengths,
                              query_width=qw, interpret=True,
                              k_scales=ks, v_scales=vs)
        kd = dequantize(kq, ks[:, :, None, None])
        vd = dequantize(vq, vs[:, :, None, None])
        ref = paged_ref_attention(q, kd, vd, table, lengths,
                                  query_width=qw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_scales_travel_together_and_need_int8(self):
        (q, kp, _, kq, vq, ks, vs, table,
         lengths) = _quantized_case()
        with pytest.raises(ValueError, match="together"):
            paged_attention(q, kq, vq, table, lengths, query_width=1,
                            interpret=True, k_scales=ks)
        with pytest.raises(ValueError, match="int8"):
            paged_attention(q, kp, kp, table, lengths, query_width=1,
                            interpret=True, k_scales=ks, v_scales=vs)

    def test_supported_gate_tightens_for_int8(self):
        """One shape rule for every pool dtype since the grouped walk
        (the kernel copies whole pages itself); what tightens for int8
        is the POOL SIZE: its scale sidecars ride SMEM."""
        assert paged_attention_supported((0, 0, 32, 128), 1,
                                         kv_dtype="int8")
        assert paged_attention_supported((0, 0, 8, 128), 1,
                                         kv_dtype="int8")
        assert not paged_attention_supported((0, 0, 12, 128), 1,
                                             kv_dtype="int8")
        assert not paged_attention_supported((0, 0, 32, 64), 1,
                                             kv_dtype="int8")
        assert paged_attention_supported((768, 2, 32, 128), 1,
                                         kv_dtype="int8")
        assert not paged_attention_supported((1025, 2, 32, 128), 1,
                                             kv_dtype="int8")
        # a native-dtype pool of that size has no sidecars to fit
        assert paged_attention_supported((1025, 2, 32, 128), 1)
        assert not paged_attention_supported((0, 0, 8, 64), 1)


# ---------------------------------------------------------------------
# engine accuracy envelope + determinism pins
# ---------------------------------------------------------------------
class TestInt8Engine:
    def _greedy(self, net, kv_dtype, steps=10, **impl):
        _, got = run_trace(
            net, PROMPTS, steps=steps, slots=3, stagger=False,
            submit_kw=dict(top_k=1),
            paging=PagedKVConfig(page_size=4, kv_dtype=kv_dtype,
                                 **impl))
        return got

    def _divergence_margins(self, net):
        """Where a prompt's greedy int8 stream parts from its bf16
        stream, how far below its best the UNQUANTIZED distribution at
        that step holds the token int8 chose: one probability gap per
        prompt that parts (the later steps follow another context and
        say nothing)."""
        g16 = self._greedy(net, "bf16")
        g8 = self._greedy(net, "int8")
        margins = []
        for a, b, p in zip(g16, g8, PROMPTS):
            step = next((i for i in range(len(p), len(a))
                         if a[i] != b[i]), None)
            if step is None:
                continue
            x = np.zeros((1, V, step), np.float32)
            x[0, a[:step], np.arange(step)] = 1.0
            net.rnn_clear_previous_state()
            probs = np.asarray(net.rnn_time_step(x))[0, :, -1]
            net.rnn_clear_previous_state()
            assert a[step] == int(np.argmax(probs))
            margins.append(float(probs[a[step]] - probs[b[step]]))
        return margins

    @staticmethod
    def _within_read_error(margins):
        return (max(margins, default=0.0) <= READ_MAX_ERR
                and float(np.mean(margins or [0.0])) <= READ_MAE)

    def test_greedy_divergence_envelope(self, rope_net):
        """The pinned accuracy envelope, stated on the margin: this
        random 16-wide net's logits are nearly flat, so WHERE a greedy
        int8 stream parts from the bf16 stream is chance (two of six
        part at the first token). What is pinned is what int8 may pick
        there: a token the unquantized distribution holds within the
        int8 read's own pinned error of its best. NOT a bit-parity
        claim — the pins are the envelope."""
        margins = self._divergence_margins(rope_net)
        assert margins, "int8 never parted: a no-op quantizer?"
        assert self._within_read_error(margins), margins

    def test_greedy_divergence_envelope_catches_a_wrong_scale(
            self, rope_model, monkeypatch):
        """The control that makes the envelope a test: the same run
        with every scale the appends write stored DOUBLED (pages read
        back at twice their value) parts for tokens far below the
        unquantized best."""
        write = quant.quantize_chunk

        def doubled(xt, scales, *args, **kw):
            xq, new = write(xt, scales, *args, **kw)
            return xq, jnp.where(new != scales, 2.0 * new, new)

        monkeypatch.setattr(quant, "quantize_chunk", doubled)
        # a net of its own: its programs trace the patched write
        margins = self._divergence_margins(rope_model.init())
        assert not self._within_read_error(margins), margins

    @pytest.mark.parametrize("impl", DIRECT_IMPLS)
    def test_deterministic_run_to_run(self, rope_net, impl):
        """Same engine config, same rngs, twice: identical sampled
        streams — quantized pool bytes are a pure function of the
        committed token stream."""
        kw = dict(steps=7, slots=3,
                  submit_kw=dict(temperature=0.9, top_p=0.9),
                  paging=PagedKVConfig(page_size=4, kv_dtype="int8",
                                       **impl))
        _, a = run_trace(rope_net, PROMPTS, **kw)
        _, b = run_trace(rope_net, PROMPTS, **kw)
        assert a == b

    def test_xla_and_kernel_agree_token_level(self, rope_net):
        """Both int8 readers dequantize the same pool bytes: greedy
        streams agree across the folded-gather and kernel impls (the
        same cross-impl pin the bf16 suite holds sampled)."""
        xla = self._greedy(rope_net, "int8", decode_impl="xla")
        kern = self._greedy(rope_net, "int8", decode_impl="pallas",
                            kernel_interpret=True)
        assert xla == kern

    @pytest.mark.parametrize("impl", DIRECT_IMPLS)
    def test_speculation_bit_identical_to_plain(self, rope_net, impl):
        """Speculative rewind re-prices pages deterministically: int8 +
        speculation streams equal plain int8 streams bit for bit (the
        wide verify writes the same base tokens at the same values, so
        the same scales)."""
        prompts = [[1, 2, 3, 1, 2], [4, 5, 4, 5], [7, 8, 7]]
        kw = dict(steps=8, slots=3, submit_kw=dict(top_k=1),
                  paging=PagedKVConfig(page_size=4, kv_dtype="int8",
                                       **impl))
        _, plain = run_trace(rope_net, prompts, **kw)
        _, spec = run_trace(
            rope_net, prompts,
            speculation=SpeculationConfig(
                draft=prompt_lookup_proposer(2), gamma=2), **kw)
        assert spec == plain

    def test_recurrent_net_refused(self):
        """A hybrid net (attention KV + LSTM h/c) passes the paging
        gate but must refuse int8: recurrent state cannot re-prime
        through the paged path."""
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.conf.layers import (
            Convolution1DLayer, GravesLSTM, RnnOutputLayer,
            SelfAttentionLayer)
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        conf = (NeuralNetConfiguration.Builder().seed(0)
                .weight_init("xavier")
                .graph_builder().add_inputs("in")
                .set_input_types(InputType.recurrent(V, 32))
                .add_layer("embed", Convolution1DLayer(
                    n_out=16, kernel=1, convolution_mode="same",
                    activation="identity"), "in")
                .add_layer("attn", SelfAttentionLayer(
                    n_out=16, n_heads=2, causal=True, cache_length=32,
                    rope=True, activation="identity"), "embed")
                .add_layer("rnn", GravesLSTM(n_out=16), "attn")
                .add_layer("out", RnnOutputLayer(
                    n_out=V, loss="mcxent", activation="softmax"),
                    "rnn")
                .set_outputs("out").build())
        net = ComputationGraph(conf).init()
        with pytest.raises(ValueError, match="recurrent"):
            GenerationEngine(net, V, slots=2,
                             paging=int8_cfg(prefix_cache=False))
        # the same net serves fine unquantized
        eng = GenerationEngine(
            net, V, slots=2,
            paging=PagedKVConfig(page_size=4, prefix_cache=False))
        h = eng.submit([1, 2, 3], steps=3, top_k=1,
                       rng=np.random.default_rng(0))
        assert drain(eng, [h])[0]

    @pytest.mark.parametrize("kv_dtype", ["fp8", "auto"])
    def test_bad_kv_dtype_rejected(self, kv_dtype):
        with pytest.raises(ValueError, match="kv_dtype"):
            PagedKVConfig(kv_dtype=kv_dtype)


# ---------------------------------------------------------------------
# bitwise pins: prefix hit == miss, rebuild, migration
# ---------------------------------------------------------------------
class TestInt8PrefixAndRecovery:
    SHARED = [3, 1, 2, 0] * 2                  # two full ps=4 blocks
    PROMPTS3 = [SHARED + [5], SHARED + [7, 8], [9, 9]]

    def _run(self, net, **kw):
        eng = GenerationEngine(net, V, slots=2, **kw)
        hs = [eng.submit(p, steps=5, top_k=1,
                         rng=np.random.default_rng(i))
              for i, p in enumerate(self.PROMPTS3)]
        return eng, drain(eng, hs)

    @pytest.mark.parametrize("impl", DIRECT_IMPLS)
    def test_prefix_hit_equals_miss_bitwise(self, rope_net, impl):
        """A prefix-cache hit re-reads the EXACT int8 bytes + scales
        the inserting prime wrote, and the suffix prime starts past
        them — hit streams equal fresh-prefill streams bit for bit
        (power-of-two scales make the dequantized read a pure function
        of the committed tokens)."""
        _, miss = self._run(rope_net,
                            paging=int8_cfg(prefix_cache=False, **impl))
        eng, hit = self._run(rope_net, paging=int8_cfg(**impl))
        assert eng.prefix_cache.hits > 0
        assert hit == miss

    @pytest.mark.parametrize("impl", DIRECT_IMPLS)
    def test_rebuild_bit_identical(self, rope_net, impl):
        """Supervisor quarantine on an int8 arena: fresh zeroed pools +
        scales, every survivor re-primes THROUGH the quantized paged
        path — streams continue bit-identical to an unperturbed int8
        run."""
        _, want = self._run(rope_net, paging=int8_cfg(**impl))
        sup = EngineSupervisor()
        eng, got = self._run(
            rope_net, paging=int8_cfg(**impl), supervisor=sup,
            decode_chaos=chaos.FaultBurstInjector(n=3, k=1))
        assert got == want
        assert sup.rebuilds == 1 and eng.is_healthy()
        assert eng.health()["kv_traffic"]["kv_dtype"] == "int8"

    def test_migration_continues_bit_identical(self, rope_net):
        """The ledger hop (fleet migration): actives exported from one
        int8 engine re-prime on another and continue bit-identical —
        the pool bytes are reproducible from the ledger alone."""
        _, want = self._run(rope_net, paging=int8_cfg())
        src = GenerationEngine(rope_net, V, slots=2, paging=int8_cfg())
        hs = [src.submit(p, steps=5, top_k=1,
                         rng=np.random.default_rng(i))
              for i, p in enumerate(self.PROMPTS3)]
        for _ in range(3):
            src.step()
        entries = src.export_ledger(include_queued=True)
        dst = GenerationEngine(rope_net, V, slots=2, paging=int8_cfg())
        took = dst.admit_from_ledger(entries, where="test migration")
        assert took == len(entries)
        dst.run_until_idle()
        assert [h.result(timeout=0) for h in hs] == want


# ---------------------------------------------------------------------
# capacity: the same byte budget admits ~2x the pages
# ---------------------------------------------------------------------
class TestInt8Capacity:
    def test_total_bytes_doubles_pages(self, rope_net):
        """Exact admission math under a byte budget: pages = budget //
        page_bytes with the scale sidecar priced in — and the int8
        pool admits at least 2x the bf16 pages (4x against an f32-
        native net: this model's 'bf16' pool stores f32 leaves)."""
        budget = 200_000
        engines = {}
        for dt in ("int8", "bf16"):
            engines[dt] = GenerationEngine(
                rope_net, V, slots=2,
                paging=PagedKVConfig(page_size=4, kv_dtype=dt,
                                     total_bytes=budget))
        dims = [(h, d) for _, h, d in engines["int8"]._quant_dims]
        for dt, eng in engines.items():
            per_page = kv_page_bytes(dims, 4, dt, "float32")
            assert eng.page_pool.usable == budget // per_page, dt
        assert engines["int8"].page_pool.usable >= \
            2 * engines["bf16"].page_pool.usable

    def test_capacity_knobs_exclusive(self):
        with pytest.raises(ValueError, match="at most one"):
            PagedKVConfig(total_bytes=1000, total_pages=4)
        with pytest.raises(ValueError, match="total_bytes"):
            PagedKVConfig(total_bytes=0)

    def test_budget_smaller_than_one_page_refused(self, rope_net):
        with pytest.raises(ValueError, match="no page"):
            GenerationEngine(rope_net, V, slots=2,
                             paging=PagedKVConfig(page_size=4,
                                                  total_bytes=10))

    def test_int8_capacity_serves_more_tokens(self, rope_net):
        """The point of the halving: a budget that head-blocks bf16
        admits the same work under int8."""
        dims = [(2, 8)] * 2
        budget = 12 * kv_page_bytes(dims, 4, "bf16", "float32")
        long_prompt = list(np.random.default_rng(0).integers(1, V, 20))
        eng8 = GenerationEngine(
            rope_net, V, slots=2,
            paging=PagedKVConfig(page_size=4, kv_dtype="int8",
                                 total_bytes=budget,
                                 prefix_cache=False))
        # 12 bf16 pages buy ~3.5x pages under int8 -> two long streams
        hs = [eng8.submit(long_prompt + [i], steps=6, top_k=1,
                          rng=np.random.default_rng(i))
              for i in range(2)]
        got = drain(eng8, hs)
        assert all(len(g) == 27 for g in got)


# ---------------------------------------------------------------------
# the byte model: int8 halves the bytes the dispatch moves
# ---------------------------------------------------------------------
class TestInt8Traffic:
    def _steady_step_bytes(self, net, paging, slots=2):
        eng = GenerationEngine(net, V, slots=slots, paging=paging)
        h = eng.submit([1, 2, 3], steps=8, top_k=1,
                       rng=np.random.default_rng(0))
        eng.step()                           # admission + first decode
        before = eng._kv_bytes_total
        eng.step()                           # pure decode
        per_step = eng._kv_bytes_total - before
        eng.shutdown()
        return per_step, eng

    def test_byte_model_exact_and_halved_both_impls(self, rope_net):
        """The mechanism pin (not wall-clock): exact per-dispatch byte
        formulas under int8 — pool terms at 1 byte/element plus the
        scale-sidecar reads — and int8 <= 0.55x bf16 on BOTH impls."""
        legs = {}
        for dt in ("bf16", "int8"):
            for impl in ("xla", "pallas"):
                kw = (dict(decode_impl="pallas", kernel_interpret=True)
                      if impl == "pallas" else dict(decode_impl="xla"))
                legs[dt, impl] = self._steady_step_bytes(
                    rope_net, PagedKVConfig(page_size=4, kv_dtype=dt,
                                            **kw))
        for impl in ("xla", "pallas"):
            per8, e8 = legs["int8", impl]
            per16, e16 = legs["bf16", impl]
            tok8, tok16 = e8._tok_bytes, e16._tok_bytes
            assert tok8 * 4 == tok16         # f32-native net: 4 -> 1 B
            S, L, ps, nm = e8.slots, e8._L, e8._ps, e8._n_max
            row = e8._scale_row_bytes
            assert row == 2 * 2 * 2 * 4      # 2 layers x k,v x Hkv x f32
            assert e16._scale_row_bytes == 0
            if impl == "xla":
                assert per16 == S * L * tok16 + S * tok16
                assert per8 == S * L * tok8 + S * tok8 + S * nm * row
            else:
                # one active row at position 4: one page-rounded live
                # read (8 positions = 2 pages) + the all-rows append
                assert per16 == 8 * tok16 + S * tok16
                assert per8 == 8 * tok8 + S * tok8 + 2 * row
            assert per8 <= 0.55 * per16, impl

    def test_health_reports_kv_dtype(self, rope_net):
        eng8 = GenerationEngine(rope_net, V, slots=2, paging=int8_cfg())
        assert eng8.health()["kv_traffic"]["kv_dtype"] == "int8"
        eng16 = GenerationEngine(rope_net, V, slots=2,
                                 paging=PagedKVConfig(page_size=4))
        assert eng16.health()["kv_traffic"]["kv_dtype"] == "bf16"


# ---------------------------------------------------------------------
# chaos: page exhaustion on a quantized pool
# ---------------------------------------------------------------------
class TestInt8Chaos:
    def test_page_exhaustion_actives_bit_identical(self, rope_net):
        """Seizing an int8 pool's free pages (scale sidecar rows travel
        implicitly with the page ids — host accounting only) starves
        new admissions while actives complete bit-identical to an
        unperturbed int8 run, and release un-blocks the stragglers."""
        kw = dict(steps=6, slots=3, stagger=False,
                  submit_kw=dict(top_k=1))
        _, want = run_trace(rope_net, PROMPTS[:2],
                            paging=int8_cfg(total_pages=6,
                                            prefix_cache=False), **kw)
        _, want_late = run_trace(rope_net, [[4, 5, 6]], steps=21,
                                 slots=3, stagger=False,
                                 submit_kw=dict(top_k=1),
                                 paging=int8_cfg(total_pages=6,
                                                 prefix_cache=False))
        eng = GenerationEngine(
            rope_net, V, slots=3,
            paging=int8_cfg(total_pages=6, prefix_cache=False))
        inj = chaos.PageExhaustionInjector(eng.page_pool, n=1,
                                           free_target=0)
        eng._decode_chaos = inj
        hs = [eng.submit(p, steps=6, top_k=1,
                         rng=np.random.default_rng(i))
              for i, p in enumerate(PROMPTS[:2])]
        eng.step()
        eng.step()                        # injector fires: free -> 0
        assert eng.page_pool.free_count() == 0
        late = eng.submit([4, 5, 6], steps=21, top_k=1,
                          rng=np.random.default_rng(0))
        eng.step()
        assert eng.queue_depth() == 1     # head-blocked, not admitted
        got = drain(eng, hs)
        assert got == want
        assert not late.done
        inj.release()
        eng.run_until_idle()
        assert late.result(timeout=0) == want_late[0]


# ---------------------------------------------------------------------
# zero retraces after warmup with int8 + prefix + speculation
# ---------------------------------------------------------------------
def _compile_total():
    c = monitoring.global_registry().get(runtime.COMPILE_COUNTER)
    return 0.0 if c is None else c.total()


class TestInt8NoRetrace:
    def test_compiles_nothing_after_warmup(self):
        monitoring.ensure_started()
        model = TextGenerationTransformer(vocab_size=V, embed_dim=16,
                                          n_heads=2, n_layers=1,
                                          max_length=64,
                                          positional="rope")
        net = model.init()
        eng = GenerationEngine(
            net, V, slots=4,
            paging=PagedKVConfig(page_size=8, kv_dtype="int8"),
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
            "int8 paged decode retraced after warmup")
