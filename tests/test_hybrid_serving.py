"""A decoder with two kinds of cache behind ``GenerationEngine``: pages for
its full-attention layers, a float32 state a slot for its linear-attention
layers (zoo ``HybridLinearTransformer``). Per-request outputs are one-shot
``sample_stream``'s on every arena; a reused slot and a free row leak
nothing; what does not work yet is refused by name; the supervisor's
rebuild and the ledger's re-admission carry the state by recomputing it;
the layers' declared counters are what ``health()`` shows."""

import numpy as np
import pytest

from deeplearning4j_tpu import monitoring
from deeplearning4j_tpu.monitoring import runtime
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.resilience import chaos
from deeplearning4j_tpu.serving import (
    EngineSupervisor, GenerationEngine, PagedKVConfig, SpeculationConfig)
from deeplearning4j_tpu.serving.engine import PHASES
from deeplearning4j_tpu.util.decoding import (prompt_lookup_proposer,
                                              sample_stream)
from deeplearning4j_tpu.zoo import (HybridLinearTransformer,
                                    TextGenerationLSTM)

V = 50
CONFIG = dict(
    vocab_size=V, hidden_size=32, intermediate_size=48,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
    layer_types=["linear_attention"] * 3 + ["full_attention"],
    attention_bias=False, rms_norm_eps=1e-6, linear_num_key_heads=4,
    linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rope_parameters={"rope_theta": None})
#: a slot's row: three layers' float32 state and float32 tail
ROW_BYTES = 3 * (4 * 8 * 16 * 4 + 3 * 4 * (2 * 8 + 16) * 4)
STEPS = 10


@pytest.fixture(scope="module")
def net():
    net = ComputationGraph(HybridLinearTransformer(CONFIG, 128).conf())
    net.init()
    return net


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [[int(t) for t in rng.integers(0, V, n)]
            for n in (5, 17, 33, 70, 9, 40)]


@pytest.fixture(scope="module")
def one_shot(net, prompts):
    return [list(sample_stream(net, p, STEPS, V, top_k=1,
                               rng=np.random.default_rng(0)))
            for p in prompts]


def _paging(**kw):
    kw = {"page_size": 8, "total_pages": 64, "prefix_cache": False,
          "decode_impl": "xla", **kw}
    return PagedKVConfig(**kw)


def _serve(net, prompts, slots=3, **kw):
    eng = GenerationEngine(net, V, slots=slots, **kw)
    hs = [eng.submit(p, STEPS, top_k=1, rng=np.random.default_rng(0))
          for p in prompts]
    eng.run_until_idle()
    return eng, [list(h.result(timeout=0)) for h in hs]


@pytest.mark.parametrize("arena", ["slots", "paged-xla", "paged-kernel"])
def test_every_request_is_its_one_shot_stream(net, prompts, one_shot,
                                              arena):
    """Six requests over three slots: every slot is reused after a
    retirement, and no request sees its last tenant's state."""
    paging = {"slots": None, "paged-xla": _paging(),
              "paged-kernel": _paging(decode_impl="pallas",
                                      kernel_interpret=True)}[arena]
    eng, got = _serve(net, prompts, paging=paging)
    assert got == one_shot
    h = eng.health()
    if paging is not None:
        assert h["kv_traffic"]["decode_path"] == (
            "direct-xla" if arena == "paged-xla" else "direct-pallas")
        assert h["kv_pages"]["used"] == 0        # retirement freed them
    # what the layers declared, counted by hand: buckets 8, 32, 64, 128,
    # 16, 64 scan 64, 64, 64, 128, 64, 64 positions in three layers
    fed = sum(len(p) for p in prompts)
    assert h["linear_attn"] == {
        "layers": 3, "state_bytes_per_slot": ROW_BYTES,
        "seated_state_bytes": 6 * ROW_BYTES,
        "scanned_positions": 3 * 448, "fed_positions": 3 * fed,
        "state_updates": 3 * 3 * h["decode_dispatch"]["count"]}
    assert h["prefill"]["fed_tokens"] == fed
    eng.shutdown()


def test_a_free_rows_updates_reach_no_seated_row(net, prompts, one_shot):
    """One request at a time in an arena of four: three rows are free and
    keep stepping on token 0 all the while; then a long-lived neighbour
    beside short ones."""
    for i in (3, 1):
        eng, got = _serve(net, [prompts[i]], slots=4, paging=_paging())
        assert got == [one_shot[i]]
        eng.shutdown()
    eng = GenerationEngine(net, V, slots=2, paging=_paging())
    long = eng.submit(prompts[3], 30, top_k=1, rng=np.random.default_rng(0))
    short = [eng.submit(p, 3, top_k=1, rng=np.random.default_rng(0))
             for p in (prompts[0], prompts[4], prompts[1])]
    eng.run_until_idle()
    want = list(sample_stream(net, prompts[3], 30, V, top_k=1,
                              rng=np.random.default_rng(0)))
    assert list(long.result(timeout=0)) == want
    assert [list(h.result(timeout=0)) for h in short] == \
        [one_shot[i][:len(prompts[i]) + 3] for i in (0, 4, 1)]
    eng.shutdown()


def test_what_does_not_work_yet_is_refused_by_name(net):
    with pytest.raises(ValueError, match="recurrent state .*linear-"
                                         "attention state.*prefix_cache"):
        GenerationEngine(net, V, paging=PagedKVConfig(page_size=8))
    with pytest.raises(ValueError, match="recurrent state .*linear-"
                                         "attention state"):
        GenerationEngine(net, V, paging=_paging(kv_dtype="int8"))
    with pytest.raises(ValueError, match="recurrent state .*linear-"
                                         "attention state"):
        GenerationEngine(net, V, paging=_paging(),
                         speculation=SpeculationConfig(
                             prompt_lookup_proposer(), gamma=2))
    # the same texts hold for the state LSTMs carry
    lstm = TextGenerationLSTM(vocab_size=12, hidden=8, n_layers=1,
                              max_length=16).init()
    with pytest.raises(ValueError, match="recurrent state"):
        GenerationEngine(lstm, 12, speculation=SpeculationConfig(
            prompt_lookup_proposer(), gamma=2))


def test_the_supervisors_rebuild_carries_the_state_by_recomputing_it(
        net, prompts, one_shot):
    """A fault mid-decode: the arena is dropped and every survivor
    re-primed from prompt + committed tokens, so its row holds the state
    it had (the chunked scan where the lost row took single steps: the
    same function, to rounding), and the streams go on as they would."""
    sup = EngineSupervisor()
    eng, got = _serve(net, prompts[:4], paging=_paging(), supervisor=sup,
                      decode_chaos=chaos.FaultBurstInjector(n=4, k=1))
    assert sup.health()["rebuilds"] == 1
    assert got == one_shot[:4]
    # the re-primes seated rows again
    assert eng.health()["linear_attn"]["seated_state_bytes"] \
        > 4 * ROW_BYTES
    eng.shutdown()


def test_a_ledger_moves_a_stream_to_another_engine(net, prompts, one_shot):
    """Migration: the exported entries hold no device state; the target
    recomputes it from the ids, as a rebuild does."""
    src = GenerationEngine(net, V, slots=2, paging=_paging())
    hs = [src.submit(p, STEPS, top_k=1, rng=np.random.default_rng(0))
          for p in prompts[:2]]
    for _ in range(4):
        src.step()
    entries = src.detach_ledger()
    assert len(entries) == 2 and all(e.request.streamed for e in entries)
    src.shutdown()
    dst = GenerationEngine(net, V, slots=2, paging=_paging())
    dst.admit_from_ledger(entries)
    dst.run_until_idle()
    assert [list(h.result(timeout=0)) for h in hs] == one_shot[:2]
    dst.shutdown()


def test_warm_up_compiles_what_serving_runs(net, prompts):
    eng = GenerationEngine(net, V, slots=3, paging=_paging())
    eng.warmup(max_prompt_len=64)
    monitoring.ensure_started()
    compiles = monitoring.global_registry().get(runtime.COMPILE_COUNTER)
    before = compiles.total()
    hs = [eng.submit(p, 4, top_k=1, rng=np.random.default_rng(0))
          for p in prompts if len(p) <= 64]
    eng.run_until_idle()
    assert all(h.result(timeout=0) is not None for h in hs)
    assert compiles.total() == before
    eng.shutdown()


def test_no_eleventh_phase():
    from benchmark.metrics import _spans
    assert _spans.PROGRAM_SPANS == PHASES
