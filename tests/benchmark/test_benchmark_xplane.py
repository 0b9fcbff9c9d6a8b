"""The reduction from a profiler trace to busy time, idle gaps and kernel
times: on hand-made events, and on a small trace recorded on a v5e."""

import os

import pytest

from benchmark.xplane import (Trace, clean, find_xplane, merged,
                              union_length)

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "small_v5e.xplane.pb")


@pytest.mark.parametrize("intervals,want", [
    ([], 0.0),
    ([(0, 1)], 1.0),
    ([(0, 1), (2, 3)], 2.0),
    ([(0, 2), (1, 3)], 3.0),                   # overlap counts once
    ([(0, 5), (1, 2), (3, 4)], 5.0),           # nested
    ([(2, 3), (0, 1), (0.5, 2.5)], 3.0),       # unsorted
])
def test_union_length(intervals, want):
    assert union_length(intervals) == pytest.approx(want)
    assert sum(b - a for a, b in merged(intervals)) == pytest.approx(want)


def _trace():
    """Two decode dispatches and one prefill on device 0; the kernel runs
    twice in each decode. Device 1 is busier."""
    ops0 = [("fusion.1", 0.00, 0.02), ("fwd.3_bf16_32_2_12_128_custom-call", 0.02, 0.03),
            ("fwd.4_bf16_32_2_12_128_custom-call", 0.03, 0.04),
            ("fusion.9", 0.10, 0.16),                       # prefill
            ("fusion.1", 0.20, 0.22), ("fwd.3_bf16_32_2_12_128_custom-call", 0.22, 0.23),
            ("fwd.4_bf16_32_2_12_128_custom-call", 0.23, 0.24), ("all-reduce.7", 0.24, 0.25)]
    mods0 = [("jit_fwd(11)", 0.00, 0.04), ("jit_fwd(22)", 0.10, 0.16),
             ("jit_fwd(11)", 0.20, 0.25)]
    ops1 = [("fusion.1", 0.00, 0.20)]
    host = [("np.asarray(jax.Array)", 0.04, 0.095),
            ("Transpose::ExecuteChunk", 0.165, 0.19),
            ("outer", 0.16, 0.20)]
    return Trace({0: ops0, 1: ops1}, {0: mods0, 1: []}, host)


def test_busy_idle_and_window():
    tr = _trace()
    assert tr.devices() == [0, 1]
    assert tr.window_s() == pytest.approx(0.25)
    assert tr.busy_s(0) == pytest.approx(0.15)
    assert tr.busy_s(1) == pytest.approx(0.20)
    assert tr.busy_s() == pytest.approx(0.175)       # mean over devices
    assert tr.idle_share() == pytest.approx(1 - 0.175 / 0.25)


def test_kernel_times_and_program_runs():
    tr = _trace()
    from benchmark.metrics._common import PAGED_KERNEL
    assert tr.seconds_matching(PAGED_KERNEL) == pytest.approx(0.04)
    assert tr.count_matching(r"^fwd") == 4
    assert tr.seconds_matching(r"all-reduce") == pytest.approx(0.01)
    assert tr.seconds_matching(r"all-reduce", device=1) == 0.0
    runs = tr.module_runs(r"^jit_fwd\(11\)")
    assert runs == [(0.00, 0.04), (0.20, 0.25)]
    assert tr.busy_within(runs) == pytest.approx(0.09)
    assert tr.busy_within([(0.10, 0.16)]) == pytest.approx(0.06)
    assert tr.busy_within([(0.05, 0.12)]) == pytest.approx(0.02)
    assert tr.op_seconds()["fusion.1"] == pytest.approx(0.04)


def _programs(*names):
    mods = [(n, 0.1 * i, 0.1 * i + 0.05) for i, n in enumerate(names)]
    return Trace({0: [("fusion.1", 0.0, 0.01)]}, {0: mods}, [])


def test_stream_programs_split_decode_from_prefill():
    from benchmark.metrics._common import stream_programs
    decode, prefill = stream_programs(_programs(
        "jit_fwd(11)", "jit_fwd(22)", "jit_fwd(11)", "jit_step(5)",
        "jit_fwd(11)"))
    assert [a for a, _ in decode] == pytest.approx([0.0, 0.2, 0.4])
    assert [a for a, _ in prefill] == pytest.approx([0.1])
    assert stream_programs(_programs("jit_step(5)")) == ([], [])
    # one program alone is the decode dispatch
    assert stream_programs(_programs("jit_fwd(1)"))[1] == []


@pytest.mark.parametrize("names", [
    ("jit_fwd(11)", "jit_fwd(22)"),                        # a tie
    ("jit_fwd(11)", "jit_fwd(22)", "jit_fwd(11)"),         # only twice
    ("jit_fwd(1)", "jit_fwd(2)", "jit_fwd(1)", "jit_fwd(2)", "jit_fwd(1)"),
])
def test_a_split_that_would_be_a_guess_is_an_error(names):
    """The readers tell decode from prefill by which program ran most
    often; where none ran over twice as often as the next, they say so
    instead of putting a prefill's time under the decode step's name."""
    from benchmark.metrics._common import stream_programs
    with pytest.raises(RuntimeError, match="cannot tell the decode"):
        stream_programs(_programs(*names))


def test_idle_gaps_are_named_by_what_the_host_was_doing():
    gaps = dict(map(tuple, _trace().idle_gaps()))
    # 0.04-0.10 under np.asarray; 0.16-0.20 under "outer", whose inner
    # Transpose covers less of the gap than it does
    assert gaps["np.asarray_jax.Array"] == pytest.approx(0.06)
    assert gaps["outer"] == pytest.approx(0.04)
    top = _trace().breakdown()
    assert top["device_ops"][0] == ["fusion.9", pytest.approx(0.06)]
    assert len(top["device_ops"]) <= 10 and len(top["idle_gaps"]) <= 10


def test_a_device_op_is_named_by_its_hlo_name_type_and_dimensions():
    from benchmark.xplane import short_op
    hlo = ("%fwd.30 = bf16[32,2,12,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
           "custom-call(s32[32,256]{1,0:T(8,128)S(1)} %copy.1, ...)")
    assert short_op(hlo) == "fwd.30_bf16_32_2_12_128_custom-call"
    assert short_op("%multiply_reduce_fusion.2 = bf16[256]{0:T(256)} "
                    "fusion(...)") == "multiply_reduce_fusion.2_bf16_256"
    assert short_op("%tuple.1 = (f32[8]{0}, f32[8]{0}) fusion(...)") \
        == "tuple.1_f32_8"
    assert short_op("jit_fwd(123)") == "jit_fwd(123)"


def test_names_in_the_breakdown_have_no_space_comma_or_slash():
    assert clean("np.asarray(jax.Array)") == "np.asarray_jax.Array"
    assert clean("a, b / c") == "a_b_c"
    assert clean("") == "_"


def test_a_trace_with_no_device_operation_is_an_error():
    with pytest.raises(ValueError, match="no device operation"):
        Trace({}, {}, []).window_s()
    with pytest.raises(FileNotFoundError):
        find_xplane("/nonexistent")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in the tree")
def test_the_recorded_v5e_trace_reduces_to_known_numbers():
    """scratch recording, PR 25: four executions of a jitted chain of
    three 512x512 bf16 matmul+tanh, a 2 ms host sleep before each."""
    tr = Trace.from_file(RECORDED)
    assert tr.devices() == [0]
    runs = tr.module_runs(r"^jit_step")
    assert len(runs) == 4
    assert all(b - a == pytest.approx(6.31e-06, abs=2e-8) for a, b in runs)
    assert tr.window_s() == pytest.approx(0.009689514, rel=1e-6)
    assert tr.busy_s() == pytest.approx(2.5202e-05, rel=1e-4)
    assert tr.idle_share() == pytest.approx(0.9974, abs=1e-4)
    top = tr.top_ops()
    assert top[0][0] == "convolution_tanh_fusion.1_bf16_512_512"
    assert top[0][1] == pytest.approx(1.5519e-05, rel=1e-3)
    assert top[1][0] == "convolution_tanh_fusion_bf16_512_512"
    # the chip waited on the host's annotated sleep, and the gaps between
    # the four programs are named by it
    gaps = tr.idle_gaps()
    assert gaps[0][0] == "host_work"
    assert gaps[0][1] == pytest.approx(0.0096643, rel=1e-4)
    assert tr.busy_s() + sum(g for _, g in gaps) == \
        pytest.approx(tr.window_s(), rel=1e-3)


# ---- every serving reader on a hand-made run: a number, and the right one
class _Handle:
    def __init__(self, queue_wait_s, prefill_s):
        self._b = {"queue_wait_s": queue_wait_s, "prefill_s": prefill_s}

    def trace(self):
        return self

    def breakdown(self):
        return self._b


class _Req:
    def __init__(self, prompt_len, token_t, queue_wait_s=0.01,
                 prefill_s=0.5):
        self.prompt = [0] * prompt_len
        self.token_t = token_t
        self.handle = _Handle(queue_wait_s, prefill_s)


class _Replay:
    t0, t1 = 0.0, 10.0

    def __init__(self, requests):
        self.requests = requests

    def in_window(self, t):
        return t is not None and self.t0 <= t <= self.t1


def _serving_ctx():
    """A 10 s window, traced from 2 s to 8 s. Two requests: 1,000 prompt
    tokens with a first token at 4 s and two decode tokens, 500 prompt
    tokens with a first token at 2.5 s (inside the trace's first second)
    and one decode token. The trace holds three decode dispatches of 50
    ms (the paged kernel 20 ms of each) and one prefill dispatch of 100
    ms."""
    from benchmark import harness
    from benchmark.peaks import peaks_for
    kernel = "fwd.3_bf16_32_2_12_128_custom-call"
    ops = [("fusion.1", 3.00, 3.03), (kernel, 3.03, 3.05),
           ("fusion.9", 3.50, 3.60),
           ("fusion.1", 5.00, 5.03), (kernel, 5.03, 5.05),
           ("fusion.1", 6.00, 6.03), (kernel, 6.03, 6.05)]
    mods = [("jit_fwd(1)", 3.00, 3.05), ("jit_fwd(2)", 3.50, 3.60),
            ("jit_fwd(1)", 5.00, 5.05), ("jit_fwd(1)", 6.00, 6.05)]
    reqs = [_Req(1000, [4.0, 4.5, 5.0], 0.02, 0.6),
            _Req(500, [2.5, 3.0], 0.04, 0.2)]
    replay = _Replay(reqs)
    cell = harness.Cell(harness.load_benchmark(),
                        "starcoder2-3b.chat_closed32")
    record = {
        "window_s": 10.0,
        "serve": {"replay": replay, "tokens": 5, "sent": reqs,
                  "finished": reqs, "ttfts": [0.7, 0.3],
                  "tpots": [0.5, 0.3, 0.4],
                  "health0": {"decode_dispatch": {"count": 10,
                                                  "mean_ms": 60.0}},
                  "health1": {"decode_dispatch": {"count": 30,
                                                  "mean_ms": 64.0}}}}
    return cell, {"cell": cell, "config": cell.config,
                  "traffic": cell.traffic, "record": record,
                  "trace": Trace({0: ops}, {0: mods}, []),
                  "peaks": peaks_for("TPU v5 lite"), "chips": 1,
                  "trace_interval": (2.0, 8.0)}


def _by_hand(cfg):
    from benchmark import flops
    peak, hbm = 197e12, 819e9
    prefill = flops.starcoder2_prefill_flops(cfg, 1000)
    window = (prefill + flops.starcoder2_prefill_flops(cfg, 500)
              + sum(flops.starcoder2_decode_flops(cfg, c)
                    for c in (1001, 1002, 501)))
    # decode tokens stamped from 2.25 s on: contexts 1001, 1002, 501
    kv = 30 * flops.paged_attention_bytes(cfg, [1001, 1002, 501]) / hbm
    return {
        "engine.decode_dispatch_ms": (30 * 64.0 - 10 * 60.0) / 20,
        "engine.prefill_time_share": 100 * (0.6 + 0.2) / 10.0,
        "decode_step.device_ms": 50.0,
        "decode_step.mfu": 100 * window / (10.0 * peak),
        "paged_attn.roofline": 100 * kv / 0.06,
        "chat.device_idle_share": 100 * (1 - 0.25 / 3.05),
        "chat.tpot_p50_s": 0.4,
        "sched.queue_wait_p50_s": 0.03,
        "engine.prefill_p50_s": 0.4,
        "prefill.device_ms_per_ktok": 100.0,
        "prefill_step.mfu": 100 * prefill / (0.1 * peak),
        "complete.device_idle_share": 100 * (1 - 0.25 / 3.05),
        "complete.ttft_p50_s": 0.5,
        "complete.out_tokens_per_s": 0.5,
    }


_CHAT, _COMPLETE = ("starcoder2-3b.chat_closed32",
                    "starcoder2-3b.complete_closed8")
#: the reader -> the one cell its entry lists (``_by_hand`` counts with
#: this configuration's FLOPs and this cell's mix)
SERVING_READERS = {
    "engine.decode_dispatch_ms": _CHAT, "engine.prefill_time_share": _CHAT,
    "decode_step.device_ms": _CHAT, "decode_step.mfu": _CHAT,
    "paged_attn.roofline": _CHAT, "chat.device_idle_share": _CHAT,
    "chat.tpot_p50_s": _CHAT, "sched.queue_wait_p50_s": _COMPLETE,
    "engine.prefill_p50_s": _COMPLETE,
    "prefill.device_ms_per_ktok": _COMPLETE, "prefill_step.mfu": _COMPLETE,
    "complete.device_idle_share": _COMPLETE,
    "complete.ttft_p50_s": _COMPLETE,
    "complete.out_tokens_per_s": _COMPLETE}


@pytest.mark.parametrize("metric", SERVING_READERS)
def test_a_serving_reader_reads_the_number_a_hand_count_gives(metric):
    cell, ctx = _serving_ctx()
    got = cell.reader(metric)(ctx)
    assert got == pytest.approx(_by_hand(cell.config)[metric], rel=1e-9)
    if metric.endswith("roofline") or "mfu" in metric:
        assert 0 < got < 100


def test_every_serving_metric_of_the_benchmark_has_that_test():
    """Every ``per_layer`` entry that names one of the two serving cells
    these tables were made for is pinned against a hand count: here
    (``SERVING_READERS``, on ``_serving_ctx``) or in
    ``test_benchmark_spans.py`` (``BY_HAND``). Either table also says
    which cell: the entry lists that one and no other, so no cell that
    comes later can be appended to an entry whose hand count is this
    configuration's. Neither table holds a name without such an entry.

    The rule for what comes later: an entry whose cells are all of a
    configuration added later, or later cells of this one, is outside
    these tables by the cells it lists, whatever its name begins with. It
    lists only its own cells, and it brings its reader file and a
    hand-count test of that reader in a new file of this directory. The
    ``set-up`` layer's entries are outside them too: their readers hold
    for any cell and are pinned, cells and all, in
    ``test_benchmark_setup_readers.py``."""
    from benchmark import harness
    from test_benchmark_spans import BY_HAND, CHAT, COMPLETE
    assert (CHAT, COMPLETE) == (_CHAT, _COMPLETE)
    bench = harness.load_benchmark()
    entries = {m["name"]: m.get("workloads") for m in bench["per_layer"]
               if m["layer"] != "set-up" and (
                   "workloads" not in m
                   or {CHAT, COMPLETE} & set(m["workloads"]))}
    spans = {metric: cell for cell, metric in BY_HAND}
    assert not set(SERVING_READERS) & set(spans)
    assert entries == {metric: [cell] for metric, cell in
                       {**SERVING_READERS, **spans}.items()}


def _unpinned_entry(bench, cell, layer="serving engine"):
    like = next(m for m in bench["per_layer"] if m["layer"] == layer)
    bench["per_layer"].append(dict(like, name="engine.unpinned",
                                   workloads=[cell]))


def _pinned_name_without_its_entry(bench, cell):
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m["name"] != "engine.batch_occupancy"]


def _span_reader_on_the_other_cell(bench, cell):
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "prefill.padding_share")
    entry["workloads"] = [cell]


def _later_cell_on_an_accepted_entry(bench, cell):
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "decode_step.mfu")
    entry["workloads"].append("later.cell")


@pytest.mark.parametrize("change,ok", [
    (_unpinned_entry, False), (_pinned_name_without_its_entry, False),
    (_span_reader_on_the_other_cell, False),
    (_later_cell_on_an_accepted_entry, False),
    # the same entry on a cell that came later is nobody's but its own
    (lambda bench, cell: _unpinned_entry(bench, "later.cell"), True),
    # and the set-up layer's are test_benchmark_setup_readers.py's
    (lambda bench, cell: _unpinned_entry(bench, cell, "set-up"), True),
], ids=["unpinned_entry", "pinned_name_without_its_entry",
        "span_reader_on_the_other_cell",
        "later_cell_on_an_accepted_entry", "entry_of_a_later_cell",
        "set_up_entry_on_the_cell"])
def test_that_guard_holds_the_tables_and_the_entries_together(
        monkeypatch, change, ok):
    from benchmark import harness
    bench = harness.load_benchmark()
    change(bench, "starcoder2-3b.chat_closed32")
    monkeypatch.setattr(harness, "load_benchmark", lambda: bench)
    if ok:
        test_every_serving_metric_of_the_benchmark_has_that_test()
    else:
        with pytest.raises(AssertionError):
            test_every_serving_metric_of_the_benchmark_has_that_test()


def test_a_reader_with_nothing_to_read_returns_nothing():
    cell, ctx = _serving_ctx()
    ctx["trace"] = Trace({0: [("fusion.1", 3.0, 3.1)]}, {0: []}, [])
    for metric in ("decode_step.device_ms", "paged_attn.roofline",
                   "prefill.device_ms_per_ktok", "prefill_step.mfu"):
        assert cell.reader(metric)(ctx) is None
