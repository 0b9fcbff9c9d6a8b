"""Device-idle time split by the program's phases (``metrics/_spans.py``)
and the readers built on it: on a hand-made trace whose idle time is
known span by span, on counters of ``health()`` at both ends of a window,
and on a run of a program that has neither (every reader reads nothing)."""

import pytest

from benchmark import harness
from benchmark.metrics import _spans
from benchmark.xplane import Trace

CHAT = "starcoder2-3b.chat_closed32"
COMPLETE = "starcoder2-3b.complete_closed8"


def _trace(host):
    """Device 0 busy 1.0-1.2, 2.0-2.2 and 3.0-3.2: idle 1.2-2.0 and
    2.2-3.0, 1.6 s in all."""
    ops = [("fusion.1", 1.0, 1.1), ("fusion.2", 1.1, 1.2),
           ("fusion.1", 2.0, 2.2), ("fusion.1", 3.0, 3.2)]
    return Trace({0: ops}, {0: []}, host)


CYCLE = [
    ("engine.sample", 1.25, 1.45),          # wholly inside an idle gap
    ("engine.reap", 1.45, 1.46),
    ("engine.admit", 1.46, 1.50),
    ("prefill.input", 1.50, 1.70),
    ("prefill.forward", 1.70, 1.75),
    ("prefill.fetch", 1.75, 1.85),
    ("engine.seat", 1.85, 1.90),
    ("decode.input", 1.90, 2.00),
    ("decode.forward", 2.00, 2.05),         # wholly inside a device op
    ("decode.fetch", 2.05, 2.30),           # cut by the op's end at 2.2
    ("engine.sample", 2.30, 2.50),
    ("decode.input", 2.80, 3.00),           # 2.50-2.80: in no span
    ("decode.forward", 3.00, 3.01),
    ("decode.fetch", 3.01, 3.40),           # runs past the last op
    ("XlaRuntimeThing", 1.30, 1.40),        # not the program's: no part
]


def test_idle_intervals_are_the_gaps_between_merged_ops():
    assert _spans.idle_intervals(_trace([("step", 0.0, 9.0)])) == [
        pytest.approx((1.2, 2.0)), pytest.approx((2.2, 3.0))]


@pytest.mark.parametrize("names,want", [
    (("engine.sample",), 0.20 + 0.20),              # idle inside
    (("decode.forward",), 0.0),                     # busy all through
    (("decode.fetch",), 0.10),                      # 2.2-2.3 of 2.05-2.3;
                                                    # nothing after 3.2
    (("decode.input",), 0.10 + 0.20),
    (_spans.DECODE_IO, 0.40),
    (_spans.ADMISSION, 0.04 + 0.20 + 0.05 + 0.10 + 0.05),
    (("engine.reap",), 0.01),
    (("XlaRuntimeThing",), 0.10),                   # any host event can be
                                                    # asked for by name
])
def test_idle_in_counts_only_idle_time_inside_the_named_spans(names, want):
    assert _spans.idle_in(_trace(CYCLE), names) == pytest.approx(want)


def test_parts_the_unnamed_rest_and_the_cut_ends_add_up_to_the_idle_time():
    """... the idle time ``*.device_idle_share`` counts: window - busy."""
    tr = _trace(CYCLE)
    parts = [_spans.idle_in(tr, (n,)) for n in _spans.PROGRAM_SPANS]
    idle = tr.idle_share() * tr.window_s()
    assert idle == pytest.approx(1.6)
    named = sum(parts)
    assert named == pytest.approx(_spans.idle_in(tr, _spans.PROGRAM_SPANS))
    # in no span: 2.50-2.80; before the first span the trace holds
    # (1.20-1.25) a span was cut by the trace's start
    unnamed, cut = _spans.idle_shares(tr)
    assert unnamed == pytest.approx(100 * 0.30 / 1.6)
    assert cut == pytest.approx(100 * 0.05 / 1.6)
    assert named + (unnamed + cut) / 100 * idle == pytest.approx(idle)


def test_idle_time_outside_the_first_and_last_span_is_a_cut_span():
    """A span reaches the trace when it closes inside it. Here the trace
    ended inside a long fetch: the last span it holds ends at 2.5, and
    the idle time after it (2.5-3.0) is reported as cut, not as unnamed
    and not as nothing."""
    tr = _trace([e for e in CYCLE if e[2] <= 2.5])
    assert _spans.idle_unnamed_share(tr) == pytest.approx(0.0)
    assert _spans.idle_cut_share(tr) == \
        pytest.approx(100 * (0.05 + 0.50) / 1.6)


def test_overlap_counts_a_second_once_whatever_the_order():
    assert _spans.overlap_s([(0, 10)], [(1, 2), (1.5, 3), (9, 12)]) == \
        pytest.approx(3.0)
    assert _spans.overlap_s([(5, 6), (0, 1)], [(0.5, 5.5)]) == \
        pytest.approx(1.0)
    assert _spans.overlap_s([], [(0, 1)]) == 0.0


def test_a_trace_without_program_spans_reads_nothing():
    tr = _trace([("np.asarray(jax.Array)", 1.2, 1.9), ("step", 2.2, 2.9)])
    assert _spans.idle_in(tr, _spans.SAMPLE) is None
    assert _spans.idle_unnamed_share(tr) is None
    assert _spans.idle_cut_share(tr) is None
    assert _spans.idle_ms_per(tr, _spans.SAMPLE, "engine.sample") is None


def test_idle_per_span_divides_by_the_spans_named():
    tr = _trace(CYCLE)
    assert _spans.idle_ms_per(tr, _spans.SAMPLE, "engine.sample") == \
        pytest.approx(200.0)
    assert _spans.idle_ms_per(tr, _spans.DECODE_IO, "decode.forward") == \
        pytest.approx(200.0)
    # a phase that never ran in the trace divides by nothing
    no_prime = [e for e in CYCLE if not e[0].startswith("prefill")]
    assert _spans.idle_ms_per(_trace(no_prime), ("prefill.fetch",),
                              "prefill.forward") is None


# ---- the readers, on that trace and on counters of health()
def _health(count, rows, fed, bucket, dh, dd, ph, pd):
    return {"slots": 4,
            "decode_dispatch": {"count": count, "mean_ms": 50.0,
                                "rows": rows},
            "prefill": {"fed_tokens": fed, "bucket_tokens": bucket},
            "host_io": {"decode": {"h2d_bytes": dh, "d2h_bytes": dd},
                        "prefill": {"h2d_bytes": ph, "d2h_bytes": pd}}}


def _ctx(host=CYCLE, health=True):
    if health:
        h0 = _health(100, 390, 7000, 9000, 10**6, 10**6, 10**8, 10**8)
        h1 = _health(110, 425, 7300, 9512, 10**6 + 4000, 10**6 + 6000,
                     10**8 + 5 * 10**7, 10**8 + 10**7)
    else:       # the program before PR 26: health() as it was
        h0 = {"slots": 4, "decode_dispatch": {"count": 100,
                                              "mean_ms": 50.0}}
        h1 = {"slots": 4, "decode_dispatch": {"count": 110,
                                              "mean_ms": 50.0}}
    return {"trace": _trace(host),
            "record": {"window_s": 10.0,
                       "serve": {"tokens": 40, "health0": h0,
                                 "health1": h1}}}


BY_HAND = {
    (CHAT, "engine.idle_in_sample_ms"): 0.40 / 2 * 1e3,
    (CHAT, "engine.idle_in_decode_io_ms"): 0.40 / 2 * 1e3,
    (CHAT, "engine.idle_in_admission_ms"): 0.44 / 2 * 1e3,
    (CHAT, "chat.idle_unnamed_share"): 100 * 0.30 / 1.6,
    (CHAT, "chat.idle_cut_share"): 100 * 0.05 / 1.6,
    (CHAT, "engine.batch_occupancy"): 100 * 35 / (10 * 4),
    (CHAT, "engine.host_io_mb_per_token"):
        (4000 + 6000 + 5 * 10**7 + 10**7) / 40 / 1e6,
    (COMPLETE, "prefill.idle_in_input_ms"): 250.0,
    (COMPLETE, "prefill.idle_in_fetch_ms"): 100.0,
    (COMPLETE, "prefill.padding_share"): 100 * (1 - 300 / 512),
    (COMPLETE, "prefill.host_io_mb_per_ktok"): 6 * 10**7 / 300 * 1e3 / 1e6,
    (COMPLETE, "complete.idle_unnamed_share"): 100 * 0.30 / 1.6,
    (COMPLETE, "complete.idle_cut_share"): 100 * 0.05 / 1.6,
    (COMPLETE, "complete.idle_in_decode_ms"): 0.80 / 2 * 1e3,
    (COMPLETE, "prefill.idle_in_seat_ms"): (0.04 + 0.05) * 1e3,
}


def _reader(cell_name, metric):
    """Found by file name, as the harness finds an entry's reader; each
    has its entry in ``BENCHMARK.json``, on the cell this table names
    (``test_benchmark_xplane.py`` holds the two together)."""
    return harness.Cell(harness.load_benchmark(), cell_name).reader(metric)


@pytest.mark.parametrize("cell_name,metric", sorted(BY_HAND))
def test_a_reader_reads_the_number_a_hand_count_gives(cell_name, metric):
    got = _reader(cell_name, metric)(_ctx())
    assert got == pytest.approx(BY_HAND[cell_name, metric], rel=1e-9)


@pytest.mark.parametrize("cell_name,metric", sorted(BY_HAND))
def test_on_a_program_without_spans_and_counters_it_reads_nothing(
        cell_name, metric):
    """The parent of PR 26 runs these readers too: no program span in its
    trace, no new key in its ``health()``. Nothing is read, nothing
    raised."""
    ctx = _ctx(host=[("np.asarray(jax.Array)", 1.2, 1.9)], health=False)
    assert _reader(cell_name, metric)(ctx) is None


def test_every_reader_file_without_an_entry_is_in_that_table():
    """No reader file of ``benchmark/metrics`` is left without an entry
    unless this table pins it, and every name of the table is a file."""
    import os
    bench = harness.load_benchmark()
    files = {f[:-3] for f in os.listdir(os.path.dirname(_spans.__file__))
             if f.endswith(".py") and not f.startswith("_")}
    by_hand = {metric for _, metric in BY_HAND}
    assert by_hand <= files
    assert files - {m["name"] for m in bench["per_layer"]} <= by_hand


def test_the_program_and_the_benchmark_name_the_same_spans():
    """``_spans.PROGRAM_SPANS`` (the benchmark keeps its own copy: it
    also runs against a program that has none) against the engine's one
    table of names, and against the strings the trace reduction drops
    as noise."""
    from benchmark.xplane import HOST_NOISE
    from deeplearning4j_tpu.serving.engine import PHASES
    assert set(_spans.PROGRAM_SPANS) == set(PHASES)
    assert _spans.ADMISSION + _spans.DECODE_IO + _spans.SAMPLE == PHASES[1:]
    for name in _spans.PROGRAM_SPANS:
        assert not any(n in name for n in HOST_NOISE)
        assert not any(c in name for c in " ,/")
