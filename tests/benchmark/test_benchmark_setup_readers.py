"""The six ``setup.*`` readers: the compile ledger (event category
``compile`` of the process's event log, monitoring/runtime.py) and the
serving engine's ``health()["setup"]["started_at"]`` split ``setup_s``
into compiling, loading from the cache, tracing and lowering, the lead-in
and what none of them names. Each reader against a hand count over a
synthetic ledger; on a record with no trace, and on a program with no
ledger and no stamp, each reads nothing; each entry lists exactly its
cells."""

import types

import pytest

from benchmark import harness
from deeplearning4j_tpu.monitoring import events

BENCH = harness.load_benchmark()
TRAIN = "resnet50.fit_b256"
DOCS = "deepseek-v3.2.docs_closed16"
REASON = "olmo-hybrid-7b.reason_closed32"
SERVING = [DOCS, REASON]

#: metric -> (unit, source, cells), as the entries have to read
ENTRIES = {
    "setup.compile_s": ("s", "program_counter", [TRAIN, DOCS, REASON]),
    "setup.cache_load_s": ("s", "program_counter", [TRAIN, DOCS, REASON]),
    "setup.trace_lower_s": ("s", "program_counter", [TRAIN, DOCS, REASON]),
    "setup.programs_compiled": ("programs", "program_counter",
                                [TRAIN, DOCS, REASON]),
    "setup.lead_in_s": ("s", "program_span", [DOCS, REASON]),
    "setup.unnamed_share": ("%", "program_span", [TRAIN, DOCS, REASON]),
}

#: the process started at 0.0 on the clock; the engine started at 20.0,
#: the window opened at 30.0 and the trace ran from 32.0 to 38.0
STARTED, T0, TRACE = 20.0, 30.0, (32.0, 38.0)

#: (outcome, trace_s, lower_s, backend_s, end)
LEDGER = [
    # set-up, before the engine started
    ("compiled", 0.5, 0.25, 2.0, 10.0),
    ("loaded", 0.1, 0.2, 0.3, 11.0),
    ("uncached", 0.05, 0.05, 1.0, 12.0),
    # the lead-in: after the engine started, before the window opened
    ("compiled", 0.2, 0.1, 4.0, 25.0),
    # the reference, after the trace began: not set-up
    ("compiled", 1.0, 1.0, 9.0, 40.0),
]

#: by hand over the first four entries (the lead-in's one included)
SERVING_WANT = {
    "setup.compile_s": 2.0 + 1.0 + 4.0,
    "setup.cache_load_s": 0.3,
    "setup.trace_lower_s": 0.75 + 0.3 + 0.1 + 0.3,
    "setup.programs_compiled": 3,
    "setup.lead_in_s": T0 - STARTED,
    # the ledger before the engine started (2.75 + 0.6 + 1.1 s) and the
    # lead-in (10 s) are named; of 30 s, 15.55 s are not
    "setup.unnamed_share": 100.0 * (30.0 - 4.45 - 10.0) / 30.0,
}
#: a training cell has no engine: everything before the trace is named
#: by the ledger alone (8.75 s of 30 s)
TRAIN_WANT = dict(SERVING_WANT, **{
    "setup.lead_in_s": None,
    "setup.unnamed_share": 100.0 * (30.0 - 8.75) / 30.0})


@pytest.fixture
def ledger(monkeypatch):
    """A fresh process-wide event log holding the synthetic ledger and
    one event of another category."""
    log = events.EventLog()
    monkeypatch.setattr(events, "_global_log", log)
    log.emit("serving", "drain")
    for outcome, t, lo, be, end in LEDGER:
        log.emit("compile", "build", fn="step", outcome=outcome,
                 trace_s=t, lower_s=lo, backend_s=be,
                 start=end - t - lo - be, end=end)
    return log


def _ctx(cell_name, trace=True, stamp=True):
    cell = harness.Cell(BENCH, cell_name)
    record = {"setup_s": T0 - 0.0}
    if cell_name != TRAIN:
        health0 = {"healthy": True}
        if stamp:
            health0["setup"] = {"started_at": STARTED}
        record["serve"] = {"replay": types.SimpleNamespace(t0=T0),
                           "health0": health0}
    return {"cell": cell, "record": record,
            "trace_interval": TRACE if trace else None}


@pytest.mark.parametrize("cell_name", SERVING)
@pytest.mark.parametrize("metric", sorted(ENTRIES))
def test_each_reader_is_a_hand_count_on_a_serving_cell(ledger, cell_name,
                                                       metric):
    got = harness.Cell(BENCH, cell_name).reader(metric)(_ctx(cell_name))
    assert got == pytest.approx(SERVING_WANT[metric])


@pytest.mark.parametrize("metric", sorted(ENTRIES))
def test_each_reader_is_a_hand_count_on_the_training_cell(ledger, metric):
    got = harness.Cell(BENCH, TRAIN).reader(metric)(_ctx(TRAIN))
    if TRAIN_WANT[metric] is None:
        assert got is None
    else:
        assert got == pytest.approx(TRAIN_WANT[metric])


@pytest.mark.parametrize("cell_name", [TRAIN, DOCS])
def test_the_parts_add_up_to_setup_s(ledger, cell_name):
    """The ledger before the engine started, the lead-in and the
    remainder are ``setup_s``, by construction."""
    ctx = _ctx(cell_name)
    cell = ctx["cell"]
    end = STARTED if cell_name != TRAIN else TRACE[0]
    named = sum(t + lo + be for _, t, lo, be, e in LEDGER if e < end)
    lead_in = cell.reader("setup.lead_in_s")(ctx) or 0.0
    rest = cell.reader("setup.unnamed_share")(ctx) / 100.0 * 30.0
    assert named + lead_in + rest == pytest.approx(30.0)


@pytest.mark.parametrize("metric", sorted(ENTRIES))
def test_without_a_trace_a_reader_reads_nothing(ledger, metric):
    for cell_name in (TRAIN, DOCS):
        ctx = _ctx(cell_name, trace=False)
        assert ctx["cell"].reader(metric)(ctx) is None


@pytest.mark.parametrize("metric", sorted(ENTRIES))
def test_on_a_program_with_no_ledger_and_no_stamp_it_reads_nothing(
        monkeypatch, metric):
    """The parent of these readers: no ``compile`` event in its log, no
    ``setup`` key in its ``health()``. Nothing read, nothing raised."""
    log = events.EventLog()
    log.emit("serving", "drain")
    monkeypatch.setattr(events, "_global_log", log)
    for cell_name in (TRAIN, DOCS):
        ctx = _ctx(cell_name, stamp=False)
        assert ctx["cell"].reader(metric)(ctx) is None


def test_a_warm_run_reads_no_program_compiled(monkeypatch):
    log = events.EventLog()
    monkeypatch.setattr(events, "_global_log", log)
    log.emit("compile", "build", fn="step", outcome="loaded", trace_s=0.1,
             lower_s=0.2, backend_s=0.3, start=5.0, end=5.6)
    ctx = _ctx(DOCS)
    assert ctx["cell"].reader("setup.programs_compiled")(ctx) == 0
    assert ctx["cell"].reader("setup.compile_s")(ctx) == 0


@pytest.mark.parametrize("metric", sorted(ENTRIES))
def test_each_entry_lists_exactly_its_cells(metric):
    unit, source, cells = ENTRIES[metric]
    [entry] = [m for m in BENCH["per_layer"] if m["name"] == metric]
    assert entry == {"name": metric, "unit": unit, "better": "lower",
                     "source": source, "layer": "set-up",
                     "moves": "setup_s", "workloads": cells}


def test_the_entries_are_the_last_six_and_name_no_pinned_cell():
    """Appended after every accepted entry, and none names chat, complete
    or longdocs, whose entries other tests pin to their own tables."""
    assert [m["name"] for m in BENCH["per_layer"][-6:]] == list(ENTRIES)
    pinned = {w["name"] for w in BENCH["workloads"]} - {TRAIN, DOCS, REASON}
    for _, _, cells in ENTRIES.values():
        assert not pinned & set(cells)


def test_the_pinned_tables_of_the_serving_and_longdocs_cells_still_hold():
    import test_benchmark_longdocs_cell
    import test_benchmark_xplane
    test_benchmark_xplane.\
        test_every_serving_metric_of_the_benchmark_has_that_test()
    test_benchmark_longdocs_cell.test_the_cells_entries_list_it()
