"""The replayed closed-loop traffic: one table for every seed, token ids
from the seed, one generator thread, the same admission order twice."""

import threading
import time

import pytest

from benchmark import harness
from benchmark.replay import (ClosedLoopReplay, percentile, prompt_ids)

BENCH = harness.load_benchmark()
ROOT = harness.ROOT


def serving_cells(bench, root):
    """Every cell of ``bench`` whose mix is of the kind
    ``serve_closed_replay``: the two rules below that take a ``name`` hold
    for each, whatever its configuration (the runner's docstring states
    them as the mix's contract)."""
    return [w["name"] for w in bench["workloads"]
            if harness.Cell(bench, w["name"], root=root).traffic["kind"]
            == "serve_closed_replay"]


SERVING = serving_cells(BENCH, ROOT)


@pytest.mark.parametrize("name", SERVING)
def test_the_schedule_is_data_and_stays_under_the_served_context(name):
    cell = harness.Cell(BENCH, name, root=ROOT)
    t = cell.traffic
    assert t["loop"] == "closed" and t["think_time_s"] == 0.0
    assert isinstance(t["generator_seed"], int)
    cap = cell.config["departures"]["served_max_context"]
    # a model whose file says (under departures) that its windowed layers
    # are served as full causal attention is served inside its window,
    # where the two are one function; a null window is no window, and a
    # model served with its windows may be served past them
    window = cell.config.get("sliding_window")
    if isinstance(window, (int, float)) \
            and "sliding_window" in cell.config["departures"]:
        assert cap <= window
    lengths = [(p, o) for c in t["clients"] for p, o in c]
    assert max(p + o for p, o in lengths) == t["drawn"]["max_context"] <= cap
    assert min(p for p, _ in lengths) >= t["prompt_tokens"]["min"]
    assert max(p for p, _ in lengths) <= t["prompt_tokens"]["max"]
    assert min(o for _, o in lengths) >= t["output_tokens"]["min"]
    assert max(o for _, o in lengths) <= t["output_tokens"]["max"]
    # the pool of the configuration never refuses the cell a page
    e = cell.config["engine"]
    need = sum(sorted((-(-(p + o) // e["page_size"])
                       for c in t["clients"] for p, o in c),
                      reverse=True)[:len(t["clients"])])
    assert len(t["clients"]) <= e["slots"]
    assert need <= e["total_pages"]
    # long enough for a 51 s window at ten times today's speed
    assert all(len(c) >= 40 for c in t["clients"])


@pytest.mark.parametrize("name,clients,median_lo,median_hi", [
    ("starcoder2-3b.chat_closed32", 32, 330, 440),
    ("starcoder2-3b.complete_closed8", 8, 1300, 1700),
])
def test_the_tables_are_the_mixes_the_issue_names(name, clients, median_lo,
                                                  median_hi):
    cell = harness.Cell(BENCH, name)
    t = cell.traffic
    assert len(t["clients"]) == clients
    assert median_lo <= t["drawn"]["prompt_median"] <= median_hi
    assert cell.config["departures"]["served_max_context"] \
        == cell.config["sliding_window"] == 4096


@pytest.mark.parametrize("name", SERVING)
def test_the_committed_table_is_what_its_recorded_parameters_draw(name):
    """The table is data, and a function of the distribution and generator
    seed written beside it: drawing again gives the committed table."""
    import importlib.util
    import os
    cell = harness.Cell(BENCH, name, root=ROOT)
    path = cell.find(os.path.join("traffic", "draw_table.py"))
    spec = importlib.util.spec_from_file_location("draw_table", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    t = cell.traffic
    assert mod.draw_clients(t) == t["clients"]
    assert mod.summary(t["clients"]) == t["drawn"]
    assert len(t["clients"]) == t["table"]["clients"]


def test_every_seed_replays_one_table_with_its_own_token_ids():
    a = harness.Cell(BENCH, SERVING[0]).traffic["clients"]
    b = harness.Cell(BENCH, SERVING[0]).traffic["clients"]
    assert a == b                      # nothing of --seed reaches the table
    big = 2 ** 31 + 11                 # the driver's seeds pass 32 bits
    ids1 = prompt_ids(1, 3, 0, 200, 49152)
    assert ids1 == prompt_ids(1, 3, 0, 200, 49152)
    assert ids1 != prompt_ids(2, 3, 0, 200, 49152)
    assert ids1 != prompt_ids(1, 4, 0, 200, 49152)
    assert ids1 != prompt_ids(1, 3, 1, 200, 49152)
    assert prompt_ids(big, 0, 0, 50, 49152) != prompt_ids(big + 1, 0, 0,
                                                          50, 49152)
    assert len(ids1) == 200 and 0 <= min(ids1) and max(ids1) < 49152


class ToyHandle:
    """What the generator knows of a stream handle: ``done``, ``error``,
    ``ids`` (a copy) and the list ``_ids`` that grows in place."""

    def __init__(self, prompt):
        self._ids = list(prompt)
        self.done = False
        self.error = None

    @property
    def ids(self):
        return list(self._ids)


class ToyEngine:
    """Two slots, one token per active slot per step; the step loop runs on
    a thread of its own, as the engine's does. It is slow beside the
    generator, as the served model is (150 ms a step against a 1 ms poll):
    while the loop is closed it takes its next step only when every
    finished request has been replaced, and it admits a step's arrivals
    in order of size, so that nothing hangs on which of two handles the
    generator happened to look at first."""

    def __init__(self, clients, slots=2, step_s=0.002):
        self.inbox, self.queue, self.slots = [], [], [None] * slots
        self.admitted = []             # (prompt length, steps) in order
        self.clients, self.submitted, self.finished = clients, 0, 0
        self.open = True               # the generator still sends
        self.step_s, self.lock = step_s, threading.Lock()
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._loop, daemon=True)
        self._t.start()

    def submit(self, prompt, steps):
        h = ToyHandle(prompt)
        with self.lock:
            self.inbox.append((h, steps))
            self.submitted += 1
        return h

    def _caught_up(self):
        with self.lock:
            return self.submitted >= self.finished + self.clients

    def _loop(self):
        while not self._stop.is_set():
            while self.open and not self._stop.is_set() \
                    and not self._caught_up():
                time.sleep(0.0002)
            with self.lock:
                self.queue += sorted(self.inbox,
                                     key=lambda q: (len(q[0]._ids), q[1]))
                self.inbox.clear()
                for s, cur in enumerate(self.slots):
                    if cur is None and self.queue:
                        h, steps = self.queue.pop(0)
                        self.admitted.append((len(h._ids), steps))
                        self.slots[s] = [h, steps]
                for s, cur in enumerate(self.slots):
                    if cur is None:
                        continue
                    cur[0]._ids.append(7)
                    cur[1] -= 1
                    if cur[1] == 0:
                        cur[0].done = True
                        self.finished += 1
                        self.slots[s] = None
            time.sleep(self.step_s)

    def close(self):
        self._stop.set()
        self._t.join(timeout=5)
        assert not self._t.is_alive()


TABLE = [[[5, 3], [9, 2], [4, 4], [6, 2]] * 40,
         [[8, 2], [3, 5], [7, 3], [5, 2]] * 40,
         [[2, 4], [6, 3], [9, 2], [3, 3]] * 40]


def _replay_once(seed):
    eng = ToyEngine(clients=len(TABLE))
    try:
        r = ClosedLoopReplay(eng.submit, TABLE, seed, 100, seconds=0.25,
                             poll_s=0.0005).start()
        r.wait_closed(timeout=30)
        eng.open = False
        r.drain_first_tokens(timeout=5)
    finally:
        eng.close()
    return r, eng.admitted


def test_the_closed_loop_gives_the_same_admission_order_twice():
    r1, order1 = _replay_once(seed=1)
    r2, order2 = _replay_once(seed=2)
    n = min(len(order1), len(order2))
    assert n >= 20
    assert order1[:n] == order2[:n]
    # each client walks its own list in order, one request outstanding
    for r in (r1, r2):
        for c in range(len(TABLE)):
            mine = [q for q in r.requests if q.client == c]
            assert [q.index for q in mine] == list(range(len(mine)))
            assert [(len(q.prompt), q.steps) for q in mine] == \
                [tuple(x) for x in TABLE[c][:len(mine)]]
            for a, b in zip(mine, mine[1:]):
                assert a.done_t is not None and b.send_t >= a.done_t
    # token ids follow the seed, the schedule does not
    assert r1.requests[0].prompt != r2.requests[0].prompt


def test_the_window_opens_in_flight_and_samples_only_what_it_holds():
    r, _ = _replay_once(seed=3)
    assert 0.25 <= r.t1 - r.t0 < 0.75      # a loaded host closes late
    lead_in = [q for q in r.requests if q.index == 0]
    assert all(q.send_t < r.t0 for q in lead_in)       # begun in set-up
    assert not set(map(id, lead_in)) & set(map(id, r.sent_in_window()))
    assert all(r.in_window(q.send_t) and r.in_window(q.done_t)
               for q in r.finished_in_window())
    assert len(r.tpots()) <= len(r.finished_in_window())
    assert len(r.ttfts()) == len(r.sent_in_window())
    stamped = sum(len(q.token_t) for q in r.requests)
    assert 0 < r.tokens_in_window() <= stamped
    for q in r.finished_in_window():
        assert len(q.token_t) == q.steps == len(q.generated)


def test_a_table_that_runs_out_is_an_error_not_a_quiet_window():
    eng = ToyEngine(clients=1)
    try:
        r = ClosedLoopReplay(eng.submit, [[[2, 1]] * 3], 1, 10,
                             seconds=5.0, poll_s=0.0005).start()
        with pytest.raises(RuntimeError, match="ran out of schedule"):
            r.wait_closed(timeout=30)
    finally:
        eng.close()


def test_a_handle_without_its_growing_list_is_an_error_not_a_slow_copy():
    """The generator counts tokens by the length of the handle's ``_ids``;
    were that renamed, a fall back to the copying ``ids`` would steal the
    interpreter from the engine and move every latency without a word."""
    class Renamed:
        done, error = False, None

        def __init__(self, prompt):
            self.ids = list(prompt)

    r = ClosedLoopReplay(lambda prompt, steps: Renamed(prompt),
                         [[[2, 1]] * 3], 1, 10, seconds=5.0,
                         poll_s=0.0005).start()
    with pytest.raises(RuntimeError, match="no `_ids` list"):
        r.wait_closed(timeout=30)


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90, 9),
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 50, 5),
    ([3.0], 90, 3.0),
    ([1, 2, float("inf")], 90, float("inf")),      # a missing answer
    ([1] * 9 + [float("inf")], 90, 1),
])
def test_percentile_is_nearest_rank_over_all_values(values, q, want):
    assert percentile(values, q) == want
