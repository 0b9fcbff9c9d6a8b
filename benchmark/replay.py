"""The closed-loop replay generator: ONE thread that reads every client's
stream, stamps tokens on a monotonic clock and sends a client's next
request the moment its last one finishes.

The schedule is the traffic file's table: for every client an ordered list
of (prompt tokens, output tokens). Every ``--seed`` replays it in the same
order; the seed gives the token ids only. So the order of events follows
the engine's step count, not the clock and not the seed.

It knows nothing of the engine beyond ``submit(prompt_ids, steps)`` and the
handle's ``done`` / ``error`` / ``ids``, and one private name: ``_ids``,
the list the engine's handle grows in place (see ``_count``).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, List, Optional

import numpy as np


def prompt_ids(seed: int, client: int, index: int, length: int,
               vocab: int) -> List[int]:
    """Token ids of one request, a function of (seed, client, index)."""
    rng = np.random.default_rng([int(seed), int(client), int(index)])
    return rng.integers(0, vocab, length).tolist()


def _count(handle, prompt_len: int) -> int:
    """Tokens generated so far. The engine's handle grows ``_ids`` in
    place; reading its length is O(1), where the public ``ids`` copies
    the whole list (32 streams polled every millisecond would steal the
    interpreter from the engine's own loop, and move every latency). The
    handle has no public count, so a handle without ``_ids`` is an error
    and never a quiet fall back to the copy."""
    try:
        return len(handle._ids) - prompt_len
    except AttributeError:
        raise RuntimeError(
            "the stream handle has no `_ids` list any more: give "
            "benchmark/replay.py another O(1) count of the tokens so far "
            "(PERF.md, Open questions: what only the program can give)"
        ) from None


class Request:
    __slots__ = ("client", "index", "prompt", "steps", "handle", "send_t",
                 "token_t", "done_t", "error")

    def __init__(self, client, index, prompt, steps, handle, send_t):
        self.client, self.index = client, index
        self.prompt, self.steps = prompt, steps
        self.handle, self.send_t = handle, send_t
        self.token_t: List[float] = []
        self.done_t: Optional[float] = None
        self.error: Optional[BaseException] = None

    @property
    def generated(self) -> List[int]:
        return list(self.handle.ids[len(self.prompt):])


class ClosedLoopReplay:
    """Drives ``submit`` from ``table`` (``clients[c] = [[prompt, out],
    ...]``). ``start()`` puts every client's first request in flight;
    the window opens (``t0``) when each client has had a first token, and
    closes ``seconds`` later. Requests sent before ``t0`` are the lead-in:
    their tokens inside the window count for throughput, their latencies
    do not enter the samples."""

    def __init__(self, submit: Callable, table: List[List[List[int]]],
                 seed: int, vocab: int, seconds: float,
                 poll_s: float = 0.001, clock=time.perf_counter,
                 on_open: Optional[Callable[[float], None]] = None):
        self.submit, self.table = submit, table
        self.seed, self.vocab, self.seconds = seed, vocab, seconds
        self.poll_s, self.clock, self.on_open = poll_s, clock, on_open
        self.requests: List[Request] = []       # in send order
        self.t0: Optional[float] = None
        self.t1: Optional[float] = None
        self._next = [0] * len(table)
        self._live: List[Optional[Request]] = [None] * len(table)
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="replay-generator")
        self._closed = threading.Event()
        self._failure: Optional[BaseException] = None

    # -- driving --------------------------------------------------------
    def start(self) -> "ClosedLoopReplay":
        self._thread.start()
        return self

    def wait_closed(self, timeout: float) -> None:
        if not self._closed.wait(timeout):
            raise TimeoutError("the replay window did not close")
        self._thread.join(timeout=10)
        if self._failure is not None:
            raise self._failure

    def prompt(self, c: int, i: int, length: int) -> List[int]:
        """Token ids of client ``c``'s ``i``-th request."""
        return prompt_ids(self.seed, c, i, length, self.vocab)

    def _send(self, c: int) -> None:
        i = self._next[c]
        if i >= len(self.table[c]):
            raise RuntimeError(
                f"client {c} ran out of schedule after {i} requests: "
                f"the table is too short for this speed")
        p_len, out = self.table[c][i]
        prompt = self.prompt(c, i, p_len)
        self._next[c] = i + 1
        t = self.clock()
        req = Request(c, i, prompt, out, self.submit(prompt, out), t)
        self._live[c] = req
        self.requests.append(req)

    def _poll(self, c: int, sending: bool) -> None:
        req = self._live[c]
        if req is None:
            return
        n = _count(req.handle, len(req.prompt))
        if n > len(req.token_t):
            now = self.clock()
            req.token_t.extend([now] * (n - len(req.token_t)))
        if req.handle.done:
            # the terminal event follows the last push: count once more
            n = _count(req.handle, len(req.prompt))
            now = self.clock()
            req.token_t.extend([now] * (n - len(req.token_t)))
            req.done_t = now
            req.error = req.handle.error
            self._live[c] = None
            if sending:
                self._send(c)

    def _loop(self) -> None:
        try:
            n = len(self.table)
            for c in range(n):
                self._send(c)
            while True:
                now = self.clock()
                if self.t0 is None:
                    firsts = [r for r in self.requests if r.index == 0]
                    if all(r.token_t or r.done_t is not None
                           for r in firsts):
                        self.t0 = now
                        if self.on_open is not None:
                            self.on_open(now)
                elif now >= self.t0 + self.seconds:
                    self.t1 = now
                    break
                for c in range(n):
                    self._poll(c, sending=True)
                time.sleep(self.poll_s)
        except BaseException as e:  # noqa: BLE001 — surfaced by wait_closed
            self._failure = e
        finally:
            self._closed.set()

    def drain_first_tokens(self, timeout: float) -> None:
        """After the close: wait (up to ``timeout``) until every request
        sent in the window has its first token or has ended; an answer
        that comes late is late, and its latency counts the wait."""
        deadline = self.clock() + timeout
        while self.clock() < deadline:
            for c in range(len(self.table)):
                self._poll(c, sending=False)
            if all(r is None or r.token_t for r in self._live):
                return
            time.sleep(self.poll_s)

    # -- the samples -----------------------------------------------------
    def in_window(self, t: Optional[float]) -> bool:
        return t is not None and self.t0 <= t <= self.t1

    def sent_in_window(self) -> List[Request]:
        return [r for r in self.requests if self.in_window(r.send_t)]

    def finished_in_window(self) -> List[Request]:
        """Sent AND finished inside the window."""
        return [r for r in self.sent_in_window() if self.in_window(r.done_t)]

    def tokens_in_window(self) -> int:
        return sum(1 for r in self.requests for t in r.token_t
                   if self.t0 <= t <= self.t1)

    def ttfts(self) -> List[float]:
        """First-token time minus send time of every request sent in the
        window; one that failed, or never got a token, counts as missing
        (infinite)."""
        out = []
        for r in self.sent_in_window():
            ok = r.token_t and r.error is None
            out.append(r.token_t[0] - r.send_t if ok else float("inf"))
        return out

    def tpots(self) -> List[float]:
        """(last token - first token) / (tokens - 1) of every request
        sent and finished in the window."""
        return [(r.token_t[-1] - r.token_t[0]) / (len(r.token_t) - 1)
                for r in self.finished_in_window()
                if r.error is None and len(r.token_t) > 1]


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile of ALL the values (an infinite value, a
    missing answer, stays infinite if it falls at the rank)."""
    if not values:
        raise ValueError("no sample to take a percentile of")
    v = sorted(values)
    rank = max(1, int(np.ceil(q / 100.0 * len(v))))
    return float(v[rank - 1])
