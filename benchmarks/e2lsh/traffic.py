"""One general load generator, driven by a traffic mix's parameters.

* ``"loop": "closed"`` -- ``clients`` callers each send a request of ``rows``
  pool queries and send the next when the answer is in hand. Latency runs
  from submission to answer.
* ``"loop": "open"`` -- requests of ``rows`` queries arrive on a schedule
  fixed before the run, whether or not earlier ones were answered: the
  warm-up and the window each hold ``round(rate_qps * seconds / rows)``
  arrivals at sorted uniform times (a Poisson process given its count, so
  every seed offers the same amount of work). Latency runs from the moment
  a request was due to the moment its answer was in hand, so a stalled
  generator or queue shows; the generator's lateness is reported.

Requests take their queries from the pool in one seeded order, without
repeats until the pool is used up, so every seed offers the same queries in
another order and a query's blocks are not read again just because the
query came back. The answers of the requests in the window are kept for
the comparison.
"""
from __future__ import annotations

import dataclasses
import queue as queue_mod
import threading
import time

import numpy as np

ANSWER_FIELDS = ("ids", "dists", "found", "radii_searched", "nio_table",
                 "nio_blocks", "cands_checked")
LATE_S = 60.0          # how long past the window an answer is waited for


@dataclasses.dataclass
class Request:
    pool_ids: np.ndarray
    due: float                    # perf_counter when due (closed: submitted)
    submitted: float = 0.0
    done: float = float("nan")
    answer: dict = None
    error: str = ""


def percentile(values, p: float) -> float:
    """Nearest rank at or above ``p``: no interpolation, so a failed
    request's infinite latency never turns a percentile into nan."""
    return float(np.percentile(np.asarray(values, np.float64), p,
                               method="higher"))


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def _answer(res) -> dict:
    return {f: np.asarray(getattr(res, f)) for f in ANSWER_FIELDS}


def _serve(q, pool, req: Request, timeout: float) -> None:
    try:
        ticket = q.submit(pool[req.pool_ids])
        req.answer = _answer(ticket.result(timeout=timeout))
    except Exception as e:                  # counted as failed
        req.error = repr(e)
    req.done = time.perf_counter()


class PoolOrder:
    """The pool's query ids in a seeded order, cycling; thread-safe."""

    def __init__(self, seed: int, pool_size: int):
        self._rng = _rng(seed, 3)
        self._size = pool_size
        self._ids = np.zeros(0, np.int64)
        self._lock = threading.Lock()

    def take(self, rows: int) -> np.ndarray:
        with self._lock:
            while self._ids.size < rows:
                self._ids = np.concatenate(
                    [self._ids, self._rng.permutation(self._size)])
            out, self._ids = self._ids[:rows], self._ids[rows:]
        return out


def open_schedule(mix: dict, seed: int, warmup_s: float, seconds: float,
                  pool_size: int) -> list:
    """[(offset_s, pool_ids)]: the warm-up's arrivals, then the window's."""
    rng = _rng(seed, 2)
    order = PoolOrder(seed, pool_size)
    out = []
    for lo, span in ((0.0, warmup_s), (warmup_s, seconds)):
        n = int(round(mix["rate_qps"] * span / mix["rows"]))
        times = np.sort(rng.uniform(lo, lo + span, size=n))
        out += [(float(t), order.take(mix["rows"])) for t in times]
    return out


class LoadGen:
    """Runs a mix against a started ``BatchQueue``. ``t0`` is the start of
    the warm-up; the window is ``[t0 + warmup_s, t0 + warmup_s + seconds]``
    on ``time.perf_counter``."""

    def __init__(self, q, pool: np.ndarray, mix: dict, seed: int,
                 warmup_s: float, seconds: float):
        self.q, self.pool, self.mix = q, pool, mix
        self.seed, self.warmup_s, self.seconds = seed, warmup_s, seconds
        self.requests: list = []
        self._lock = threading.Lock()
        self._threads: list = []
        self.t0 = self.w0 = self.w1 = 0.0
        self.lateness: list = []
        self._order = PoolOrder(seed, len(pool))

    def start(self) -> None:
        self.t0 = time.perf_counter()
        self.w0 = self.t0 + self.warmup_s
        self.w1 = self.w0 + self.seconds
        loop = self.mix["loop"]
        if loop == "closed":
            for _ in range(int(self.mix["clients"])):
                self._spawn(self._client)
        elif loop == "open":
            pending: queue_mod.Queue = queue_mod.Queue()
            self._spawn(self._generator, pending)
            self._spawn(self._waiter, pending)
        else:
            raise ValueError(f"unknown loop {loop!r}")

    def join(self) -> None:
        for t in self._threads:
            t.join()

    def _spawn(self, fn, *args) -> None:
        t = threading.Thread(target=fn, args=args, daemon=True,
                             name=f"e2lsh-bench-{fn.__name__}")
        t.start()
        self._threads.append(t)

    def _client(self) -> None:
        while True:
            now = time.perf_counter()
            if now >= self.w1:
                return
            req = Request(pool_ids=self._order.take(self.mix["rows"]),
                          due=now, submitted=now)
            with self._lock:
                self.requests.append(req)
            _serve(self.q, self.pool, req, self.seconds + LATE_S)

    def _generator(self, pending) -> None:
        sched = open_schedule(self.mix, self.seed, self.warmup_s,
                              self.seconds, len(self.pool))
        for off, ids in sched:
            due = self.t0 + off
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            req = Request(pool_ids=ids, due=due)
            req.submitted = time.perf_counter()
            self.lateness.append(req.submitted - due)
            try:
                ticket = self.q.submit(self.pool[ids])
            except Exception as e:
                req.error, req.done = repr(e), time.perf_counter()
                ticket = None
            with self._lock:
                self.requests.append(req)
            pending.put((req, ticket))
        pending.put(None)

    def _waiter(self, pending) -> None:
        while True:
            item = pending.get()
            if item is None:
                return
            req, ticket = item
            if ticket is None:
                continue
            left = max(0.0, self.w1 + LATE_S - time.perf_counter())
            try:
                req.answer = _answer(ticket.result(timeout=left))
            except Exception as e:
                req.error = repr(e)
            req.done = time.perf_counter()

    # -- what the window holds -----------------------------------------------
    def window_requests(self) -> list:
        """Closed loop: requests submitted in the window. Open loop: requests
        due in the window (every one of them is waited for)."""
        return [r for r in self.requests if self.w0 <= r.due < self.w1]

    def rows_done_in(self, lo: float, hi: float) -> int:
        return sum(len(r.pool_ids) for r in self.requests
                   if r.answer is not None and lo <= r.done <= hi)

    def answered_in(self, lo: float, hi: float) -> list:
        return [r for r in self.requests
                if r.answer is not None and lo <= r.done <= hi]
