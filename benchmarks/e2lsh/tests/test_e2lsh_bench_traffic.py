"""The load generator: open-loop schedule, latency from the due time,
generator lateness, and the closed loop's client count."""
import pathlib
import sys
import threading
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from traffic import LoadGen, open_schedule  # noqa: E402


class _Result:
    def __init__(self, rows):
        z = np.zeros((rows,), np.int32)
        self.ids = np.zeros((rows, 10), np.int32)
        self.dists = np.zeros((rows, 10), np.float32)
        self.found = z.astype(bool)
        self.radii_searched = self.nio_table = self.nio_blocks = z
        self.cands_checked = z


class _Ticket:
    def __init__(self, rows, delay):
        self.rows, self.at = rows, time.perf_counter() + delay

    def result(self, timeout=None):
        time.sleep(max(0.0, self.at - time.perf_counter()))
        return _Result(self.rows)


class FakeQueue:
    """Answers each request ``delay`` seconds after submission."""

    def __init__(self, delay=0.002):
        self.delay, self.submits = delay, []
        self.threads = set()

    def submit(self, q):
        self.submits.append(len(q))
        self.threads.add(threading.get_ident())
        return _Ticket(len(q), self.delay)


MIX_OPEN = dict(loop="open", rate_qps=200.0, rows=1)


def test_open_schedule_fixed_count_sorted_and_seeded():
    a = open_schedule(MIX_OPEN, 2**40 + 3, 0.5, 2.0, 100)
    b = open_schedule(MIX_OPEN, 2**40 + 3, 0.5, 2.0, 100)
    c = open_schedule(MIX_OPEN, 7, 0.5, 2.0, 100)
    assert len(a) == len(c) == 100 + 400      # same work for every seed
    assert [t for t, _ in a] == [t for t, _ in b]
    assert [t for t, _ in a] != [t for t, _ in c]
    times = np.asarray([t for t, _ in a])
    assert np.all(np.diff(times[:100]) >= 0) and np.all(times[:100] < 0.5)
    assert np.all(np.diff(times[100:]) >= 0) and np.all(times[100:] >= 0.5)
    assert all(ids.shape == (1,) and 0 <= ids[0] < 100 for _, ids in a)
    # the pool in a seeded order: each query once before any comes back
    ids = np.concatenate([i for _, i in a])
    assert sorted(ids[:100]) == list(range(100))
    assert sorted(ids[100:200]) == list(range(100))


def test_open_loop_latency_runs_from_the_due_time():
    q = FakeQueue(delay=0.01)
    pool = np.zeros((50, 4), np.float32)
    drv = LoadGen(q, pool, MIX_OPEN, seed=3, warmup_s=0.2, seconds=0.5)
    drv.start()
    drv.join()
    win = drv.window_requests()
    assert len(win) == 100
    assert all(r.answer is not None for r in win)
    for r in win:
        assert drv.w0 <= r.due < drv.w1
        assert r.done - r.due >= 0.01 - 1e-4       # includes the service
        assert r.submitted >= r.due
    assert len(drv.lateness) == 40 + 100          # lateness of every send
    assert min(drv.lateness) >= 0.0


def test_closed_loop_runs_one_stream_per_client():
    q = FakeQueue(delay=0.005)
    pool = np.zeros((50, 4), np.float32)
    mix = dict(loop="closed", clients=3, rows=8)
    drv = LoadGen(q, pool, mix, seed=5, warmup_s=0.1, seconds=0.3)
    drv.start()
    drv.join()
    assert len(q.threads) == 3
    assert set(q.submits) == {8}
    # each client has at most one request in flight: at most 3 open at once
    done = sorted((r.submitted, r.done) for r in drv.requests)
    for t, _ in done:
        assert sum(1 for s, e in done if s <= t < e) <= 3
    assert drv.rows_done_in(drv.w0, drv.w1) > 0
    assert all(drv.w0 <= r.due < drv.w1 for r in drv.window_requests())
