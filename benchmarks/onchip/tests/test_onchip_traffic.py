"""The traffic generator and the end-to-end metric arithmetic."""
import numpy as np
import pytest

import _paths  # noqa: F401
import traffic
from repro.configs.base import SVQConfig

MIX = {"rate_per_s": 200.0, "tasks": [0, 1, 2], "item_zipf_a": 1.1}
CFG = SVQConfig(n_items=5000, n_users=700, user_hist_len=7)


def test_same_seed_same_requests():
    a = traffic.make_requests(MIX, CFG, 2 ** 33 + 7, 3.0)
    b = traffic.make_requests(MIX, CFG, 2 ** 33 + 7, 3.0)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_seeds_change_order_not_amount():
    a = traffic.make_requests(MIX, CFG, 1, 3.0)
    b = traffic.make_requests(MIX, CFG, 2, 3.0)
    assert a.due.size == b.due.size == 600
    assert not np.array_equal(a.user_id, b.user_id)
    for r in (a, b):
        assert np.all(np.diff(r.due) >= 0) and r.due.max() < 3.0
        assert r.hist.shape == (600, 7) and r.hist.max() < 5000
        assert set(np.unique(r.task)) <= {0, 1, 2}
        assert r.user_id.max() < 700


def test_zipf_items_are_skewed():
    rng = np.random.default_rng(0)
    ids = traffic.zipf_items(rng, 1000, 1.1, 20000)
    counts = np.sort(np.bincount(ids, minlength=1000))[::-1]
    # Zipf(1.1) over 1000 items: the top item takes ~13% of draws
    assert 0.08 < counts[0] / ids.size < 0.2
    assert counts[:10].sum() > 0.3 * ids.size


def test_percentile_is_exact_not_bucketed():
    v = np.arange(1, 101, dtype=float)
    assert traffic.percentile(v, 95) == np.percentile(v, 95) == 95.05
    assert traffic.percentile(v, 50) == 50.5


class FakeServer:
    """A FIFO server on a fake clock: each request takes ``cost`` s,
    ``stall`` s more for request ``stall_at``."""

    def __init__(self, reqs, cost, stall_at=None, stall=0.0):
        self.now = 0.0
        self.free_at = 0.0
        self.reqs = reqs
        self.cost, self.stall_at, self.stall = cost, stall_at, stall
        self.done = traffic.Completions(reqs.due.size)

    def clock(self):
        return self.now

    def sleep(self, s):
        self.now += s

    def submit(self, i):
        start = max(self.free_at, self.now)
        self.free_at = start + self.cost + (self.stall if i == self.stall_at
                                            else 0.0)
        self.done.mark(np.array([i]), self.free_at)
        return None


def _p95(stall_at=None, stall=0.0):
    reqs = traffic.make_requests({**MIX, "tasks": [0]}, CFG, 5, 4.0)
    srv = FakeServer(reqs, cost=0.001, stall_at=stall_at, stall=stall)
    w = traffic.drive(srv.submit, reqs, 4.0, clock=srv.clock,
                      sleep=srv.sleep)
    lat = traffic.latencies(reqs, w, srv.done)
    return traffic.percentile(lat, 95), lat


def test_a_stall_raises_p95_for_the_requests_behind_it():
    base, lat0 = _p95()
    stalled, lat1 = _p95(stall_at=100, stall=0.5)
    # timed from the due time, the stall delays every request queued
    # behind it, not only the stalled one
    assert base < 0.01
    assert (lat1 > lat0 + 0.1).sum() > 50
    assert stalled > 10 * base


def test_open_loop_keeps_its_schedule_when_the_server_is_slow():
    reqs = traffic.make_requests({**MIX, "tasks": [0]}, CFG, 6, 2.0)
    srv = FakeServer(reqs, cost=0.05)   # far below the offered rate
    w = traffic.drive(srv.submit, reqs, 2.0, clock=srv.clock,
                      sleep=srv.sleep)
    assert np.all(w.lateness_s < 1e-9)
    lat = traffic.latencies(reqs, w, srv.done)
    assert lat[-1] > 10.0               # the queue grew


def test_unanswered_requests_count_as_infinite():
    reqs = traffic.make_requests({**MIX, "tasks": [0]}, CFG, 7, 1.0)
    done = traffic.Completions(reqs.due.size)
    done.mark(np.arange(10), 0.5)
    w = traffic.Window(t0=0.0, seconds=1.0, futures=[],
                       lateness_s=np.zeros(reqs.due.size))
    lat = traffic.latencies(reqs, w, done)
    assert np.isfinite(lat[:10]).all() and np.isinf(lat[10:]).all()


BURSTY = {**MIX, "bursts": {"period_s": 10.0, "on_s": 1.0, "rate_x": 4.0}}


def test_bursts_keep_the_mean_rate_and_the_seed_sets_only_the_order():
    a = traffic.make_requests(BURSTY, CFG, 2 ** 33 + 9, 20.0)
    b = traffic.make_requests(BURSTY, CFG, 2 ** 33 + 9, 20.0)
    c = traffic.make_requests(BURSTY, CFG, 11, 20.0)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert a.due.size == c.due.size == 4000     # 200/s over 20 s
    assert np.all(np.diff(a.due) >= 0) and a.due.max() < 20.0


def test_bursts_arrive_rate_x_times_as_fast():
    r = traffic.make_requests(BURSTY, CFG, 12, 20.0)
    on = (r.due % 10.0) < 1.0
    # per period: 1 s at 4 x base and 9 s at base, mean 200/s, so
    # base = 2000 / 13 per second
    base = 200.0 * 10 / 13
    assert abs(on.sum() - 2 * 4 * base) < 4 * np.sqrt(8 * base)
    assert abs((~on).sum() - 2 * 9 * base) < 4 * np.sqrt(18 * base)


def test_a_mix_key_the_generator_does_not_read_is_refused():
    assert traffic.check_mix({**BURSTY, "kind": "serve_open_loop"})
    for bad in ({**MIX, "rate": 3}, {**MIX, "bursts": {"period_s": 1.0}}):
        with pytest.raises(KeyError):
            traffic.check_mix(bad)


def test_users_served_count_the_flush_in_flight_by_its_elapsed_share():
    f = traffic.Flushes()
    f.add(0.0, 1.0, 10)
    f.add(1.0, 3.0, 20)
    assert f.served_by(0.5) == 5.0
    assert f.served_by(1.0) == 10.0
    assert f.served_by(2.0) == 20.0
    assert f.served_by(9.0) == 30.0
    # a close that moves by a little moves the count by a little, not by
    # the 20 rows of a whole flush
    assert f.served_by(3.0 - 1e-9) == pytest.approx(30.0, abs=1e-6)
