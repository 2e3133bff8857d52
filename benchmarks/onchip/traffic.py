"""The one traffic generator: reads a mix's parameters, makes its requests
from the seed, and drives them open loop.

A mix is a JSON file under ``traffic/`` (see ``traffic/*.json``):

    kind          "serve_open_loop" (the one kind ``run.py`` drives)
    why           what the mix stands for, one line
    rate_per_s    offered load, requests per second, over the window
    bursts        optional {"period_s", "on_s", "rate_x"}: in every
                  period the first ``on_s`` seconds arrive ``rate_x``
                  times as fast as the rest, the mean staying
                  ``rate_per_s``
    tasks         user-tower tasks, drawn uniformly per request
    item_zipf_a   exponent of the Zipf item popularity the history
                  ids are drawn from (1.1, as ``data/streaming.py``)
    max_batch, max_delay_s
                  the micro-batcher's settings
    buckets       optional: the batcher's shapes; without it the
                  program's own default
    check_requests
                  how many completed requests ``correct`` compares

Every seed gets the same number of requests, the expected count of the
rate profile over the window, due at sorted times drawn from that
profile (a Poisson process given its count), so seeds change which users
and items are asked for and when, not how much work there is.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, NamedTuple

import numpy as np


MIX_KEYS = {"kind", "why", "rate_per_s", "bursts", "tasks", "item_zipf_a",
            "max_batch", "max_delay_s", "buckets", "check_requests"}
BURST_KEYS = {"period_s", "on_s", "rate_x"}


def check_mix(mix: Dict) -> Dict:
    """The mix itself, or an error naming a key this generator does not
    read (a misspelt key would otherwise be ignored)."""
    unknown = set(mix) - MIX_KEYS
    if "bursts" in mix:
        unknown |= set(mix["bursts"]) ^ BURST_KEYS
    if unknown:
        raise KeyError(f"traffic mix keys not understood: {sorted(unknown)}")
    return mix


class Requests(NamedTuple):
    due: np.ndarray          # (R,) seconds after the window opens
    user_id: np.ndarray      # (R,) int32
    hist: np.ndarray         # (R, H) int32
    task: np.ndarray         # (R,) int64


def zipf_items(rng: np.random.Generator, n_items: int, a: float,
               shape) -> np.ndarray:
    """Item ids with Zipf(a) popularity over a seeded permutation of the
    corpus ids (inverse-CDF draws)."""
    ranks = np.arange(1, n_items + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** (-a))
    cdf /= cdf[-1]
    pick = np.searchsorted(cdf, rng.random(shape), side="right")
    perm = rng.permutation(n_items)
    return perm[np.minimum(pick, n_items - 1)].astype(np.int32)


def expected_arrivals(mix: Dict, seconds: float):
    """(t, cum): knots of the expected number of arrivals by time t, which
    is linear between knots, over [0, seconds]."""
    rate = mix["rate_per_s"]
    b = mix.get("bursts")
    if b is None:
        return (np.array([0.0, seconds]), np.array([0.0, rate * seconds]))
    period, on, x = b["period_s"], b["on_s"], b["rate_x"]
    base = rate * period / (on * x + period - on)
    starts = np.arange(0.0, seconds, period)
    t = np.unique(np.concatenate([starts, starts + on, [seconds]]))
    t = t[t <= seconds]
    mid = 0.5 * (t[:-1] + t[1:])
    lam = np.where(mid % period < on, base * x, base)
    return t, np.concatenate([[0.0], np.cumsum(lam * np.diff(t))])


def make_requests(mix: Dict, cfg, seed: int, seconds: float) -> Requests:
    rng = np.random.default_rng([seed, 0x5e7e])
    t, cum = expected_arrivals(mix, seconds)
    n = int(round(cum[-1]))
    due = np.interp(np.sort(rng.random(n)) * cum[-1], cum, t)
    user_id = rng.integers(0, cfg.n_users, n).astype(np.int32)
    hist = zipf_items(rng, cfg.n_items, mix["item_zipf_a"],
                      (n, cfg.user_hist_len))
    tasks = np.asarray(mix["tasks"], np.int64)
    task = tasks[rng.integers(0, tasks.size, n)]
    return Requests(due=due, user_id=user_id, hist=hist, task=task)


class Completions:
    """Completion clock of each request, set by the serve wrapper when
    the flush holding the request returns."""

    def __init__(self, n: int):
        self.t_done = np.full(n, np.nan)
        self.lock = threading.Lock()

    def mark(self, req: np.ndarray, t: float) -> None:
        with self.lock:
            self.t_done[req] = t


class Flushes:
    """Start, end and real rows of each serve call, for the users served
    in a window."""

    def __init__(self):
        self.spans: List[tuple] = []
        self.lock = threading.Lock()

    def add(self, t0: float, t1: float, rows: int) -> None:
        with self.lock:
            self.spans.append((t0, t1, rows))

    def served_by(self, t: float) -> float:
        """Users served by time ``t``: the rows of every call that returned
        by then, and those of a call still running at ``t`` in proportion
        to its time elapsed, so a window's count does not step by a whole
        flush with where its close falls."""
        total = 0.0
        with self.lock:
            for t0, t1, rows in self.spans:
                if t1 <= t:
                    total += rows
                elif t0 < t:
                    total += rows * (t - t0) / (t1 - t0)
        return total


class Window(NamedTuple):
    t0: float                # perf_counter when the window opened
    seconds: float
    futures: List            # one per request, in due order
    lateness_s: np.ndarray   # submit time minus due time, per request


def drive(submit, reqs: Requests, seconds: float, clock=time.perf_counter,
          sleep=time.sleep) -> Window:
    """Submit every request at its due time (open loop): a slow server
    never slows the arrivals.  ``submit(i) -> future``."""
    n = reqs.due.size
    futures = [None] * n
    late = np.zeros(n)
    t0 = clock()
    for i in range(n):
        due = t0 + reqs.due[i]
        wait = due - clock()
        if wait > 0:
            sleep(wait)
        late[i] = clock() - due
        futures[i] = submit(i)
    left = t0 + seconds - clock()
    if left > 0:
        sleep(left)
    return Window(t0=t0, seconds=seconds, futures=futures, lateness_s=late)


def percentile(values: np.ndarray, q: float) -> float:
    """Exact q-th percentile (0-100) by linear interpolation between the
    closest ranks (numpy's default), over every value given."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def latencies(reqs: Requests, window: Window,
              done: Completions) -> np.ndarray:
    """Completion minus due time of every request; one that never
    completed counts as +inf."""
    t = done.t_done - (window.t0 + reqs.due)
    return np.where(np.isnan(t), np.inf, t)
