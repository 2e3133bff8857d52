"""Multi-scenario retrieval federation: route, fan out, merge, account.

The paper's deployment story is not one retriever but a FLEET: streaming
VQ "has been fully deployed at Douyin and Douyin Lite, replacing all
major retrievers" — which means a routing layer existed that served many
retrieval paradigms side by side per scenario, ramped traffic between
them (A/B), and attributed the final candidate set to its sources while
the replacement was argued item by item.  This module is that layer:

  ``Scenario``      a named serving surface (task / product page) with
                    its ordered backend fan-out and an optional A/B arm
  ``ABSplit``       deterministic hash-based traffic split — the same
                    request id always lands on the same arm (crc32 of
                    ``salt|request_id``; no RNG, replayable offline)
  ``federated_merge``   k-way merge of per-backend ``Candidates`` into
                    one deduplicated top-k, reusing the Alg. 1 heap
                    (``core.merge_sort.merge_sort_serve_np``) with
                    cluster scores pinned to zero: each backend's list
                    is one "cluster", chunk=1.  Scores in the merged
                    output are GATHERED from the input arrays by merge
                    position, so every (id, score) pair survives the
                    merge bit-exactly; the heap's f64 sum is only the
                    ordering key.
  ``FederationRouter``  the serve front door: scenario resolution,
                    single-backend short-circuit (bit-identical to
                    calling the backend directly — the contract
                    tests/test_federation.py pins), per-backend spans,
                    windowed contribution accounting
                    (``obs.quality.ContributionEstimator`` over backend
                    buckets) and the ``svq_fed_*`` metric surface.

Contribution accounting answers the replacement question: of the final
top-k actually served, what fraction did each retriever supply?  A
backend whose contribution decays to ~0 under merge is dominated —
exactly the evidence the paper's full-replacement claim rests on.
"""
from __future__ import annotations

import threading
import time
import zlib
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from repro.core import merge_sort
from repro.obs import quality as quality_lib
from repro.obs import registry as registry_lib
from repro.obs import slo as slo_lib
from repro.obs import trace as trace_lib
from repro.retrieval.api import (INVALID_ID, INVALID_SOURCE, Candidates)
from repro.retrieval.registry import RetrieverRegistry
from repro.serving import batcher as batcher_lib

NEG = merge_sort.NEG


class ABSplit(NamedTuple):
    """Deterministic two-arm traffic split appended to a scenario.

    The selected arm's backend joins the scenario fan-out for that
    request (ramping a challenger INTO the merge), or — when the
    scenario lists no other backends — serves it alone (classic A/B).
    """
    arm_a: str
    arm_b: str
    fraction_b: float = 0.5
    salt: str = ""


def assign_arm(split: ABSplit, request_id: int) -> str:
    """Hash-based arm assignment: stable per (salt, request_id).

    crc32 over the decimal request id keyed by the salt, mapped to
    [0, 1); below ``fraction_b`` -> arm B.  Changing the salt reshuffles
    the population (a fresh experiment) without touching per-request
    determinism.
    """
    h = zlib.crc32(f"{split.salt}|{request_id}".encode())
    return split.arm_b if h / 2 ** 32 < split.fraction_b else split.arm_a


class Scenario(NamedTuple):
    """One serving surface: ordered backend fan-out + optional A/B."""
    name: str
    backends: Tuple[str, ...]
    split: Optional[ABSplit] = None
    k: Optional[int] = None             # scenario default top-k


def _source_offsets(cands: Sequence[Candidates]
                    ) -> Tuple[Tuple[str, ...], List[int]]:
    """Chain input source-name tables into one, with per-input offsets
    (inputs are usually single-source, but a merged Candidates can be
    re-merged and its labels survive)."""
    names: List[str] = []
    offsets: List[int] = []
    for c in cands:
        offsets.append(len(names))
        names.extend(c.source_names)
    return tuple(names), offsets


def federated_merge(cands: Sequence[Candidates], k: int) -> Candidates:
    """K-way merge of per-backend candidate lists into one top-k.

    Per row, each backend's (already score-descending) valid prefix is
    one merge lane of the Alg. 1 heap (``merge_sort_serve_np`` with
    cluster scores = 0, chunk = 1); the merged order is walked once,
    dropping ids already taken (keep-first dedup: the highest-scoring
    occurrence wins, ties by fan-out position).  Output rows carry at
    most ``k`` entries, (INVALID_ID, NEG, invalid) trailing; ids and
    scores are GATHERED from the inputs by merge position, bit-exact.
    """
    if not cands:
        raise ValueError("federated_merge needs at least one input")
    b = cands[0].batch
    for c in cands:
        if c.batch != b:
            raise ValueError("mismatched batch sizes in federated merge")
    names, offsets = _source_offsets(cands)
    n_src = len(cands)
    width = max(c.k for c in cands)
    ids = np.full((b, k), INVALID_ID, np.int64)
    scores = np.full((b, k), NEG, np.float64)
    valid = np.zeros((b, k), bool)
    sources = np.full((b, k), INVALID_SOURCE, np.int16)
    zeros = np.zeros(n_src, np.float64)
    lane = np.full((n_src, width), NEG, np.float64)
    for row in range(b):
        lengths = np.zeros(n_src, np.int64)
        lane[:] = NEG
        for j, c in enumerate(cands):
            n = int(np.asarray(c.valid[row], bool).sum())
            lengths[j] = n
            lane[j, :n] = np.asarray(c.scores[row, :n], np.float64)
        total = int(lengths.sum())
        if total == 0:
            continue
        pos, _ = merge_sort.merge_sort_serve_np(
            zeros, lane, lengths, chunk=1, target=total)
        taken = set()
        col = 0
        for p in pos:
            src, slot = int(p) // width, int(p) % width
            item = int(cands[src].ids[row, slot])
            if item in taken:
                continue
            taken.add(item)
            ids[row, col] = cands[src].ids[row, slot]
            scores[row, col] = cands[src].scores[row, slot]
            sources[row, col] = (offsets[src]
                                 + int(cands[src].sources[row, slot]))
            valid[row, col] = True
            col += 1
            if col == k:
                break
    return Candidates(ids=ids, scores=scores, valid=valid,
                      sources=sources, source_names=names)


class FederationRouter:
    """Scenario-routing serve front door over a ``RetrieverRegistry``.

    Construction freezes the ordered union of every backend any
    scenario (or A/B arm) can reach — the contribution bucket space —
    so contribution ratios stay comparable as traffic shifts between
    scenarios.  Backends are still constructed lazily: a backend no
    request routes to is never built.
    """

    def __init__(self, registry: RetrieverRegistry,
                 scenarios: Sequence[Scenario], default_scenario: str,
                 task_scenarios: Optional[Dict[int, str]] = None,
                 tracer: Optional[trace_lib.Tracer] = None,
                 default_k: int = 64,
                 contribution_window: int = 512):
        self.registry = registry
        self.scenarios = {s.name: s for s in scenarios}
        if default_scenario not in self.scenarios:
            raise KeyError(f"default scenario {default_scenario!r} "
                           "not configured")
        self.default_scenario = default_scenario
        self.task_scenarios = dict(task_scenarios or {})
        for t, name in self.task_scenarios.items():
            if name not in self.scenarios:
                raise KeyError(f"task {t} routes to unknown scenario "
                               f"{name!r}")
        self.tracer = tracer
        self.default_k = default_k
        # frozen ordered union of reachable backends (fan-out order,
        # then arms), first appearance wins
        seen: Dict[str, int] = {}
        for s in scenarios:
            arms = () if s.split is None else (s.split.arm_a,
                                               s.split.arm_b)
            for name in (*s.backends, *arms):
                seen.setdefault(name, len(seen))
        self.backend_names: Tuple[str, ...] = tuple(seen)
        self._backend_index = seen
        self.contribution = quality_lib.ContributionEstimator(
            window=contribution_window)
        self._lock = threading.Lock()
        self._scenario_requests: Dict[str, int] = {}
        self._arm_requests: Dict[Tuple[str, str], int] = {}
        self._backend_requests: Dict[str, int] = {}
        self._backend_hist = {
            name: registry_lib.LatencyHistogram()
            for name in self.backend_names}
        self._merge_hist = registry_lib.LatencyHistogram()
        self.n_requests = 0
        self.n_merges = 0

    # -- routing -----------------------------------------------------------
    @staticmethod
    def request_id_of(batch: Dict[str, np.ndarray]) -> int:
        """Content-addressed fallback request id: crc32 of the batch's
        user ids — deterministic for replay, unique enough for A/B."""
        uid = np.ascontiguousarray(np.asarray(batch["user_id"], np.int64))
        return zlib.crc32(uid.tobytes())

    def resolve(self, scenario: Optional[str] = None,
                request_id: Optional[int] = None,
                task: int = 0) -> Tuple[Scenario, Tuple[str, ...],
                                        Optional[str]]:
        """(scenario, fan-out backend names, A/B arm) for one request.

        Resolution order: explicit ``scenario`` arg -> task routing
        table -> default scenario.  The A/B-selected arm is APPENDED to
        the scenario's fan-out (deduplicated, order-preserving), so an
        arm already in the fan-out changes nothing and a challenger arm
        joins the merge for its share of traffic.
        """
        name = scenario or self.task_scenarios.get(task,
                                                   self.default_scenario)
        sc = self.scenarios.get(name)
        if sc is None:
            raise KeyError(f"unknown scenario {name!r}; configured: "
                           f"{sorted(self.scenarios)}")
        backends = list(sc.backends)
        arm = None
        if sc.split is not None:
            rid = 0 if request_id is None else int(request_id)
            arm = assign_arm(sc.split, rid)
            if arm not in backends:
                backends.append(arm)
        return sc, tuple(backends), arm

    # -- serving -----------------------------------------------------------
    def serve(self, batch: Dict[str, np.ndarray],
              scenario: Optional[str] = None,
              request_id: Optional[int] = None, task: int = 0,
              k: Optional[int] = None, n_valid: Optional[int] = None,
              span_sink: Optional[List[trace_lib.Span]] = None
              ) -> Candidates:
        """Route one batch through its scenario's backend fan-out.

        Single-backend scenarios SHORT-CIRCUIT: the backend's
        ``Candidates`` is returned verbatim (bit-identical to calling
        it directly — no merge, no normalization).  Multi-backend
        fan-outs serve each backend in fan-out order (per-backend
        ``fed_<name>`` spans into ``span_sink``) and k-way merge.
        Contribution counts fold the leading ``n_valid`` rows of the
        result into the windowed estimator either way.
        """
        if request_id is None:
            request_id = self.request_id_of(batch)
        sc, backends, arm = self.resolve(scenario, request_id, task)
        k = k or sc.k or self.default_k
        with self._lock:
            self.n_requests += 1
            self._scenario_requests[sc.name] = \
                self._scenario_requests.get(sc.name, 0) + 1
            if arm is not None:
                key = (sc.name, arm)
                self._arm_requests[key] = self._arm_requests.get(key, 0) + 1
        results: List[Candidates] = []
        for name in backends:
            backend = self.registry.get(name)
            t0 = time.monotonic()
            # span_sink is per-backend only on the fan-out path; the
            # short-circuit backend receives the router's sink directly
            # so its own spans (the SVQ service's serve.*) keep flowing
            inner_sink = span_sink if len(backends) == 1 else None
            cand = backend.serve(batch, k, task=task, n_valid=n_valid,
                                 span_sink=inner_sink)
            dt = time.monotonic() - t0
            self._backend_hist[name].record(dt)
            with self._lock:
                self._backend_requests[name] = \
                    self._backend_requests.get(name, 0) + 1
            if span_sink is not None and len(backends) > 1:
                t1 = t0 + dt
                span_sink.append(trace_lib.make_span(
                    f"fed_{name}", t0, t1, backend=name,
                    scenario=sc.name))
            results.append(cand)
        if len(results) == 1:
            out = results[0]
        else:
            t0 = time.monotonic()
            out = federated_merge(results, k)
            dt = time.monotonic() - t0
            self._merge_hist.record(dt)
            with self._lock:
                self.n_merges += 1
            if span_sink is not None:
                span_sink.append(trace_lib.make_span(
                    "fed_merge", t0, t0 + dt, n_backends=len(results),
                    scenario=sc.name))
        self._account(out, n_valid)
        return out

    def _account(self, out: Candidates, n_valid: Optional[int]) -> None:
        """Fold one result's per-source counts into the frozen global
        backend bucket space."""
        local = out.contribution(n_valid)
        counts = np.zeros(len(self.backend_names), np.int64)
        for j, name in enumerate(out.source_names):
            idx = self._backend_index.get(name)
            if idx is not None:
                counts[idx] += local[j]
        self.contribution.update(counts)

    # -- batcher facade ----------------------------------------------------
    def serve_batch(self, batch: Dict[str, np.ndarray], task: int = 0,
                    n_valid: Optional[int] = None,
                    span_sink: Optional[List[trace_lib.Span]] = None
                    ) -> Dict[str, np.ndarray]:
        """Dict-of-arrays facade over ``serve`` (MicroBatcher protocol:
        every value has a leading batch axis, so the batcher can split
        responses per caller)."""
        out = self.serve(batch, task=task, n_valid=n_valid,
                         span_sink=span_sink)
        return dict(item_ids=out.ids, scores=out.scores,
                    valid=out.valid, sources=out.sources)

    def make_batcher(self, max_batch: int = 64,
                     max_delay_s: float = 0.002,
                     buckets=None) -> batcher_lib.MicroBatcher:
        """Micro-batching front door through the router (per-flush
        scenario resolution: the batcher's task IS the routing key)."""
        return batcher_lib.MicroBatcher(
            self.serve_batch, max_batch=max_batch,
            max_delay_s=max_delay_s, buckets=buckets,
            tracer=self.tracer)

    # -- observability -----------------------------------------------------
    def contribution_snapshot(self) -> Dict[str, float]:
        """Per-backend windowed contribution ratios + evenness stats."""
        r = self.contribution.ratios()
        snap = self.contribution.snapshot()
        out = {f"ratio_{name}": (float(r[i]) if r.size else 0.0)
               for i, name in enumerate(self.backend_names)}
        out["entropy_ratio"] = snap["entropy_ratio"]
        out["max_ratio"] = snap["max_ratio"]
        return out

    def register_metrics(self, registry: Optional[
            registry_lib.MetricRegistry] = None,
            namespace: str = "svq") -> registry_lib.MetricRegistry:
        """Export the ``{namespace}_fed_*`` surface (+ the registry's
        backend lifecycle series) into a MetricRegistry."""
        reg = registry if registry is not None \
            else registry_lib.MetricRegistry()
        ns = f"{namespace}_fed"

        def collect() -> List[registry_lib.Family]:
            with self._lock:
                scen = sorted(self._scenario_requests.items())
                arms = sorted(self._arm_requests.items())
                bks = sorted(self._backend_requests.items())
                n_req, n_merge = self.n_requests, self.n_merges
            r = self.contribution.ratios()
            snap = self.contribution.snapshot()
            contrib = [({"backend": name},
                        float(r[i]) if r.size else 0.0)
                       for i, name in enumerate(self.backend_names)]
            return [
                registry_lib.Family(
                    f"{ns}_requests_total", "counter",
                    "federated serve calls", [({}, float(n_req))]),
                registry_lib.Family(
                    f"{ns}_scenario_requests_total", "counter",
                    "serve calls per scenario",
                    [({"scenario": s}, float(n)) for s, n in scen]),
                registry_lib.Family(
                    f"{ns}_arm_requests_total", "counter",
                    "A/B arm assignments per scenario",
                    [({"scenario": s, "arm": a}, float(n))
                     for (s, a), n in arms]),
                registry_lib.Family(
                    f"{ns}_backend_requests_total", "counter",
                    "per-backend fan-out serve calls",
                    [({"backend": b}, float(n)) for b, n in bks]),
                registry_lib.Family(
                    f"{ns}_backend_latency_seconds", "histogram",
                    "per-backend serve wall time inside the fan-out",
                    [({"backend": name}, self._backend_hist[name]
                      .snapshot()) for name in self.backend_names]),
                registry_lib.Family(
                    f"{ns}_merge_seconds", "histogram",
                    "k-way federated merge wall time",
                    [({}, self._merge_hist.snapshot())]),
                registry_lib.Family(
                    f"{ns}_merges_total", "counter",
                    "multi-backend merges performed",
                    [({}, float(n_merge))]),
                registry_lib.Family(
                    f"{ns}_contribution", "gauge",
                    "windowed share of served candidates per backend",
                    contrib),
                registry_lib.Family(
                    f"{ns}_contribution_entropy_ratio", "gauge",
                    "contribution evenness (1 = even, 0 = one backend)",
                    [({}, snap["entropy_ratio"])]),
            ]

        reg.register_collector(collect)
        self.registry.register_metrics(reg, namespace=ns)
        return reg


def default_federation_slos(namespace: str = "svq",
                            latency_p99_s: float = 0.25,
                            entropy_floor: float = 0.05
                            ) -> List[slo_lib.SLOSpec]:
    """Starter objectives for the federation surface.

    The entropy floor fires when the merge collapses onto a single
    backend — either the challenger contributes nothing (kill the arm)
    or it dominates completely (finish the migration); both are ship
    decisions, which is why it is an SLO and not just a dashboard line.
    """
    ns = f"{namespace}_fed"
    return [
        slo_lib.SLOSpec(
            name="fed_merge_latency",
            metric=f"{ns}_merge_seconds", objective=latency_p99_s,
            op="le", stat="p99",
            description="k-way federated merge stays off the tail"),
        slo_lib.SLOSpec(
            name="fed_contribution_evenness",
            metric=f"{ns}_contribution_entropy_ratio",
            objective=entropy_floor, op="ge", stat="value",
            description="merged top-k draws from more than one backend"),
    ]
