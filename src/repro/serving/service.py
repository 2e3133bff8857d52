"""Retrieval service: the two-step serving pipeline of Fig. 1 / §3.4.

RetrievalService is now a thin facade over the serving subsystem
(see ``serving/__init__.py`` for the file -> paper-section map):

  - the trained retriever params + live IndexState (codebook + PS
    tables), swapped in atomically from the training side (§3.1 model
    dump cadence; assignments inside it are already real-time),
  - the ServingIndex lifecycle, double-buffered behind
    ``swap.DoubleBufferedIndex``: a background (or on-demand) rebuild
    produces the next epoch-tagged generation from the live
    AssignmentStore while the old generation keeps serving,
  - optional cluster-major sharding over a device mesh
    (``sharding.ShardedServingIndex``; pass ``n_shards`` / ``mesh``),
  - lock-exact counters + log-spaced latency histograms
    (``telemetry.ServeStats``) so p50/p95/p99 are benchmarkable,
  - an async micro-batching front door (``make_batcher``) multiplexing
    many small client requests into one jitted serve call.

serve_batch: cluster ranking (Eq. 11) -> k-way chunked merge sort
(Alg. 1) -> ranking-step model -> final ordered candidates.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.baselines import brute_force
from repro.configs.base import SVQConfig
from repro.core import assignment_store as astore
from repro.core import merge_sort
from repro.core import retriever
from repro.models.dense import mlp
from repro.obs.index_health import health_of, register_index_health
from repro.obs import quality as quality_lib
from repro.obs import registry as registry_lib
from repro.obs import sampling as sampling_lib
from repro.obs import trace as trace_lib
from repro.serving import batcher as batcher_lib
from repro.serving import deltas as deltas_lib
from repro.serving import sharding as sharding_lib
from repro.serving.swap import DoubleBufferedIndex, IndexGeneration
from repro.serving.telemetry import ServeStats


class RetrievalService:
    def __init__(self, cfg: SVQConfig, params, index_state,
                 items_per_cluster: int = 256, use_kernel: bool = False,
                 fused: bool = False,
                 n_shards: Optional[int] = None, mesh=None,
                 delta_spare: int = 0,
                 tracer: Optional[trace_lib.Tracer] = None,
                 rank_parallel: bool = False):
        self.cfg = cfg
        self.items_per_cluster = items_per_cluster
        self.use_kernel = use_kernel
        # fused=True serves through the slab-free merge+gather+rank
        # stage (bit-identical candidates; adds exact_scores in-pass)
        self.fused = fused
        self.n_shards = n_shards
        self.mesh = mesh
        # spare slots per cluster segment: the headroom incremental delta
        # publication (serving/deltas.py) appends into.  0 = dense layout,
        # every immediate apply falls back to a forced compaction rebuild.
        self.delta_spare = delta_spare
        # batch-parallel replicated ranking (sharding.py stage 4):
        # tolerance-contract opt-in, sequential/replicated stays the
        # oracle.  Only meaningful with n_shards + mesh.
        self.rank_parallel = rank_parallel
        # request tracer (obs/trace.py): a sampled request runs the same
        # jitted serve as every other and collects the host spans of its
        # flush (batcher.*, serve.*)
        self.tracer = tracer
        self.stats = ServeStats()
        self._lock = threading.Lock()
        self._params = params
        self._index_state = index_state
        self._store_capacity = index_state.store.capacity
        self._log = deltas_lib.DeltaLog()
        idx0, v0 = self._build_index()
        self._buffer = DoubleBufferedIndex(
            self._build_index, idx0,
            on_publish=self._on_publish,
            reconcile_fn=self._reconcile,
            initial_version=v0)
        self.stats.index_rebuilds += 1          # the initial build
        # single dispatch: single-device and sharded serve go through the
        # same retriever serve_kernel/rank_codebook switches
        if n_shards:
            def _serve(p, s, idx, b, task):
                return sharding_lib.sharded_serve(
                    p, s, cfg, idx, b,
                    items_per_cluster=items_per_cluster, task=task,
                    use_kernel=use_kernel, fused=fused, mesh=mesh,
                    rank_parallel=rank_parallel)
        else:
            def _serve(p, s, idx, b, task):
                return retriever.serve(
                    p, s, cfg, idx, b,
                    items_per_cluster=items_per_cluster, task=task,
                    use_kernel=use_kernel, fused=fused)
        self._serve_jit = jax.jit(_serve, static_argnames=("task",))
        # shadow-probe pipeline (obs/quality.py): attached by
        # enable_probes(); the oracle user tower is a separate tiny jit
        # so probe re-scoring never touches the serve jits

        def _user_emb(p, b, task):
            user_feat, _ = retriever.user_features(p, b["user_id"],
                                                   b["hist"])
            return jax.vmap(lambda tw: mlp(tw, user_feat))(
                p["user_towers"])[task]

        self._user_emb_jit = jax.jit(_user_emb, static_argnames=("task",))
        self.prober: Optional[quality_lib.QualityProber] = None

    def user_embedding(self, batch: Dict[str, np.ndarray],
                       task: int = 0) -> np.ndarray:
        """(B, dim) user-tower embedding for a request batch.

        The same tiny jit the shadow-probe oracle uses; this is the
        standard ``embed_fn`` the non-SVQ retrieval backends
        (``repro.retrieval.backends``) score queries with, so every
        federated backend sees the identical user representation.
        """
        with self._lock:
            params = self._params
        jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
        return np.asarray(self._user_emb_jit(params, jbatch, task=task))

    # -- index lifecycle (swap.py) -----------------------------------------
    def _build_index(self):
        """Snapshot the live store -> fresh Appendix-B layout (+shards).

        The DeltaLog version is captured under the SAME lock acquisition
        as the store snapshot, so every log entry with version <= v0 is
        already reflected in this build and every later entry is not —
        the invariant ``_reconcile`` relies on for truncation/replay.
        """
        with self._lock:
            state = self._index_state
            v0 = self._log.version
        idx = astore.build_serving_index(state.store, self.cfg.n_clusters,
                                         use_kernel=self.use_kernel,
                                         spare_per_cluster=self.delta_spare)
        if self.n_shards:
            idx = sharding_lib.shard_serving_index(
                idx, self.cfg.n_clusters, self.n_shards)
            if self.mesh is not None:
                idx = sharding_lib.place_sharded_index(idx, self.mesh)
        return idx, v0

    def _apply_to_index(self, index, batch: deltas_lib.DeltaBatch):
        if self.n_shards:
            return deltas_lib.apply_deltas_sharded(
                index, batch, self.cfg.n_clusters, self._store_capacity,
                mesh=self.mesh)
        return deltas_lib.apply_deltas(index, batch, self.cfg.n_clusters,
                                       self._store_capacity)

    def _record_freshness(self, batch: deltas_lib.DeltaBatch,
                          now: float) -> None:
        """Freshness = assignment time -> first retrievable publish."""
        n_new = int((batch.new_id >= 0).sum())
        if n_new:
            self.stats.freshness.record(max(now - batch.t_assign, 0.0),
                                        n_new)

    def _reconcile(self, build_result):
        """Fold the pending delta log into a freshly built index.

        Runs under the publish lock just before the swap.  Entries the
        build snapshot already covers (version <= v0) are truncated —
        that is the compaction step: their spare-slot edits became part
        of the dense rebuild.  Entries appended DURING the build window
        (version > v0) are replayed onto the new index so publication
        never loses an applied delta.  Freshness is recorded here for
        deferred entries whose first retrievable moment is this publish.
        """
        idx, v0 = build_result
        now = time.monotonic()
        version = v0
        for e in self._log.entries():
            if e.version <= v0:
                if not e.applied:
                    self._record_freshness(e.batch, now)
                    e.applied = True
                continue
            if version != e.version - 1:
                break                       # keep replay gap-free
            try:
                idx = self._apply_to_index(idx, e.batch)
            except deltas_lib.SpareCapacityExceeded:
                break                       # next rebuild covers the rest
            version = e.version
            if not e.applied:
                self._record_freshness(e.batch, now)
                e.applied = True
        self._log.truncate_upto(v0)
        return idx, version

    def _on_publish(self, gen: IndexGeneration, build_s: float) -> None:
        with self._lock:
            self.stats.index_rebuilds += 1
            self.stats.delta_version = gen.delta_version
            self.stats.stale_builds = self._buffer.n_stale_builds
        self.stats.stage("rebuild").record(build_s)

    # -- training-side hooks -------------------------------------------------
    def swap_model(self, params, index_state) -> None:
        """Atomic model dump swap (the §3.1 5-10 min cadence)."""
        with self._lock:
            self._params = params
            self._index_state = index_state
            self.stats.index_swaps += 1

    def rebuild_index(self) -> IndexGeneration:
        """Synchronous candidate scan -> next index generation."""
        return self._buffer.rebuild_once()

    def start_auto_rebuild(self, interval_s: float) -> None:
        """Background double-buffered rebuilds every ``interval_s``."""
        self._buffer.start_background(interval_s)

    def stop_auto_rebuild(self) -> None:
        self._buffer.stop_background()

    @property
    def index_generation(self) -> IndexGeneration:
        return self._buffer.current()

    @property
    def delta_log(self) -> deltas_lib.DeltaLog:
        return self._log

    def store_snapshot(self) -> astore.AssignmentStore:
        """The store the serving side currently reflects (applied deltas
        included) — what a batch rebuild oracle should be built from."""
        with self._lock:
            return self._index_state.store

    # -- incremental delta path (deltas.py) --------------------------------
    def apply_deltas(self, batch: deltas_lib.DeltaBatch,
                     immediate: bool = True) -> int:
        """Ingest one step's (re)assignment deltas; returns log version.

        ``immediate=True`` (the delta path): the store write-back, the
        log append and the live-index edit all happen atomically under
        the publish lock (``DoubleBufferedIndex.mutate``), so readers
        see either the pre-batch or post-batch index, never a partial
        apply, and no concurrent rebuild can double-apply the batch.
        When a cluster's spare capacity is exhausted the batch aborts
        (live index untouched), the write stays in the store + log, and
        a FORCED COMPACTION (synchronous rebuild) publishes it instead.

        ``immediate=False`` (deferred baseline): store + log only; the
        batch becomes retrievable at the next rebuild, which is when its
        freshness is recorded — the rebuild-cadence baseline the
        freshness benchmark compares against.
        """
        if not immediate:
            with self._lock:
                self._index_state = self._index_state._replace(
                    store=deltas_lib.write_back(
                        self._index_state.store, batch))
                entry = self._log.append(batch, applied=False)
            return entry.version

        holder = {}

        def fn(index, _version):
            with self._lock:
                self._index_state = self._index_state._replace(
                    store=deltas_lib.write_back(
                        self._index_state.store, batch))
                entry = self._log.append(batch, applied=False)
            holder["entry"] = entry
            new_index = self._apply_to_index(index, batch)  # may raise
            entry.applied = True
            self._record_freshness(batch, time.monotonic())
            with self._lock:
                self.stats.delta_applies += 1
                self.stats.delta_items += batch.n
                self.stats.delta_tombstones += int(
                    (batch.old_id >= 0).sum())
                self.stats.delta_version = entry.version
            return new_index, entry.version

        try:
            self._buffer.mutate(fn)
        except deltas_lib.SpareCapacityExceeded:
            # The store already holds the write (fn ran it before the
            # raise), so one synchronous rebuild both compacts the spare
            # layout and publishes the batch; _reconcile records its
            # freshness and truncates it out of the log.
            with self._lock:
                self.stats.delta_compactions += 1
            self.rebuild_index()
        return holder["entry"].version

    # -- request path ----------------------------------------------------------
    def serve_batch(self, batch: Dict[str, np.ndarray], task: int = 0,
                    n_valid: Optional[int] = None,
                    span_sink: Optional[List[trace_lib.Span]] = None,
                    flush: Optional[int] = None
                    ) -> Dict[str, np.ndarray]:
        """Serve one request batch.

        ``n_valid`` lets a padding caller (the MicroBatcher) report how
        many leading rows are real so ``stats.n_requests`` stays exact.
        The host phases run in spans (``obs.trace.span``): ``serve.put``
        (the batch to the device), ``serve.dispatch`` (the jit call,
        which returns once the program is enqueued) and ``serve.fetch``
        (the outputs to the host, which waits for the device).  Each
        carries ``task``, ``rows`` (real rows), ``bucket`` (padded rows)
        and, from the batcher, ``flush`` (its flush sequence number).
        ``span_sink`` (a list, passed by the batcher for traced flushes)
        receives them; without it, a direct call on a service with a
        sampling tracer records its own trace.
        """
        own_trace = None
        if span_sink is None and self.tracer is not None \
                and self.tracer.should_sample():
            own_trace = self.tracer.start_trace(
                "serve_batch", rows=len(batch["user_id"]), task=task)
            span_sink = []
        bucket = len(batch["user_id"])
        args = dict(task=task, rows=bucket if n_valid is None else n_valid,
                    bucket=bucket)
        if flush is not None:
            args["flush"] = flush
        t0 = time.perf_counter()
        with self._lock:
            params, state = self._params, self._index_state
        gen = self._buffer.current()            # atomic epoch-tagged read
        with trace_lib.span("serve.put", span_sink, **args):
            jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
        t_jit = time.perf_counter()
        with trace_lib.span("serve.dispatch", span_sink, **args):
            out = self._serve_jit(params, state, gen.index, jbatch,
                                  task=task)
        with trace_lib.span("serve.fetch", span_sink, **args):
            out = {k: np.asarray(v) for k, v in out.items()}
        t1 = time.perf_counter()
        self.stats.stage("serve_jit").record(t1 - t_jit)
        self.stats.latency.record(t1 - t0)
        # counters mutate under the lock so concurrent callers stay exact
        with self._lock:
            self.stats.n_batches += 1
            self.stats.n_requests += (n_valid if n_valid is not None
                                      else len(batch["user_id"]))
            self.stats.total_latency_s += t1 - t0
            self.stats.generation = gen.epoch
            if gen.epoch < self._buffer.latest_epoch:
                self.stats.stale_serves += 1
        if own_trace is not None:
            own_trace.attrs["generation"] = gen.epoch
            own_trace.spans.extend(span_sink)
            self.tracer.finish(own_trace)
        prober = self.prober
        if prober is not None and prober.should_sample():
            # merge-order view keeps ids, validity and exact scores
            # aligned in ONE order (exact_scores carries NEG sentinels
            # exactly where the candidate slot is invalid)
            exact = out["exact_scores"]
            prober.submit(quality_lib.ProbeJob(
                batch={k: np.asarray(v) for k, v in batch.items()},
                served_ids=out["index_ids"],
                served_valid=exact > merge_sort.NEG / 2,
                served_exact=exact,
                task=task, generation=gen.epoch,
                t_serve=time.monotonic(), n_valid=n_valid))
        return out

    def make_batcher(self, max_batch: int = 64,
                     max_delay_s: float = 0.002,
                     buckets=None) -> batcher_lib.MicroBatcher:
        """Micro-batching front door sharing this service's telemetry
        (and tracer: sampled requests get queue-wait and their flush's
        batcher.* and serve.* spans)."""
        return batcher_lib.MicroBatcher(
            self.serve_batch, max_batch=max_batch,
            max_delay_s=max_delay_s, buckets=buckets, stats=self.stats,
            tracer=self.tracer)

    # -- shadow quality probes (obs/quality.py) -----------------------------
    def _probe_oracle(self, job: quality_lib.ProbeJob
                      ) -> quality_lib.OracleAnswer:
        """Exact re-scoring of one sampled serve (probe worker thread).

        Params + store are captured under ONE ``self._lock``
        acquisition, so the oracle never scores against a half-swapped
        model or a partially written store — the consistency contract
        ``OracleAnswer`` documents.  The corpus is the CURRENT store
        (deltas included even when the live index has not published
        them), which is exactly what makes probe recall a staleness
        signal: an item the store holds but the index cannot retrieve
        is a probe miss.
        """
        with self._lock:
            params = self._params
            store = self._index_state.store
        jbatch = {k: jnp.asarray(v) for k, v in job.batch.items()}
        u = self._user_emb_jit(params, jbatch, task=job.task)
        # empty slots carry zero embeddings; the NEG bias mask keeps
        # them out of the oracle's top-k even against negative scores
        bias = jnp.where(store.cluster >= 0, store.item_bias,
                         merge_sort.NEG)
        vals, slots = brute_force.mips_topk(u, store.item_emb, bias,
                                            self.prober.k)
        exact_ids = np.asarray(store.item_id)[np.asarray(slots)]
        exact_scores = np.asarray(vals)
        served = np.where(job.served_valid, job.served_ids, 0)
        clof = np.asarray(astore.read_cluster(store, jnp.asarray(served)))
        clof = np.where(job.served_valid, clof, -1)
        shard_of, n_shards = None, 0
        if self.n_shards:
            per = max(self.cfg.n_clusters // self.n_shards, 1)
            shard_of = np.where(clof >= 0, clof // per, -1)
            n_shards = self.n_shards
        return quality_lib.OracleAnswer(
            exact_ids=exact_ids, exact_scores=exact_scores,
            cluster_of=clof, n_clusters=self.cfg.n_clusters,
            shard_of=shard_of, n_shards=n_shards)

    def enable_probes(self, k: int = 20, sample_every: int = 8,
                      window: int = 512, max_queue: int = 64,
                      sampler: Optional[sampling_lib.CounterSampler] = None,
                      registry: Optional[
                          registry_lib.MetricRegistry] = None,
                      namespace: str = "svq"
                      ) -> quality_lib.QualityProber:
        """Attach the shadow-probe pipeline to this service.

        Sampled ``serve_batch`` calls are re-scored against the exact
        MIPS oracle over the live store, off the hot path; pass
        ``sampler=`` (e.g. the tracer's) to make probes and traces the
        same requests.  Pass ``registry=`` to export the probe gauges
        immediately; a later ``register_metrics`` exports them too.
        """
        if self.prober is not None:
            raise RuntimeError("probes already enabled")
        self.prober = quality_lib.QualityProber(
            self._probe_oracle, k=k, sample_every=sample_every,
            sampler=sampler, window=window, max_queue=max_queue)
        if registry is not None:
            self.prober.register(registry, namespace=namespace)
        return self.prober

    def disable_probes(self) -> None:
        """Stop the probe worker (idempotent)."""
        prober, self.prober = self.prober, None
        if prober is not None:
            prober.close()

    # -- alert-driven auto-repair (obs/slo.py) ------------------------------
    def repair(self, reason: str = "") -> IndexGeneration:
        """One repair action: the forced-compaction rebuild.

        The same ticket-guarded ``swap.py`` build path a spare-capacity
        overflow takes — a full candidate scan of the CURRENT store into
        a fresh dense generation, folding in every pending delta-log
        entry.  This is the paper's "reparability" property invoked as
        a closed loop: it restores balance (fresh segments), recall
        (unpublished store content becomes retrievable) and spare
        headroom in one publish.
        """
        with self._lock:
            self.stats.auto_repairs += 1
        return self.rebuild_index()

    def attach_auto_repair(self, engine, slos=None,
                           cooldown_s: float = 30.0):
        """Subscribe ``repair()`` to an ``SLOEngine``'s alert stream.

        Fires on ``"firing"`` transitions only; ``slos`` (iterable of
        SLO names) restricts which alerts trigger a repair (default:
        any).  ``cooldown_s`` rate-limits repairs so a persistently
        burning objective cannot convert the alert stream into a
        rebuild storm.  Returns the listener (useful in tests).
        """
        watched = None if slos is None else frozenset(slos)
        gate_lock = threading.Lock()
        state = {"last": None}
        service = self

        def on_alert(event) -> None:
            if event.state != "firing":
                return
            if watched is not None and event.slo not in watched:
                return
            with gate_lock:
                now = time.monotonic()
                last = state["last"]
                if last is not None and now - last < cooldown_s:
                    return
                state["last"] = now
            service.repair(reason=event.slo)

        engine.add_listener(on_alert)
        return on_alert

    # -- observability surface ---------------------------------------------
    def health_snapshot(self, now: Optional[float] = None
                        ) -> Dict[str, float]:
        """Index-health gauges + freshness view as ONE consistent read.

        The generation tuple and the delta-log version are captured
        under the publish lock (``with_published``), so the gauges, the
        epoch age and the delta lag all describe the same instant — a
        scrape can never see a new index with the old log version.  The
        gauge math itself (numpy over host copies) runs after the lock
        is released.
        """
        def read(gen):
            with self._lock:
                return gen, self._log.version
        gen, log_version = self._buffer.with_published(read)
        h = health_of(gen.index)
        now = time.monotonic() if now is None else now
        h["index_epoch"] = float(gen.epoch)
        h["index_age_s"] = max(now - gen.published_at, 0.0)
        h["delta_version"] = float(gen.delta_version)
        # delta-log entries appended but not yet folded into the live
        # index (0 when every immediate apply succeeded)
        h["delta_log_lag"] = float(log_version - gen.delta_version)
        return h

    def register_metrics(self, registry: Optional[
            registry_lib.MetricRegistry] = None,
            namespace: str = "svq") -> registry_lib.MetricRegistry:
        """Register this service's full telemetry into a MetricRegistry
        (ServeStats counters + histograms, index-health gauges, build
        histogram, tracer ring counters); returns the registry, ready
        for ``repro.obs.start_exporter``."""
        reg = registry if registry is not None \
            else registry_lib.MetricRegistry()
        registry_lib.register_serve_stats(reg, self.stats,
                                          namespace=namespace)
        register_index_health(reg, self.health_snapshot,
                                         namespace=f"{namespace}_index")

        def _build_hist():
            return [registry_lib.Family(
                f"{namespace}_index_build_seconds", "histogram",
                "index build wall time (candidate scan -> publish)",
                [({}, self._buffer.build_hist.snapshot())])]

        reg.register_collector(_build_hist)
        if self.prober is not None:
            self.prober.register(reg, namespace=namespace)
        if self.tracer is not None:
            tracer = self.tracer
            reg.counter_fn(f"{namespace}_traces_finished_total",
                           lambda: float(tracer.n_finished),
                           help="request traces completed into the ring")
            reg.counter_fn(f"{namespace}_traces_dropped_total",
                           lambda: float(tracer.n_dropped),
                           help="oldest traces evicted from the ring")
        return reg


def drive_requests(service: RetrievalService, batches: List[Dict],
                   rebuild_every: int = 0, task: int = 0) -> ServeStats:
    """Batched request driver (examples / benchmarks)."""
    for i, b in enumerate(batches):
        service.serve_batch(b, task=task)
        if rebuild_every and (i + 1) % rebuild_every == 0:
            service.rebuild_index()
    return service.stats
