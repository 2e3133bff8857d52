"""Quickstart: train a streaming-VQ retriever and serve a request batch.

    PYTHONPATH=src python examples/quickstart.py

Trains the paper's retriever on the synthetic impression + candidate
streams for a few hundred steps (CPU-sized config), builds the serving
index (Appendix-B layout), serves a batch of user requests through the
two-step pipeline (cluster ranking -> merge sort -> ranking model) and
through the fused gather+rank path (bit-identical, no candidate slab),
publishes a live delta, runs the async micro-batched front door,
scrapes the Prometheus endpoint and dumps the sampled request traces as
Chrome trace-event JSON (open in Perfetto), federates streaming VQ with
a brute-force incumbent behind one router (merged fan-out + per-backend
contribution on /metrics), and finally reports Recall@50 against the
stream's ground-truth affinity.
"""
import sys

sys.path.insert(0, "src")

import numpy as np

from repro.configs import get_smoke
from repro.core import assignment_store as astore
from repro.core.freq_estimator import hash_ids
from repro.data import RecsysStream, StreamConfig
from repro.launch.train import eval_svq_recall, train_svq
from repro.obs import Tracer, start_exporter
from repro.retrieval import (BruteForceRetriever, RetrieverRegistry,
                             SVQServiceRetriever, corpus_from_service)
from repro.serving import (FederationRouter, RetrievalService, Scenario,
                           extract_deltas)


def main() -> None:
    cfg = get_smoke("svq").with_(
        n_clusters=256, n_items=10_000, n_users=2_000, embed_dim=32,
        clusters_per_query=32, candidates_out=256)
    stream = RecsysStream(StreamConfig(
        n_items=cfg.n_items, n_users=cfg.n_users,
        hist_len=cfg.user_hist_len))

    print("== training (impression + candidate streams) ==")
    params, index, res = train_svq(cfg, stream, n_steps=200, batch=256,
                                   log_every=50)
    print(f"final metrics: {res.metrics[-1]}")

    print("== serving ==")
    # delta_spare reserves per-cluster headroom for live delta appends;
    # the tracer samples every 3rd request: it runs the same serve jit
    # and records its host spans (serve.put / serve.dispatch /
    # serve.fetch); per-stage device times come from a jax.profiler
    # trace, by named scope
    svc = RetrievalService(cfg, params, index, delta_spare=32,
                           tracer=Tracer(capacity=128, sample_every=3))
    users = np.arange(16, dtype=np.int32)
    out = svc.serve_batch(dict(user_id=users,
                               hist=stream.user_hist[users]))
    print(f"served {out['item_ids'].shape} candidates; "
          f"mean latency {svc.stats.mean_latency_ms:.1f} ms/batch")
    print("top items for user 0:", out["item_ids"][0, :10].tolist())

    # fused gather+rank serve: the merge pops are consumed in-kernel and
    # scored against the query without materializing the candidate slab
    # — same pops, same ids, bit-identical to the staged path (the
    # exact Eq. 11 scores agree to float tolerance)
    print("== fused gather+rank serve ==")
    svc_fused = RetrievalService(cfg, params, index, fused=True)
    out_f = svc_fused.serve_batch(dict(user_id=users,
                                       hist=stream.user_hist[users]))
    assert np.array_equal(out["item_ids"], out_f["item_ids"])
    assert np.array_equal(out["scores"], out_f["scores"])
    print(f"fused path bit-matches the staged pipeline; "
          f"mean latency {svc_fused.stats.mean_latency_ms:.1f} ms/batch")

    # index immediacy (§3.1): publish a brand-new item into the LIVE
    # index via the delta path — no rebuild, retrievable right away
    print("== real-time delta publication ==")
    donor = int(out["item_ids"][0, 0])          # a served hot item
    prev = svc.store_snapshot()
    slot = int(np.asarray(hash_ids(np.asarray([donor], np.int32),
                                   prev.capacity))[0])
    new_id = cfg.n_items - 1
    new_store = astore.write(prev, np.asarray([new_id], np.int32),
                             prev.cluster[np.asarray([slot])],
                             prev.item_emb[np.asarray([slot])],
                             np.asarray([1e6], np.float32))
    svc.apply_deltas(extract_deltas(prev, new_store,
                                    np.asarray([new_id], np.int32)))
    out2 = svc.serve_batch(dict(user_id=users,
                                hist=stream.user_hist[users]))
    assert (np.asarray(out2["index_ids"]) == new_id).any()
    f = svc.stats.freshness
    print(f"new item {new_id} retrievable after one apply_deltas "
          f"(freshness {f.percentile(0.5) * 1e3:.1f} ms, "
          f"{svc.stats.delta_applies} delta batch applied, "
          f"0 rebuilds in between)")

    # the production front door: background double-buffered rebuilds +
    # async micro-batching of small per-user requests (serving/)
    print("== async micro-batched serving ==")
    svc.start_auto_rebuild(interval_s=0.5)
    batcher = svc.make_batcher(max_batch=16, max_delay_s=1.0)
    futs = [batcher.submit(dict(user_id=users[i:i + 2],
                                hist=stream.user_hist[users[i:i + 2]]))
            for i in range(0, 16, 2)]
    got = [f.result(timeout=120) for f in futs]
    batcher.close()
    svc.stop_auto_rebuild()
    # same answers through the batched route (per-row candidate-set
    # overlap: a partial deadline flush serves at a different batch
    # shape, where the ranking matmul may drift by 1 ulp and reorder
    # exact ties, so bitwise equality would be timing-dependent)
    got_ids = np.concatenate([g["item_ids"] for g in got])
    overlap = np.mean([len(set(a) & set(b)) / len(set(a))
                       for a, b in zip(out["item_ids"], got_ids)])
    assert overlap > 0.99, overlap
    print(f"{len(futs)} small requests -> {batcher.n_flushes} jit calls "
          f"(buckets {sorted(batcher.shapes_seen)}); index generation "
          f"{svc.index_generation.epoch}; "
          f"p50/p95/p99 = {svc.stats.p50_ms:.0f}/"
          f"{svc.stats.p95_ms:.0f}/{svc.stats.p99_ms:.0f} ms")

    # observability (obs/): every serve above already fed the metric
    # registry and the sampling tracer — scrape them like prod would
    print("== observability: scrape + trace export ==")
    reg = svc.register_metrics()                 # counters/gauges/histos
    with start_exporter(reg, port=0, tracer=svc.tracer) as ex:
        import urllib.request
        with urllib.request.urlopen(ex.url("/metrics"), timeout=10) as r:
            text = r.read().decode()
        wanted = ("svq_requests_total", "svq_serve_latency_seconds_count",
                  "svq_freshness_seconds_count",
                  "svq_index_cluster_entropy")
        shown = [ln for ln in text.splitlines()
                 if ln.startswith(wanted)]
        print(f"GET {ex.url('/metrics')} -> "
              f"{sum(1 for ln in text.splitlines() if ln and ln[0] != '#')}"
              f" series, e.g.:")
        for ln in shown[:4]:
            print(f"  {ln}")
    traces = svc.tracer.traces()
    trace_path = "/tmp/svq_trace.json"
    svc.tracer.export_chrome_trace_json(trace_path)
    spans = sorted({s.name for t in traces for s in t.spans})
    print(f"{len(traces)} sampled traces ({spans}) -> {trace_path} "
          f"(open in Perfetto / chrome://tracing)")

    # federation (retrieval/ + serving/federation.py): run streaming VQ
    # NEXT TO an exact-MIPS incumbent behind one router — scenario
    # fan-out, Alg.-1 merged top-k with keep-first dedup, and
    # per-backend contribution accounting on the same /metrics endpoint
    print("== federated serving (svq + brute-force) ==")
    fed_reg = RetrieverRegistry()
    fed_reg.register("svq", lambda: SVQServiceRetriever(svc))
    fed_reg.register("bf", lambda: BruteForceRetriever(
        svc.user_embedding, corpus_from_service(svc), name="bf"))
    router = FederationRouter(
        fed_reg,
        [Scenario("solo", ("svq",), k=32),
         Scenario("both", ("svq", "bf"), k=32)],
        default_scenario="both")
    batch = dict(user_id=users, hist=stream.user_hist[users])
    direct = svc.serve_batch(batch)                   # post-delta index
    solo = router.serve(batch, scenario="solo")
    assert np.array_equal(np.asarray(solo.ids),
                          direct["item_ids"][:, :32])  # bit-identical path
    fed = router.serve(batch, scenario="both")
    mreg = router.register_metrics()        # svq_fed_* series
    with start_exporter(mreg, port=0) as ex:
        import urllib.request
        with urllib.request.urlopen(ex.url("/metrics"), timeout=10) as r:
            text = r.read().decode()
    contrib = [ln for ln in text.splitlines()
               if ln.startswith(("svq_fed_contribution",
                                 "svq_fed_backend_requests_total"))]
    print(f"single-backend scenario bit-matches serve_batch; "
          f"2-way merge sources for user 0: "
          f"{[fed.source_names[s] for s in np.asarray(fed.sources)[0, :6]]}")
    print("contribution series scraped from /metrics:")
    for ln in contrib:
        print(f"  {ln}")

    rep = eval_svq_recall(cfg, params, index, stream, n_users=64, k=50)
    print(f"Recall@50 vs ground truth: {rep['recall']:.3f}")


if __name__ == "__main__":
    main()
