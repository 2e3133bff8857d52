"""Observability overhead benchmark — tracing + registry must be ~free.

The layer is only shippable if turning it on does not move the serve
tail (Appendix B: the p99 budget is the product constraint).  Two
serve phases over the SAME trained retriever, INTERLEAVED in rounds so
host drift hits both equally:

  disabled  plain RetrievalService: no tracer, no registry,
  obs_on    production observability: a sampling Tracer (every
            ``SAMPLE_EVERY``-th request records its host spans),
            ``register_metrics()`` into a MetricRegistry, a live HTTP
            exporter being scraped during the run.

Acceptance: obs_on p99 within 5% of disabled (``within_5pct``).  The
per-TRACED-request cost (a sampled request runs the same serve jit and
also records its spans) is reported separately, as is the scrape cost.

Satellite: the batched-numpy ``apply_deltas_batched`` vs the sequential
``apply_deltas_loop`` reference on identical delta streams (bit-parity
asserted, speedup reported).  Rows per batch matches a train-step's
delta stream (one row per written item, so ~training batch size); the
public ``apply_deltas`` dispatches to the loop below ~n_clusters/2 rows
where per-row inserts win.

Results land in ``BENCH_observability.json``:

  backend, device_count        jax platform of the run
  shape                        rounds / calls / sample_every / batch rows
  rows.serve_p50, serve_p99    per-phase latencies (ms); inflation_pct
                               is the MEDIAN of paired per-round p99
                               inflations (round_inflations_pct), which
                               is what within_5pct accepts on — pooled
                               p99s are one-hiccup-decides on a shared
                               host
  rows.traced_request          untraced vs traced mean (ms), overhead_x,
                               spans recorded per traced request
  rows.scrape                  scrapes completed during the run, mean ms
  rows.probe_overhead          shadow quality probes (obs/quality.py)
                               off vs on, same paired per-round p50/p99
                               protocol; the async oracle thread must
                               not move the serve tail even while
                               probes are being scored (within_5pct)
  rows.apply_deltas            loop vs vectorized us/batch, speedup_x,
                               parity (bit-equal final index)
"""
from __future__ import annotations

import json
import time
import urllib.request

import jax.numpy as jnp
import numpy as np

import jax
from benchmarks.common import out_json, sz, trained_retriever
from repro.core import assignment_store as astore
from repro.obs import Tracer, start_exporter
from repro.serving import RetrievalService, extract_deltas
from repro.serving.deltas import apply_deltas_batched, apply_deltas_loop

OUT_JSON = out_json("BENCH_observability.json")
ROUNDS = sz(10, 2)              # interleaved rounds per phase
CALLS_PER_ROUND = sz(40, 8)
SAMPLE_EVERY = 256              # production-style trace sampling
PROBE_SAMPLE_EVERY = sz(64, 4)  # production-style probe sampling
PROBE_K = 20
BATCH_ROWS = 32
DELTA_BATCHES = sz(50, 6)
DELTA_ROWS = sz(1024, 128)      # one train step's writes (= batch size)


def _serve_loop(svc, batch, n, out):
    for _ in range(n):
        t0 = time.perf_counter()
        svc.serve_batch(batch)
        out.append(time.perf_counter() - t0)


def _p(xs, q):
    return float(np.percentile(np.asarray(xs), q) * 1e3)      # ms


def _bench_serve(tr, batch):
    cfg = tr.cfg
    svc_off = RetrievalService(cfg, tr.params, tr.index)
    tracer = Tracer(capacity=512, sample_every=SAMPLE_EVERY)
    svc_on = RetrievalService(cfg, tr.params, tr.index, tracer=tracer)
    reg = svc_on.register_metrics()
    # warm both jit paths outside the measurement window
    svc_off.serve_batch(batch)
    svc_on.serve_batch(batch)
    rounds_off, rounds_on, scrape_ms = [], [], []
    with start_exporter(reg, port=0, tracer=tracer) as ex:
        url = ex.url("/metrics")
        for _ in range(ROUNDS):                  # interleave phases
            r_off, r_on = [], []
            _serve_loop(svc_off, batch, CALLS_PER_ROUND, r_off)
            _serve_loop(svc_on, batch, CALLS_PER_ROUND, r_on)
            rounds_off.append(r_off)
            rounds_on.append(r_on)
            t0 = time.perf_counter()
            with urllib.request.urlopen(url, timeout=10.0) as r:
                body = r.read().decode()
            scrape_ms.append((time.perf_counter() - t0) * 1e3)
        n_series = sum(1 for ln in body.splitlines()
                       if ln and not ln.startswith("#"))
    lat_off = [x for r in rounds_off for x in r]
    lat_on = [x for r in rounds_on for x in r]
    # per-traced-request cost: untraced vs traced, same service
    untraced, traced = [], []
    for _ in range(sz(20, 5)):
        t0 = time.perf_counter()
        svc_on.serve_batch(batch, span_sink=None)
        untraced.append(time.perf_counter() - t0)
        sink = []
        t0 = time.perf_counter()
        svc_on.serve_batch(batch, span_sink=sink)
        traced.append(time.perf_counter() - t0)
    n_spans = len(sink)
    p99_off, p99_on = _p(lat_off, 99), _p(lat_on, 99)
    # single pooled p99s are hostile to a shared, noisy host: one
    # scheduler hiccup in either 400-sample pool decides the verdict.
    # The acceptance statistic is the MEDIAN over paired per-round p99
    # inflations — each round saw the same machine weather, and the
    # median discards hiccup rounds in either direction.
    per_round = [(_p(on, 99) - _p(off, 99)) / _p(off, 99) * 100.0
                 for off, on in zip(rounds_off, rounds_on)]
    inflation = float(np.median(per_round))
    return dict(
        serve_p50=dict(disabled_ms=round(_p(lat_off, 50), 4),
                       obs_ms=round(_p(lat_on, 50), 4)),
        serve_p99=dict(disabled_ms=round(p99_off, 4),
                       obs_ms=round(p99_on, 4),
                       inflation_pct=round(inflation, 2),
                       round_inflations_pct=[round(x, 2)
                                             for x in per_round],
                       within_5pct=bool(inflation <= 5.0)),
        traced_request=dict(
            untraced_mean_ms=round(float(np.mean(untraced)) * 1e3, 4),
            traced_mean_ms=round(float(np.mean(traced)) * 1e3, 4),
            overhead_x=round(float(np.mean(traced) / np.mean(untraced)),
                             2),
            spans=n_spans,
            traces_finished=tracer.n_finished),
        scrape=dict(n_scrapes=len(scrape_ms),
                    mean_ms=round(float(np.mean(scrape_ms)), 3),
                    series=n_series),
    )


def _bench_probe_overhead(tr, batch):
    """Shadow-probe cost on the serve path: probes off vs on, paired
    per-round p99 inflation (same protocol as the tracing phases).  The
    oracle re-scoring runs on the prober's worker thread; what this
    measures is the residual hot-path cost — the sampled submit (host
    array copies + enqueue) plus any lock shadow the async oracle casts
    over concurrent serves."""
    cfg = tr.cfg
    svc_off = RetrievalService(cfg, tr.params, tr.index)
    svc_on = RetrievalService(cfg, tr.params, tr.index)
    svc_on.enable_probes(k=PROBE_K, sample_every=PROBE_SAMPLE_EVERY)
    svc_off.serve_batch(batch)                   # warm both jit paths
    svc_on.serve_batch(batch)
    assert svc_on.prober.drain(120.0)            # warm the oracle jit
    rounds_off, rounds_on = [], []
    for _ in range(ROUNDS):                      # interleave phases
        r_off, r_on = [], []
        _serve_loop(svc_off, batch, CALLS_PER_ROUND, r_off)
        _serve_loop(svc_on, batch, CALLS_PER_ROUND, r_on)
        rounds_off.append(r_off)
        rounds_on.append(r_on)
    assert svc_on.prober.drain(120.0)
    snap = svc_on.prober.snapshot()
    svc_on.disable_probes()
    lat_off = [x for r in rounds_off for x in r]
    lat_on = [x for r in rounds_on for x in r]
    per_round = [(_p(on, 99) - _p(off, 99)) / _p(off, 99) * 100.0
                 for off, on in zip(rounds_off, rounds_on)]
    inflation = float(np.median(per_round))
    return dict(
        serve_p50=dict(disabled_ms=round(_p(lat_off, 50), 4),
                       probes_ms=round(_p(lat_on, 50), 4)),
        serve_p99=dict(disabled_ms=round(_p(lat_off, 99), 4),
                       probes_ms=round(_p(lat_on, 99), 4),
                       inflation_pct=round(inflation, 2),
                       round_inflations_pct=[round(x, 2)
                                             for x in per_round],
                       within_5pct=bool(inflation <= 5.0)),
        sample_every=PROBE_SAMPLE_EVERY,
        probes_scored=snap["n_scored"],
        probes_dropped=snap["n_dropped"],
        probe_errors=snap["n_errors"],
        probe_recall=round(snap["recall"]["mean"], 4))


def _bench_apply_deltas(tr):
    cfg = tr.cfg
    store = tr.index.store
    cap = store.capacity
    idx0 = astore.build_serving_index(store, cfg.n_clusters,
                                      spare_per_cluster=128)
    rng = np.random.default_rng(7)
    batches = []
    for _ in range(DELTA_BATCHES):
        ids = rng.integers(0, cfg.n_items, DELTA_ROWS).astype(np.int32)
        new_store = astore.write(
            store, jnp.asarray(ids),
            jnp.asarray(rng.integers(0, cfg.n_clusters, DELTA_ROWS),
                        jnp.int32),
            jnp.asarray(rng.normal(size=(DELTA_ROWS, cfg.embed_dim)),
                        jnp.float32),
            jnp.asarray(rng.normal(size=DELTA_ROWS), jnp.float32))
        batches.append(extract_deltas(store, new_store, jnp.asarray(ids)))
        store = new_store

    def drive(apply_fn):
        idx = idx0
        t0 = time.perf_counter()
        for b in batches:
            idx = apply_fn(idx, b, cfg.n_clusters, cap)
        return (time.perf_counter() - t0) / len(batches) * 1e6, idx

    drive(apply_deltas_loop), drive(apply_deltas_batched)    # warm
    loop_us, idx_loop = drive(apply_deltas_loop)
    vec_us, idx_vec = drive(apply_deltas_batched)
    parity = all(
        np.array_equal(np.asarray(getattr(idx_vec, f)),
                       np.asarray(getattr(idx_loop, f)))
        for f in ("item_ids", "item_bias", "item_emb", "cluster_of",
                  "counts"))
    return dict(loop_us=round(loop_us, 1), vectorized_us=round(vec_us, 1),
                speedup_x=round(loop_us / vec_us, 2), parity=bool(parity),
                n_batches=DELTA_BATCHES, rows_per_batch=DELTA_ROWS)


def run() -> list:
    tr = trained_retriever()
    users = np.arange(BATCH_ROWS) % tr.cfg.n_users
    batch = dict(user_id=users.astype(np.int32),
                 hist=tr.stream.user_hist[users].astype(np.int32))
    record = {"backend": jax.default_backend(),
              "device_count": jax.device_count(),
              "shape": dict(rounds=ROUNDS, calls_per_round=CALLS_PER_ROUND,
                            sample_every=SAMPLE_EVERY,
                            batch_rows=BATCH_ROWS,
                            n_clusters=tr.cfg.n_clusters),
              "rows": {}}
    record["rows"].update(_bench_serve(tr, batch))
    record["rows"]["probe_overhead"] = _bench_probe_overhead(tr, batch)
    record["rows"]["apply_deltas"] = _bench_apply_deltas(tr)
    with open(OUT_JSON, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    r = record["rows"]
    return [
        ("obs/serve_p99_disabled", None, f"{r['serve_p99']['disabled_ms']}ms"),
        ("obs/serve_p99_obs_on", None, f"{r['serve_p99']['obs_ms']}ms"),
        ("obs/p99_inflation", None,
         f"{r['serve_p99']['inflation_pct']}% "
         f"(within_5pct={r['serve_p99']['within_5pct']})"),
        ("obs/traced_request_overhead", None,
         f"{r['traced_request']['overhead_x']}x "
         f"({r['traced_request']['spans']} spans)"),
        ("obs/scrape_mean", None, f"{r['scrape']['mean_ms']}ms "
         f"({r['scrape']['series']} series)"),
        ("obs/probe_p99_inflation", None,
         f"{r['probe_overhead']['serve_p99']['inflation_pct']}% "
         f"(within_5pct={r['probe_overhead']['serve_p99']['within_5pct']}, "
         f"scored={r['probe_overhead']['probes_scored']})"),
        ("obs/apply_deltas_loop", r["apply_deltas"]["loop_us"],
         "us/batch"),
        ("obs/apply_deltas_vectorized", r["apply_deltas"]["vectorized_us"],
         f"speedup={r['apply_deltas']['speedup_x']}x "
         f"parity={r['apply_deltas']['parity']}"),
    ]


if __name__ == "__main__":
    for row in run():
        print(row)
