"""Tracing suite: lock-exact ring buffer, Chrome export, deterministic
sampling, end-to-end trace-ID propagation batcher -> serve, and the
bridge into ``jax.profiler`` traces (host spans, device scopes).

The acceptance contract under test: one serve() request submitted
through the micro-batcher yields a single trace holding its queue wait,
then its flush's ``batcher.*`` and ``serve.*`` spans, all carrying the
flush's sequence number and stamped with the request's trace ID in the
Chrome trace-event export; a traced request runs the same jitted serve
as an untraced one, bit for bit.
"""
import glob
import gzip
import json
import re
import threading

import jax
import numpy as np
import pytest

from _obs_svc import make_service
from repro.obs import trace as trace_lib
from repro.obs.trace import Span, Trace, Tracer, make_span

SERVE_SPANS = ["serve.put", "serve.dispatch", "serve.fetch"]
BATCHER_SPANS = ["batcher.wait", "batcher.take", "batcher.assemble"]


# ---------------------------------------------------------------------------
# ring buffer + sampling (pure host)
# ---------------------------------------------------------------------------

def test_ring_buffer_lock_exact_under_threads():
    """N threads x M finishes: counts are EXACT, no tolerance."""
    n_threads, per_thread, cap = 8, 25, 50
    tr = Tracer(capacity=cap)
    barrier = threading.Barrier(n_threads)

    def work():
        barrier.wait()
        for _ in range(per_thread):
            t = tr.start_trace("req")
            t.add_span(make_span("s", 0.0, 1.0))
            tr.finish(t)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * per_thread
    assert tr.n_started == total
    assert tr.n_finished == total
    assert tr.n_dropped == total - cap
    kept = tr.traces()
    assert len(kept) == cap
    assert len({t.trace_id for t in kept}) == cap     # ids stay unique


def test_ring_smaller_than_capacity_keeps_everything():
    tr = Tracer(capacity=100)
    for _ in range(7):
        tr.finish(tr.start_trace("r"))
    assert (tr.n_finished, tr.n_dropped, len(tr.traces())) == (7, 0, 7)


def test_sampling_deterministic_counter():
    tr = Tracer(sample_every=3)
    picks = [tr.should_sample() for _ in range(9)]
    assert picks == [True, False, False] * 3
    off = Tracer(enabled=False)
    assert not any(off.should_sample() for _ in range(5))


def test_tracer_validates_parameters():
    with pytest.raises(ValueError):
        Tracer(capacity=0)
    with pytest.raises(ValueError):
        Tracer(sample_every=0)


def test_trace_span_context_manager_orders_times():
    t = Trace(1, "r")
    with t.span("a", step=3) as s:
        pass
    assert t.spans == [s]
    assert s.t_end >= s.t_start
    assert s.attrs == {"step": 3}
    assert s.thread_id == threading.get_ident()


def test_find_and_clear():
    tr = Tracer()
    t = tr.start_trace("r")
    tr.finish(t)
    assert tr.find(t.trace_id) is t
    assert tr.find(t.trace_id + 999) is None
    tr.clear()
    assert tr.traces() == []


# ---------------------------------------------------------------------------
# Chrome trace-event export
# ---------------------------------------------------------------------------

def _finish_with_spans(tr, name, span_names):
    t = tr.start_trace(name, kind="test")
    for i, s in enumerate(span_names):
        t.add_span(make_span(s, float(i), float(i) + 0.5))
    tr.finish(t)
    return t


def test_chrome_export_valid_and_id_stamped(tmp_path):
    tr = Tracer()
    t1 = _finish_with_spans(tr, "req1", ["a", "b"])
    t2 = _finish_with_spans(tr, "req2", ["c"])
    path = tmp_path / "trace.json"
    text = tr.export_chrome_trace_json(str(path))
    doc = json.loads(text)                      # valid JSON, and
    assert doc == json.loads(path.read_text())  # file == returned text
    events = doc["traceEvents"]
    # every event is a complete event with numeric us timestamps
    for ev in events:
        assert ev["ph"] == "X"
        assert isinstance(ev["ts"], float) and isinstance(ev["dur"], float)
        assert ev["dur"] >= 0.0
        assert "trace_id" in ev["args"]
    by_id = {}
    for ev in events:
        by_id.setdefault(ev["args"]["trace_id"], []).append(ev)
    assert set(by_id) == {t1.trace_id, t2.trace_id}
    names1 = sorted(e["name"] for e in by_id[t1.trace_id])
    assert names1 == ["a", "b", "req1"]
    # request-level attrs ride along on the request event
    req = next(e for e in by_id[t1.trace_id] if e["cat"] == "request")
    assert req["args"]["kind"] == "test"


# ---------------------------------------------------------------------------
# service integration: serve spans, span structure, bit-parity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_service():
    tracer = Tracer()
    cfg, svc, batch = make_service(tracer=tracer)
    return cfg, svc, batch, tracer


def test_direct_serve_records_stage_spans(traced_service):
    _, svc, batch, tracer = traced_service
    tracer.clear()
    svc.serve_batch(batch)
    traces = tracer.traces()
    assert len(traces) == 1
    t = traces[0]
    assert [s.name for s in t.spans] == SERVE_SPANS
    assert t.attrs["rows"] == len(batch["user_id"])
    assert "generation" in t.attrs
    rows = len(batch["user_id"])
    for s in t.spans:
        assert s.attrs == {"task": 0, "rows": rows, "bucket": rows}
    # the host phases follow one another: ordered, non-overlapping
    for a, b in zip(t.spans, t.spans[1:]):
        assert a.t_end <= b.t_start
    assert all(s.duration_s >= 0.0 for s in t.spans)


def test_traced_staged_serve_is_bit_identical_to_fused(traced_service):
    """A sampled request's output equals an unsampled one's, bit for
    bit, and both run the one serve jit (no second compile)."""
    _, svc, batch, tracer = traced_service
    traced = svc.serve_batch(batch)             # sampled
    n_compiled = svc._serve_jit._cache_size()
    tracer.enabled = False
    try:
        plain = svc.serve_batch(batch)          # unsampled
    finally:
        tracer.enabled = True
    assert svc._serve_jit._cache_size() == n_compiled
    assert not hasattr(svc, "_serve_staged")
    assert set(traced) == set(plain)
    for k in traced:
        np.testing.assert_array_equal(traced[k], plain[k], err_msg=k)


def test_batcher_propagates_trace_id_with_four_spans(traced_service):
    """THE acceptance criterion: one request through the batcher ==
    one trace, >= 4 named spans, one shared trace ID in the export."""
    _, svc, batch, tracer = traced_service
    tracer.clear()
    b = svc.make_batcher(max_batch=16, max_delay_s=0.001)
    try:
        futs = [b.submit({k: v[i:i + 1] for k, v in batch.items()})
                for i in range(3)]
        outs = [f.result(timeout=30.0) for f in futs]
    finally:
        b.close()
    assert all(len(o["item_ids"]) == 1 for o in outs)
    traces = tracer.traces()
    assert len(traces) == 3                     # sample_every=1: all
    for t in traces:
        names = [s.name for s in t.spans]
        assert names == ["queue_wait", *BATCHER_SPANS, *SERVE_SPANS]
        assert t.attrs["flush_rows"] >= 1
        # every span carries the flush that served the request, which
        # is the join key to that flush's spans in a device profile
        assert all(s.attrs["flush"] == t.attrs["flush"] for s in t.spans)
        assert all(s.attrs["rows"] == t.attrs["flush_rows"]
                   for s in t.spans if s.name != "batcher.wait")
    # the export stamps every span of a request with ITS trace id
    doc = tracer.export_chrome_trace()
    for t in traces:
        evs = [e for e in doc["traceEvents"]
               if e["args"]["trace_id"] == t.trace_id]
        assert len(evs) == 1 + len(t.spans)
        assert {e["name"] for e in evs if e["cat"] == "span"} == \
            {"queue_wait", *BATCHER_SPANS, *SERVE_SPANS}


def test_batcher_sampling_traces_subset():
    tracer = Tracer(sample_every=2)
    _, svc, batch, = make_service(tracer=tracer)[:3]
    b = svc.make_batcher(max_batch=16, max_delay_s=0.001)
    try:
        futs = [b.submit({k: v[:1] for k, v in batch.items()})
                for _ in range(4)]
        for f in futs:
            f.result(timeout=30.0)
    finally:
        b.close()
    assert tracer.n_finished == 2               # every 2nd submit


@pytest.mark.parametrize("n_shards", [2])
def test_sharded_staged_serve_matches_single_device(n_shards):
    """Sharded traced serve: same span structure, and its output matches
    the single-device serve bit-for-bit (the sharded-vs-single parity
    the serving suite establishes, now through a traced request).
    Under the multi-device tier the mesh places the shard rows on real
    devices."""
    tracer = Tracer()
    _, svc_s, batch = make_service(tracer=tracer, n_shards=n_shards)
    _, svc_1, _ = make_service(tracer=None)
    out_s = svc_s.serve_batch(batch)
    out_1 = svc_1.serve_batch(batch)
    t = tracer.traces()[-1]
    assert [s.name for s in t.spans] == SERVE_SPANS
    assert t.spans[0].attrs["rows"] == len(batch["user_id"])
    for k in out_s:
        np.testing.assert_array_equal(out_s[k], out_1[k], err_msg=k)


# ---------------------------------------------------------------------------
# device-profile bridging
# ---------------------------------------------------------------------------

@pytest.fixture
def annotations_on():
    trace_lib.enable_device_annotations(True)
    try:
        yield
    finally:
        trace_lib.enable_device_annotations(False)


def test_annotate_noop_by_default_and_bridges_when_enabled(annotations_on):
    trace_lib.enable_device_annotations(False)
    assert not trace_lib.device_annotations_enabled()
    with trace_lib.annotate("region"):          # no-op path
        x = 1
    assert x == 1
    assert not jax.config.jax_compilation_cache_include_metadata_in_key
    trace_lib.enable_device_annotations(True)
    assert trace_lib.device_annotations_enabled()
    # a cached program compiled with other scopes must not be loaded,
    # and the key must not depend on where the source lies
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    assert jax.config.jax_traceback_in_locations_limit == 0

    def f(a):
        with trace_lib.annotate("region"):      # a named scope
            return a + 1

    y = jax.jit(f)(jax.numpy.ones(2))
    assert float(y.sum()) == 4.0
    hlo = jax.jit(f).lower(jax.numpy.ones(2)).compile().as_text()
    assert 'op_name="jit(f)/region/add"' in hlo
    trace_lib.enable_device_annotations(False)
    assert not jax.config.jax_compilation_cache_include_metadata_in_key
    assert jax.config.jax_traceback_in_locations_limit == 10


def test_span_records_sink_and_profiler_event(annotations_on, tmp_path):
    """With annotations on, ``span`` writes a TraceAnnotation into the
    profiler's trace with its arguments (a key added in the body
    included) and appends the same Span to the sink."""
    sink = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace_lib.span("serve.put", sink, flush=7, task=1) as args:
            jax.block_until_ready(jax.numpy.ones(4) + 1)
            args["rows"] = 3
    finally:
        jax.profiler.stop_trace()
    assert [s.name for s in sink] == ["serve.put"]
    assert isinstance(sink[0], Span)
    assert sink[0].attrs == {"flush": 7, "task": 1, "rows": 3}
    assert sink[0].t_end >= sink[0].t_start
    path, = glob.glob(str(tmp_path / "**" / "*.trace.json.gz"),
                      recursive=True)
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    ev, = [e for e in events if e.get("name") == "serve.put"]
    assert ev["ph"] == "X"
    assert ev["args"] == {"flush": "7", "task": "1", "rows": "3"}


def test_span_off_makes_no_jax_call(monkeypatch):
    """Annotations off and no sink: one branch, no jax call; a sink
    alone records on the monotonic clock, still without jax."""
    def boom(*a, **k):
        raise AssertionError("jax called")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    assert not trace_lib.device_annotations_enabled()
    with trace_lib.span("batcher.wait", flush=1) as args:
        args["task"] = 0
    sink = []
    with trace_lib.span("batcher.take", sink, flush=1):
        pass
    assert [(s.name, s.attrs) for s in sink] == [("batcher.take",
                                                  {"flush": 1})]


SERVE_SCOPES = ("user_tower", "slab_gather", "cand_gather",
                "rank_features", "rank_score")


def _compiled_serve(annotations: bool, n_shards=None) -> str:
    """Compiled HLO of a small service's ``jit(_serve)``, traced with
    device annotations on or off."""
    trace_lib.enable_device_annotations(annotations)
    try:
        _, svc, batch = make_service(n_shards=n_shards)
        gen = svc.index_generation
        jbatch = {k: jax.numpy.asarray(v) for k, v in batch.items()}
        return svc._serve_jit.lower(svc._params, svc._index_state,
                                    gen.index, jbatch,
                                    task=0).compile().as_text()
    finally:
        trace_lib.enable_device_annotations(False)


def _strip_metadata(hlo: str) -> str:
    """The HLO without op metadata and without the source-location
    tables (FileNames ... StackFrames) that the metadata points into."""
    hlo = re.sub(r",? metadata=\{[^}]*\}", "", hlo)
    hlo = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)"
                 r"\n.*?\n\n", "", hlo, flags=re.S | re.M)
    return "\n".join(ln for ln in hlo.splitlines() if ln.strip())


@pytest.mark.parametrize("n_shards", [None, 2])
def test_serve_scopes_change_metadata_only(n_shards):
    """The named scopes change the compiled serve's op metadata and
    nothing else: with annotations on and off the compiled HLO is the
    same once ``metadata={...}`` is stripped, and each new scope names
    ops of the compiled program."""
    on = _compiled_serve(True, n_shards)
    off = _compiled_serve(False, n_shards)
    assert on != off
    assert _strip_metadata(on) == _strip_metadata(off)
    names = set(re.findall(r'op_name="jit\(_serve\)/([a-z_]+)/', on))
    assert names >= {*SERVE_SCOPES, "cluster_rank", "merge_serve"}
    assert not re.findall(r'op_name="jit\(_serve\)/([a-z_]+)/', off)
