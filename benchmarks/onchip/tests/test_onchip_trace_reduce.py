"""Trace reduction, on a trace recorded on a TPU v5e and on hand-made
ones.

``data/serve_one_run.trace.json.gz`` is one run of the service's jitted
serve (128 users, 1,024 clusters, 65,536 items) recorded on one v5e by
the JAX profiler, cut to that run's device ops and the harness's
``serve_batch`` spans, with each op's ``tf_op`` kept.
"""
from pathlib import Path

import pytest

import _paths  # noqa: F401
import trace_reduce as T

DATA = Path(__file__).with_name("data") / "serve_one_run.trace.json.gz"


@pytest.fixture(scope="module")
def chip():
    return T.load(str(DATA))


def test_recorded_trace_busy_equals_the_module_run(chip):
    # the serve module ran once, 82.473276 ms on the device, and its
    # ops leave no gap: busy time is the run's length
    assert T.module_runs(chip, "jit(_serve)") == 1
    assert len(chip.modules) == 1
    assert chip.modules[0].dur_ns == pytest.approx(82473276.406)
    assert T.busy_s(chip) == pytest.approx(0.082472591482, rel=1e-9)
    # every op belongs to the serve module, and self times add up to
    # the busy time: a while loop's body is not counted twice
    assert T.module_s(chip, "jit(_serve)") == pytest.approx(
        T.busy_s(chip), rel=1e-9)
    assert sum(o.dur_ns for o in chip.ops) > 1.4 * T.busy_s(chip) * 1e9


def test_recorded_trace_scopes(chip):
    rank = T.ms_per_run(chip, "jit(_serve)", ("cluster_rank",))
    merge = T.ms_per_run(chip, "jit(_serve)", ("merge_serve",))
    rest = T.ms_per_run(chip, "jit(_serve)", ("cluster_rank",
                                              "merge_serve"), outside=True)
    assert rank == pytest.approx(0.029718906, rel=1e-6)
    assert merge == pytest.approx(47.942860186, rel=1e-6)
    assert rest == pytest.approx(34.50001239, rel=1e-6)
    assert rank + merge + rest == pytest.approx(82.472591482, rel=1e-9)
    # a scope that is not there reads nothing, not 0
    assert T.ms_per_run(chip, "jit(_serve)", ("no_such_scope",)) is None
    assert T.ms_per_run(chip, "jit(train)", ("merge_serve",)) is None


def test_recorded_trace_top_ops_and_gaps(chip):
    top = T.top_ops(chip, 2)
    # named by tf_op: the merge's per-pop gather, then an unscoped one
    assert top[0][0] == ("jit(_serve)/merge_serve/vmap(jit(merge_sort_serve))"
                         "/while/body/closed_call/jit(take_along_axis)"
                         "/gather:")
    assert top[0][1] == pytest.approx(0.039674267578, rel=1e-9)
    assert top[1] == ["jit(_serve)/gather:",
                      pytest.approx(0.031158833906, rel=1e-9)]
    gaps = T.idle_gaps(chip, ("serve_batch",), 3)
    assert len(gaps) == 3
    assert all(label == "serve_batch" and s < 1e-6 for label, s in gaps)


def _op(start, dur, scope="jit(f)/a/x", dev="/device:TPU:0"):
    return T.Op(device=dev, name="op", start_ns=start, dur_ns=dur,
                module=scope.split("/")[0], scope=scope, self_ns=dur)


def test_union_of_overlapping_intervals():
    assert T.union_ns([(0, 10), (5, 10), (20, 5), (21, 1)]) == 20
    assert T.union_ns([]) == 0


def test_nested_ops_count_their_self_time():
    ops = T._self_times([_op(0, 100, "jit(f)/loop/while"),
                         _op(10, 30, "jit(f)/loop/body"),
                         _op(50, 20, "jit(f)/loop/body"),
                         _op(200, 10, "jit(f)/other")])
    tr = T.Trace(ops=ops, modules=[], host=[], n_devices=1)
    assert [o.self_ns for o in ops] == [50, 30, 20, 10]
    assert T.scope_s(tr, ("loop",)) == pytest.approx(100e-9)
    assert T.busy_s(tr) == pytest.approx(110e-9)


def test_gaps_are_labelled_by_the_overlapping_span():
    ops = T._self_times([_op(0, 10), _op(100, 10), _op(1000, 10)])
    host = [T.Span("serve_batch", 0, 50), T.Span("submit", 55, 500)]
    tr = T.Trace(ops=ops, modules=[], host=host, n_devices=1)
    gaps = T.idle_gaps(tr, ("serve_batch", "submit"))
    assert gaps == [["submit", 890e-9], ["submit", 90e-9]]
    tr2 = tr._replace(host=[])
    assert T.idle_gaps(tr2, ("serve_batch",))[0][0] == "no harness span"


def test_busy_is_averaged_over_devices():
    ops = T._self_times([_op(0, 10, dev="/device:TPU:0"),
                         _op(0, 30, dev="/device:TPU:1")])
    tr = T.Trace(ops=ops, modules=[], host=[], n_devices=2)
    assert T.busy_s(tr) == pytest.approx(20e-9)


def test_busy_is_clipped_to_the_window():
    ops = T._self_times([_op(0, 10), _op(20, 10), _op(40, 10)])
    host = [T.Span("submit", 25, 1), T.Span("submit", 5, 1)]
    tr = T.Trace(ops=ops, modules=[], host=host, n_devices=1)
    lo = T.first_span_ns(tr, "submit")
    assert lo == 5
    assert T.busy_s(tr, lo, lo + 40) == pytest.approx(20e-9)
    assert T.first_span_ns(tr, "nothing") is None


def _roofline_ctx(tr, n_clusters=1024, rows=128):
    import types
    import run
    import work
    cfg = types.SimpleNamespace(n_clusters=n_clusters, embed_dim=64,
                                clusters_per_query=128)
    return dict(trace=tr, module="jit(_serve)", cfg=cfg,
                batcher=types.SimpleNamespace(served_rows=rows - 8,
                                              padded_rows=8, n_flushes=1),
                peaks=work.peaks("TPU v5 lite")), \
        run.metric_reader("cluster_rank_roofline.tput")


def test_roofline_counts_the_work_its_scope_holds(chip):
    # the recorded scope holds the top-k and no dot: the work is reading
    # 128 x 1,024 scores and writing 128 x 128 (score, id) pairs
    assert "dot_general" not in T.scope_primitives(chip, "cluster_rank",
                                                   "jit(_serve)")
    ctx, read = _roofline_ctx(chip)
    least = (128 * 1024 * 4 + 128 * 128 * 8) / 819e9
    assert read(ctx) == pytest.approx(100 * least / 0.029718906e-3,
                                      rel=1e-6)
    # a scope that holds the dot as well counts the dot's operations and
    # the codebook's bytes
    ops = T._self_times([
        _op(0, 1000, "jit(_serve)/cluster_rank/dot_general:"),
        _op(2000, 1000, "jit(_serve)/cluster_rank/top_k:")])
    runs = [_op(0, 3000, "jit(_serve)")._replace(name="jit__serve(1)")]
    tr = T.Trace(ops=ops, modules=runs, host=[], n_devices=1)
    ctx, read = _roofline_ctx(tr)
    flops = 2 * 128 * 1024 * 64
    bytes_ = (1024 * 64 + 128 * 64) * 4 + 128 * 128 * 8
    least = max(flops / 197e12, bytes_ / 819e9)
    assert read(ctx) == pytest.approx(100 * least / 2e-6, rel=1e-9)
