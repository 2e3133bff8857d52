"""The readers of the program's own names: its serve scopes and the host
spans of a flush, on a trace recorded on a TPU v5e and on hand-made
ones.

``data/serve_spans.trace.json.gz`` is the start of one open-loop window
of ``run.py``'s serve cell on one v5e, recorded by the JAX profiler at a
small configuration (1,024 clusters, 32 probed, 64 candidates, 65,536
item slots) and 1,500 users/s: the device ops and module runs of the
first WINDOW_S seconds after the first ``submit`` (five runs of
``jit(_serve)``, flushes of 3 to 20 rows), with each op's ``tf_op`` and
no other argument, and the host spans ``submit``, ``serve_batch``,
``batcher.*`` and ``serve.*`` with their arguments.
"""
from pathlib import Path

import pytest

import _paths  # noqa: F401
import run
import span_reduce as S
import trace_reduce as T

DATA = Path(__file__).with_name("data")
WINDOW_S = 0.036656147578       # first submit to the fifth run's end
MODULE = "jit(_serve)"
SCOPE_METRICS = ("user_tower_ms.tput", "slab_gather_ms.tput",
                 "cand_gather_ms.tput", "rank_features_ms.tput",
                 "rank_score_ms.tput")
IDLE_METRICS = ("idle_launch_ms.tput", "idle_fetch_ms.tput",
                "idle_batcher_ms.tput", "idle_unattributed.tput")


@pytest.fixture(scope="module")
def chip():
    return T.load(str(DATA / "serve_spans.trace.json.gz"))


def _ctx(tr, window_s=WINDOW_S):
    return dict(trace=tr, window_s=window_s, module=MODULE)


@pytest.mark.parametrize("name", SCOPE_METRICS + IDLE_METRICS)
def test_every_new_metric_reads_a_value(chip, name):
    v = run.metric_reader(name)(_ctx(chip))
    assert v is not None and v >= 0.0


def test_scopes_and_residue_tile_the_serve_module(chip):
    """The seven scopes and the time outside them add up to the module's
    device time per run, and every op the serve code makes lies in a
    scope."""
    runs = T.module_runs(chip, MODULE)
    assert runs == 5
    whole = T.module_s(chip, MODULE) * 1e3 / runs
    parts = [T.ms_per_run(chip, MODULE, (s,)) for s in S.SERVE_SCOPES]
    assert all(p is not None for p, s in zip(parts, S.SERVE_SCOPES)
               if s != "fused_gather_rank")
    residue = T.ms_per_run(chip, MODULE, S.SERVE_SCOPES, outside=True) \
        or 0.0
    assert sum(p or 0.0 for p in parts) + residue == pytest.approx(
        whole, rel=1e-9)
    # the residue is only what XLA adds around the program's own ops:
    # argument relayouts (named by the argument, as "p['tables']['item_id']")
    # and async copy and slice halves with no tf_op
    outside = [op for op in chip.ops if MODULE in op.module
               and not any(T.in_scope(op, s) for s in S.SERVE_SCOPES)]
    assert outside and not any(op.scope.startswith(MODULE + "/")
                               for op in outside)
    # the readers are those per-scope times
    for name, scope in zip(SCOPE_METRICS, ("user_tower", "slab_gather",
                                           "cand_gather", "rank_features",
                                           "rank_score")):
        assert run.metric_reader(name)(_ctx(chip)) == \
            T.ms_per_run(chip, MODULE, (scope,))


def test_idle_attributions_sum_to_the_idle_time(chip):
    """Launch, fetch and batcher idle, and the unattributed share, add up
    to the window's idle time: the window less busy time, from the first
    op in it to the last."""
    ctx = _ctx(chip)
    lo = T.first_span_ns(chip, "submit")
    ops = [(o.start_ns, o.start_ns + o.dur_ns) for o in chip.ops
           if o.start_ns + o.dur_ns > lo]
    first, last = min(s for s, _ in ops), max(e for _, e in ops)
    assert lo < first < last < lo + WINDOW_S * 1e9
    idle_s = (last - first) / 1e9 - T.busy_s(chip, first, last)
    idle = S.window_idle(ctx)
    assert sum(e - s for s, e in idle) / 1e9 == pytest.approx(idle_s,
                                                              rel=1e-9)
    runs = T.module_runs(chip, MODULE)
    named_ms = sum(run.metric_reader(m)(ctx) for m in IDLE_METRICS[:3]) \
        * runs
    share = run.metric_reader("idle_unattributed.tput")(ctx)
    assert named_ms + share / 100 * idle_s * 1e3 == pytest.approx(
        idle_s * 1e3, rel=1e-9)


@pytest.mark.parametrize("name", SCOPE_METRICS + IDLE_METRICS)
def test_a_trace_without_program_names_reads_nothing(name):
    """A program that writes neither the new scopes nor the host spans
    (the trace of ``test_onchip_trace_reduce``) reads None, not 0."""
    old = T.load(str(DATA / "serve_one_run.trace.json.gz"))
    assert run.metric_reader(name)(_ctx(old, 0.1)) is None


def _op(start, dur, scope="jit(_serve)/rank_score/x"):
    return T.Op(device="/device:TPU:0", name="op", start_ns=start,
                dur_ns=dur, module="jit(_serve)", scope=scope,
                self_ns=dur)


def _hand_made(host):
    ops = T._self_times([_op(10, 20), _op(25, 15), _op(60, 10),
                         _op(95, 30)])
    modules = [T.Op(device="/device:TPU:0", name="jit__serve(1)",
                    start_ns=10, dur_ns=115, module="", scope="")]
    return T.Trace(ops=ops, modules=modules, n_devices=1,
                   host=[T.Span("submit", 0, 1)] + host)


def test_idle_is_the_gaps_between_ops_in_the_window():
    tr = _hand_made([T.Span("batcher.wait", 0, 5)])
    # ops cover [10, 40), [60, 70), [95, 125); before the first op and
    # after the last the trace saw nothing, so that is no gap
    assert S.idle_intervals(tr, 0, 200) == [(40, 60), (70, 95)]
    assert S.idle_intervals(tr, 30, 65) == [(40, 60)]
    assert S.idle_intervals(tr, 45, 80) == [(45, 60), (70, 80)]


def test_each_idle_ns_goes_to_the_span_over_it():
    tr = _hand_made([T.Span("batcher.wait", 0, 5),       # before the ops
                     T.Span("batcher.take", 42, 8),      # 8 idle
                     T.Span("serve.put", 50, 2),         # 2 idle
                     T.Span("serve.dispatch", 52, 20),   # 8 + 2 idle
                     T.Span("serve.fetch", 72, 30),      # 23 idle
                     T.Span("serve_batch", 0, 100)])     # the harness's
    ctx = dict(trace=tr, window_s=100e-9, module=MODULE)
    assert S.idle_ms_per_run(ctx, S.BATCHER) == pytest.approx(8e-6)
    assert S.idle_ms_per_run(ctx, S.LAUNCH) == pytest.approx(12e-6)
    assert S.idle_ms_per_run(ctx, S.FETCH) == pytest.approx(23e-6)
    # idle 45 ns (the gaps [40, 60) and [70, 95)), named 43: the
    # harness's serve_batch names nothing
    assert S.idle_unattributed_pct(ctx) == pytest.approx(100 * 2 / 45)


def test_overlapping_spans_count_once():
    assert S.spans(_hand_made([T.Span("serve.put", 0, 10),
                               T.Span("serve.dispatch", 5, 10)]),
                   S.LAUNCH) == [(0, 15)]
    assert S.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
