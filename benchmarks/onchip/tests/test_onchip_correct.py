"""``correct``: a whole run on the CPU at a small size is correct, and
the same run with the served path broken underneath, or the control
(the reference at "high", three bfloat16 passes) or a planted fault in
its place, is not."""
import time

import jax
import numpy as np
import pytest

import _paths  # noqa: F401
import control
import corpus
import reference
import run
from repro.configs.svq import CONFIG
from repro.core import retriever
from repro.serving import RetrievalService

SMALL = CONFIG.with_(n_clusters=256, n_items=8192, n_users=4096,
                     clusters_per_query=16, candidates_out=64)
CELL = "svq16k-serve-overload"


@pytest.fixture(autouse=True)
def own_jax_settings(tmp_path, monkeypatch):
    """A run turns on JAX's compile cache, device annotations and the
    configuration's matmul precision: keep them to this file's tests."""
    from repro.obs import trace as obs_trace
    names = ("jax_compilation_cache_dir", "jax_default_matmul_precision",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    annotations = obs_trace.device_annotations_enabled()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    yield
    from jax.experimental.compilation_cache import compilation_cache
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()
    obs_trace.enable_device_annotations(annotations)


def _run(seconds=0.5):
    return run.run_cell(CELL, 2 ** 33 + 1, seconds, False, platform="cpu",
                        config=SMALL, t_start=time.perf_counter())


def _break(monkeypatch, fn):
    serve = RetrievalService.serve_batch

    def broken(self, batch, task=0, **kw):
        return fn(serve(self, batch, task, **kw))

    monkeypatch.setattr(RetrievalService, "serve_batch", broken)


def test_a_sound_run_is_correct():
    res = _run()
    assert res["correct"] is True
    rate = run.load_traffic("overload_svq16k")["rate_per_s"]
    assert res["attempted"] == round(rate * 0.5) and res["failed"] == 0
    assert set(res["metrics"]) == {"serve_users_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    for name, c in res["checks"].items():
        assert c["value"] <= c["limit"], name
    assert res["device"]["platform"] == "cpu"


def _other_ids(out):
    out = dict(out)
    ids = out["index_ids"].copy()
    ids[:, 0] = (ids[:, 0] + 1) % SMALL.n_items   # one answer altered
    out["index_ids"] = ids
    return out


def _wrong_merge(out):
    out = dict(out)
    out["merge_scores"] = np.where(out["merge_scores"] > -1e29,
                                   out["merge_scores"] + 0.5,
                                   out["merge_scores"])
    return out


def _wrong_ranking(out):
    out = dict(out)
    out["scores"] = out["scores"] * 1.1
    return out


def _break_merge(monkeypatch, fn):
    merge = retriever.serve_kernel

    def broken(top_scores, bias, lengths, chunk, target, **kw):
        return fn(merge, top_scores, bias, lengths, chunk, target, **kw)

    monkeypatch.setattr(retriever, "serve_kernel", broken)


def _half_empty(out, n_valid):
    """The second half of the flush's requests get no candidates."""
    out = dict(out)
    gone = np.arange(len(out["valid"]))[:, None] >= n_valid // 2
    for k in ("merge_scores", "exact_scores", "scores"):
        out[k] = np.where(gone, np.float32(-1e30), out[k])
    out["valid"] = out["valid"] & ~gone
    return out


def _truncated(merge, top_scores, bias, lengths, chunk, target, **kw):
    """Alg. 1 stops after its first chunk."""
    pos, sc = merge(top_scores, bias, lengths, chunk, chunk, **kw)
    pad = target - chunk
    return (jax.numpy.pad(pos, ((0, 0), (0, pad)), constant_values=-1),
            jax.numpy.pad(sc, ((0, 0), (0, pad)), constant_values=-1e30))


def _reversed_heap(merge, top_scores, bias, lengths, chunk, target, **kw):
    """Alg. 1 pops the cluster with the lowest head first; each list is
    still read from its head and each score is still u . e_c + b."""
    pos, sc = merge(-top_scores, -bias, lengths, chunk, target, **kw)
    return pos, jax.numpy.where(pos >= 0, -sc, sc)


@pytest.mark.parametrize("fault", [_other_ids, _wrong_merge,
                                   _wrong_ranking, _half_empty,
                                   _truncated, _reversed_heap])
def test_a_broken_answer_is_not_correct(monkeypatch, fault):
    if fault is _half_empty:
        serve = RetrievalService.serve_batch

        def broken(self, batch, task=0, **kw):
            return fault(serve(self, batch, task, **kw),
                         kw.get("n_valid", len(batch["user_id"])))

        monkeypatch.setattr(RetrievalService, "serve_batch", broken)
    elif fault in (_truncated, _reversed_heap):
        _break_merge(monkeypatch, fault)
    else:
        _break(monkeypatch, fault)
    res = _run()
    assert res["correct"] is False
    failed = {k for k, c in res["checks"].items()
              if c["value"] is None or c["value"] > c["limit"]}
    # each of these faults passes every number but the new ones
    if fault is _half_empty:
        assert "count_diff" in failed
    elif fault is _truncated:
        assert {"count_diff", "cand_miss"} <= failed
    elif fault is _reversed_heap:
        assert failed == {"cand_miss"}


# A fingerprint of the weights at SMALL from seed 2**33 + 1, taken before
# the ranking towers' weights moved into models/two_tower.py: over the
# leaves of params["rank"] and of the whole tree, flattened in order,
# (element count, float64 sum, {flat index: value}).
PINNED = {
    "rank": (427905, -28.311161270982257,
             {1000: -0.0422891341149807, 12345: 0.1390955150127411,
              213952: -0.08973265439271927,
              427904: -0.0017671141540631652}),
    "all": (1904386, -147.8326998799551,
            {1000: -0.013625750318169594, 12345: 0.13305427134037018,
             952193: 0.24422898888587952, 1904385: 0.011851324699819088}),
}


def test_the_weights_are_pinned():
    params, _ = corpus.make_all(2 ** 33 + 1, SMALL)
    for name, tree in (("rank", params["rank"]), ("all", params)):
        flat = np.concatenate([np.asarray(x).ravel() for x in
                               jax.tree_util.tree_leaves(tree)])
        n, total, values = PINNED[name]
        assert flat.size == n, name
        assert float(flat.astype(np.float64).sum()) == total, name
        for i, v in values.items():
            assert float(flat[i]) == v, (name, i)


def test_the_control_fails_a_limit():
    nums = control.readings(CELL, 5, 0.3, config=SMALL)["control"]
    assert not reference.verdict(nums), nums


@pytest.mark.parametrize("fault,fails", [
    ("reverse_heap", {"cand_miss"}),
    ("truncated_merge", {"count_diff", "cand_miss"}),
    ("half_empty", {"count_diff", "cand_miss"})])
def test_faults_planted_in_the_reference_fail_their_numbers(fault, fails):
    nums = control.readings(CELL, 6, 0.3, config=SMALL,
                            faults=(fault,))[fault]
    failed = {k for k, lim in reference.LIMITS.items() if nums[k] > lim}
    assert failed == fails, nums
