"""The target-attention cell (``svqcomplicated-serve-overload``) on the CPU
at a small size: a sound run is correct; the control and faults planted
in the program's attention are not; the work counts match hand counts;
the new scope readers read what the program names and nothing
otherwise."""
import time

import jax
import jax.numpy as jnp
import pytest

import _paths  # noqa: F401
import control
import rankers
import reference
import run
import trace_reduce as T
import work
from repro.configs import svq
from repro.configs.base import SVQConfig
from repro.core import ranking
from repro.models.dense import linear

SMALL = svq.COMPLICATED.with_(n_clusters=256, n_items=8192, n_users=4096,
                              clusters_per_query=16, candidates_out=64)
CELL = "svqcomplicated-serve-overload"
NEW_METRICS = ("rank_attn_ms.tput", "rank_head_ms.tput",
               "rank_attn_roofline.tput")


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


def _run(seconds=0.3):
    return run.run_cell(CELL, 2 ** 33 + 7, seconds, False, platform="cpu",
                        config=SMALL, t_start=time.perf_counter())


def _failed(res):
    return {k for k, c in res["checks"].items()
            if c["value"] is None or c["value"] > c["limit"]}


def test_the_config_file_states_the_repo_settings():
    assert run.load_config(run.HERE / "configs/svqcomplicated.json") \
        == svq.COMPLICATED


def test_a_sound_run_is_correct():
    res = _run()
    assert res["correct"] is True, res["checks"]
    rate = run.load_traffic("overload_svqcomplicated")["rate_per_s"]
    assert res["attempted"] == round(rate * 0.3) and res["failed"] == 0
    assert set(res["metrics"]) == {"serve_users_per_s", "setup_s"}
    assert not _failed(res)


def _faulty_pool(fault):
    """The program's target attention with one fault planted."""
    def pool(attn, t, item_feat, hist_emb, n_heads):
        p = {k: jax.tree_util.tree_map(lambda x: x[t], attn[k])
             for k in ("wq", "wk", "wv")}
        q = linear(p["wq"], item_feat)
        k, v = linear(p["wk"], hist_emb), linear(p["wv"], hist_emb)
        if fault == "k_v_swapped":
            k, v = v, k
        b, s, d_e = q.shape
        hd = d_e // n_heads
        qh = q.reshape(b, s, n_heads, hd)
        kh = k.reshape(b, -1, n_heads, hd)
        vh = v.reshape(kh.shape)
        logits = jnp.einsum("bsnd,bhnd->bsnh", qh, kh) / jnp.sqrt(hd)
        axis = -2 if fault == "softmax_over_heads" else -1
        w = jax.nn.softmax(logits, axis=axis)
        return jnp.einsum("bsnh,bhnd->bsnd", w, vh).reshape(b, s, d_e)
    return pool


@pytest.mark.parametrize("fault", ["softmax_over_heads", "k_v_swapped"])
def test_a_fault_in_the_programs_attention_fails_rank_err(monkeypatch,
                                                          fault):
    monkeypatch.setattr(ranking, "_mha_pool", _faulty_pool(fault))
    res = _run()
    assert res["correct"] is False
    # the candidates are the merge's; only the ranking scores are wrong
    assert _failed(res) == {"rank_err"}, res["checks"]


def test_the_control_fails_rank_err():
    nums = control.readings(CELL, 5, 0.2, config=SMALL)["control"]
    assert nums["rank_err"] > reference.LIMITS["rank_err"], nums


def test_the_reference_refuses_another_head_count():
    model = rankers.ranking_model("complicated")
    with pytest.raises(ValueError, match="heads"):
        model.make_rank_params(jax.random.PRNGKey(0),
                               SMALL.with_(ranking_heads=2))


# the cell's shapes: S = 512 candidates, H = 50, d_e = 64, d_i = 128,
# head 384 -> 512 -> 256 -> 64 -> 1
FULL = svq.COMPLICATED


def test_rank_flops_by_hand():
    q = 512 * 2 * 128 * 64                   # 8,388,608
    kv = 2 * 2 * 50 * 64 * 64                # 819,200
    att = 2 * 2 * 512 * 50 * 64              # 6,553,600
    head = 512 * 2 * (384 * 512 + 512 * 256 + 256 * 64 + 64 * 1)
    model = rankers.ranking_model("complicated")
    assert model.rank_flops(FULL) == q + kv + att + head == 368_148_480
    assert work.serve_user_flops(FULL) == 370_737_152


def test_attn_work_by_hand():
    flops, bytes_ = rankers.ranking_model("complicated").attn_work(512, FULL)
    assert flops == 512 * (8_388_608 + 819_200 + 6_553_600) \
        == 8_069_840_896
    weights = (128 * 64 + 64) + 2 * (64 * 64 + 64)
    assert bytes_ == 4 * (512 * 512 * 128 + 512 * 50 * 64
                          + 512 * 512 * 64 + weights) == 207_946_496
    # a tiny configuration, every term by hand
    cfg = SVQConfig(item_embed_dim=2, user_embed_dim=2, user_hist_len=3,
                    candidates_out=5, ranking="complicated")
    flops, bytes_ = rankers.ranking_model("complicated").attn_work(7, cfg)
    assert flops == 7 * (2 * 5 * 4 * 2 + 2 * 2 * 3 * 2 * 2
                         + 2 * 2 * 5 * 3 * 2)
    assert bytes_ == 4 * (7 * (5 * 4 + 3 * 2 + 5 * 2) + 10 + 2 * 6)


def _op(start, dur, scope):
    return T.Op(device="/device:TPU:0", name="op", start_ns=start,
                dur_ns=dur, module="jit(_serve)", scope=scope, self_ns=dur)


class _Batcher:
    served_rows, padded_rows, n_flushes = 900, 124, 2


def _ctx(scopes):
    ops = [_op(10 + 100 * i, 50, f"jit(_serve)/{s}/dot_general:")
           for i, s in enumerate(scopes)]
    runs = [T.Op(device="/device:TPU:0", name=f"jit__serve({i})",
                 start_ns=0, dur_ns=1000, module="", scope="")
            for i in range(2)]
    tr = T.Trace(ops=ops, modules=runs, host=[], n_devices=1)
    return dict(trace=tr, module="jit(_serve)", cfg=FULL,
                batcher=_Batcher, peaks=work.peaks("TPU v5 lite"))


def test_the_new_scopes_are_read_inside_rank_score():
    ctx = _ctx(["rank_score/rank_attn", "rank_score/rank_attn",
                "rank_score/rank_head", "rank_score", "rank_features"])
    read = lambda n: run.metric_reader(n)(ctx)
    # 50 ns ops over 2 runs of the module
    assert read("rank_attn_ms.tput") == pytest.approx(50e-6)
    assert read("rank_head_ms.tput") == pytest.approx(25e-6)
    assert read("rank_score_ms.tput") == pytest.approx(100e-6)
    flops, bytes_ = rankers.ranking_model("complicated").attn_work(512,
                                                                   FULL)
    least, bound = work.roofline_s(flops, bytes_, ctx["peaks"])
    assert bound == "memory"
    assert read("rank_attn_roofline.tput") == pytest.approx(
        100 * least / 50e-9)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_scopes_reads_nothing(name):
    assert run.metric_reader(name)(_ctx(["rank_score", "rank_features"])) \
        is None
