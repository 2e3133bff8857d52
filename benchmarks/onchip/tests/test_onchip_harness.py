"""The harness finds every cell, configuration, mix and metric by name,
and the manifest keeps to the benchmark's contract."""
import dataclasses
import json
import re
import shutil

import jax.numpy as jnp
import numpy as np
import pytest

import _paths  # noqa: F401
import corpus
import rankers
import reference
import run
import work
from repro.configs import svq

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
MAN = run.manifest()


def test_manifest_keys_and_names():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["paths"] == ["benchmarks/onchip"]
    assert 1 <= MAN["run_seconds"] <= 51
    names = [c["name"] for c in MAN["configs"]] \
        + [w["name"] for w in MAN["workloads"]] \
        + [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/onchip/")
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in MAN["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert UNIT.match(m["unit"])
    e2e = {m["name"] for m in MAN["end_to_end"]}
    for m in MAN["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_cell_reports_setup_another_e2e_and_a_layer():
    for w in MAN["workloads"]:
        e2e = [m["name"] for m in run.metrics_of(MAN, w["name"],
                                                 "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert run.metrics_of(MAN, w["name"], "per_layer")


def test_everything_named_is_found():
    for c in MAN["configs"]:
        model = rankers.ranking_model(
            run.load_config(run.ROOT / c["file"]).ranking)
        for fn in ("make_rank_params", "reference_rank", "rank_flops"):
            assert callable(getattr(model, fn)), (c["name"], fn)
    for w in MAN["workloads"]:
        mix = run.load_traffic(w["traffic"])
        assert mix["kind"] == "serve_open_loop"
    for m in MAN["per_layer"]:
        assert callable(run.metric_reader(m["name"]))


def test_config_files_state_the_repo_settings():
    assert run.load_config(run.HERE / "configs/svq16k.json") == svq.CONFIG
    assert run.load_config(run.HERE / "configs/svqmt32k.json") \
        == svq.MULTITASK


def _copy_harness(tmp_path):
    """The benchmark's files, tests left out, under ``tmp_path``."""
    here = tmp_path / "benchmarks" / "onchip"
    shutil.copytree(run.HERE, here, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    return here


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A later PR adds files and manifest entries only."""
    here = _copy_harness(tmp_path)
    cfg = json.loads((here / "configs" / "svq16k.json").read_text())
    cfg["n_clusters"] = 8192
    (here / "configs" / "svq8k.json").write_text(json.dumps(cfg))
    mix = run.load_traffic("overload_svq16k")
    mix["rate_per_s"] = 123
    (here / "traffic" / "slow.json").write_text(json.dumps(mix))
    (here / "metrics" / "answer.tail.py").write_text(
        "def read(ctx):\n    return ctx['x'] * 2\n")
    man = json.loads(json.dumps(MAN))
    man["configs"].append(dict(MAN["configs"][0], name="svq8k",
                               file="benchmarks/onchip/configs/svq8k.json"))
    man["workloads"].append({"name": "svq8k-slow", "config": "svq8k",
                             "traffic": "slow", "chips": 1, "why": "test"})
    man["per_layer"].append({"name": "answer.tail", "unit": "ms",
                             "better": "lower", "source": "program_counter",
                             "layer": "front door (serving/batcher.py)",
                             "moves": "setup_s",
                             "workloads": ["svq8k-slow"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    m2 = run.manifest(tmp_path)
    entry = run.cell_entry(m2, "svq8k-slow")
    cfg8 = run.load_config(tmp_path / run.config_entry(
        m2, entry["config"])["file"])
    assert cfg8.n_clusters == 8192
    assert run.load_traffic(entry["traffic"], here)["rate_per_s"] == 123
    layer = run.metrics_of(m2, "svq8k-slow", "per_layer")
    assert [m["name"] for m in layer] == ["answer.tail"]
    assert run.metric_reader("answer.tail", here)({"x": 21}) == 42


TOY_MODEL = '''"""A ranker for tests: a per-task scale times the sum of the
candidate's item-table row, plus the sum of the history's rows."""
import jax
import jax.numpy as jnp


def make_rank_params(key, cfg):
    return {"scale": jax.random.normal(key, (cfg.n_tasks,))}


def reference_rank(params, feats, task, precision):
    t = params["tables"]["item_id"]
    hist = jnp.sum(t[feats["hist_rows"]], axis=(-2, -1))
    return params["rank"]["scale"][task] * jnp.sum(t[feats["id_rows"]], -1) \\
        + hist[:, None]


def rank_flops(cfg):
    return 7
'''


def test_a_new_ranking_model_is_found_by_name(tmp_path):
    """A configuration with another ``ranking`` needs a model file and a
    configuration file, and no edit of the harness: the weights, the
    reference's ranking scores and the work count all come from
    ``models/<ranking>.py``."""
    here = _copy_harness(tmp_path)
    (here / "models" / "toy.py").write_text(TOY_MODEL)
    raw = json.loads((here / "configs" / "svq16k.json").read_text())
    raw.update(ranking="toy", n_clusters=16, n_items=1024, n_users=256,
               clusters_per_query=4, candidates_out=16)
    (here / "configs" / "toy.json").write_text(json.dumps(raw))
    toy = run.load_config(here / "configs" / "toy.json")

    params = corpus.make_params(corpus.key_of(3), toy, here)
    assert set(params["rank"]) == {"scale"}
    two_tower = dataclasses.replace(toy, ranking="two_tower")
    assert work.serve_user_flops(toy, here) == (
        work.serve_user_flops(two_tower)
        - rankers.ranking_model("two_tower").rank_flops(two_tower) + 7)

    ids = jnp.asarray(corpus.corpus_ids(toy.n_items))
    corp = corpus.make_corpus(corpus.key_of(4), params, ids,
                              toy.n_clusters)
    ref = reference.Reference(toy, params, corp, here=here)
    rng = np.random.default_rng(0)
    hist = rng.integers(0, toy.n_items, (2, toy.user_hist_len))
    item_ids = np.asarray(corp.ids)[:6].reshape(2, 3)
    _, got = ref.score_items(ref.user(np.arange(2), hist, 0), item_ids, 0)
    table = np.asarray(params["tables"]["item_id"], np.float64)
    want = float(params["rank"]["scale"][0]) \
        * table[corpus.hash_ids_np(item_ids, toy.n_items)].sum(-1) \
        + table[corpus.hash_ids_np(hist, toy.n_items)].sum((-2, -1))[:, None]
    np.testing.assert_allclose(got, want, rtol=1e-5)


@pytest.mark.parametrize("use", ["make_params", "serve_user_flops",
                                 "Reference"])
def test_a_ranking_with_no_model_file_is_an_error(tmp_path, use):
    cfg = svq.CONFIG.with_(ranking="absent")
    calls = {
        "make_params": lambda: corpus.make_params(corpus.key_of(1), cfg,
                                                  tmp_path),
        "serve_user_flops": lambda: work.serve_user_flops(cfg, tmp_path),
        "Reference": lambda: reference.Reference(cfg, None, None,
                                                 here=tmp_path),
    }
    with pytest.raises(FileNotFoundError, match=r"models/absent\.py"):
        calls[use]()


def test_a_mix_of_an_unknown_kind_is_refused(tmp_path):
    (tmp_path / "traffic").mkdir()
    mix = dict(run.load_traffic("overload_svq16k"), kind="closed_loop")
    (tmp_path / "traffic" / "closed.json").write_text(json.dumps(mix))
    with pytest.raises(ValueError, match="closed_loop"):
        run.driver(run.load_traffic("closed", tmp_path), "closed")
    assert run.driver(run.load_traffic("overload_svq16k"), "overload") \
        is run.serve_open_loop


def test_a_config_key_that_is_no_field_is_refused(tmp_path):
    cfg = json.loads((run.HERE / "configs" / "svq16k.json").read_text())
    cfg["n_cluster"] = 8192
    f = tmp_path / "typo.json"
    f.write_text(json.dumps(cfg))
    with pytest.raises(KeyError, match="n_cluster"):
        run.load_config(f)


def test_a_per_layer_metric_must_name_its_cells():
    man = json.loads(json.dumps(MAN))
    del man["per_layer"][0]["workloads"]
    with pytest.raises(KeyError, match=man["per_layer"][0]["name"]):
        run.metrics_of(man, MAN["workloads"][0]["name"], "per_layer")
    for m in MAN["per_layer"]:
        assert m["workloads"], m["name"]


def test_no_tpu_means_no_result(capsys):
    # the tests run on the CPU backend: a run must refuse, print nothing
    # on stdout and exit non-zero
    rc = run.main(["--workload", MAN["workloads"][0]["name"], "--seed",
                   "3", "--seconds", "1", "--trace", "0"])
    assert rc == 2
    assert capsys.readouterr().out == ""


def test_too_few_chips_refused():
    with pytest.raises(run.NoChip):
        run.check_devices(64, "cpu")
