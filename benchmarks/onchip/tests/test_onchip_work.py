"""Peaks table and operation / byte counts from shapes."""
import json

import pytest

import _paths  # noqa: F401
import work
from repro.configs.base import SVQConfig


def test_known_kind_has_its_peaks():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_unknown_kind_raises(tmp_path):
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
    f = tmp_path / "peaks.json"
    f.write_text(json.dumps({"x": {"bf16_flops_per_s": 1.0}}))
    assert work.peaks("x", f)["bf16_flops_per_s"] == 1.0
    with pytest.raises(KeyError):
        work.peaks("cpu", f)


def test_cluster_rank_by_hand():
    # 2 users x 3 clusters x 4 dims: 2*2*3*4 = 48 operations; bytes:
    # codebook 3*4 floats, users 2*4 floats, top-2 (score, id) per user
    flops, bytes_ = work.cluster_rank(2, 3, 4, 2)
    assert flops == 48
    assert bytes_ == (12 + 8) * 4 + 2 * 2 * 8


def test_top_k_by_hand():
    # 2 rows of 3 scores read, top-2 (score, id) per row written
    assert work.top_k(2, 3, 2) == (0, 2 * 3 * 4 + 2 * 2 * 8)


def test_mlp_flops_by_hand():
    assert work.mlp_flops(3, (5, 2)) == 2 * 3 * 5 + 2 * 5 * 2


def test_serve_user_flops_by_hand():
    cfg = SVQConfig(n_clusters=10, embed_dim=4, user_tower=(6, 4),
                    item_tower=(6, 4), item_embed_dim=2, user_embed_dim=2,
                    ranking_mlp=(3, 4), candidates_out=5)
    tower = 2 * 4 * 6 + 2 * 6 * 4            # user tower, 4 inputs
    clusters = 2 * 10 * 4
    exact = 2 * 5 * 4
    rank_user = 2 * 4 * 3 + 2 * 3 * 4
    rank_item = 5 * (2 * 4 * 3 + 2 * 3 * 5)  # item head outputs d + 1
    rank_dot = 2 * 5 * 4
    assert work.serve_user_flops(cfg) == (tower + clusters + exact
                                          + rank_user + rank_item
                                          + rank_dot)


def test_roofline_names_its_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_s(1000, 10, peak) == (10.0, "compute")
    assert work.roofline_s(10, 1000, peak) == (100.0, "memory")
