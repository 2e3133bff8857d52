"""The two-tower ranker ("VQ Two-tower", paper §3.5): per task a user
MLP and an item MLP whose last layer has one output more; a candidate's
score is the dot product of the two plus that extra output.

The reference ranks every candidate from its item-table row and the row
of category 0, not from its own category: the program's serve zeroes
each candidate's category before ranking
(``core/retriever.py: serve_stage_ranking``), and the reference is held
to what the program serves.  So ``feats["cate_rows"]``, ``feats["rows"]``
and ``feats["hist_rows"]`` are not read.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import plain
import work
from corpus import N_CATES, hash_ids_np, mlp_params, stacked

# the category-table row of category 0, the one the program ranks with
CATE_ROW = int(hash_ids_np(np.zeros(1), N_CATES)[0])


def _dims(cfg):
    """(user MLP input width, item MLP input width, item MLP widths)."""
    return (cfg.user_embed_dim + cfg.item_embed_dim,
            2 * cfg.item_embed_dim,
            cfg.ranking_mlp[:-1] + (cfg.ranking_mlp[-1] + 1,))


def make_rank_params(key: jax.Array, cfg) -> dict:
    d_user_in, d_item_in, item_dims = _dims(cfg)
    ku, ki = jax.random.split(key)
    return {
        "user_mlp": stacked(ku, cfg.n_tasks, lambda k: mlp_params(
            k, d_user_in, cfg.ranking_mlp)),
        "item_mlp": stacked(ki, cfg.n_tasks, lambda k: mlp_params(
            k, d_item_in, item_dims)),
    }


def reference_rank(params, feats, task: int, precision: str):
    t = params["tables"]
    id_rows = feats["id_rows"]
    item_feat = jnp.concatenate([
        t["item_id"][id_rows],
        jnp.broadcast_to(t["item_cate"][CATE_ROW],
                         id_rows.shape + (t["item_cate"].shape[1],))], -1)
    rank = plain.task_slice(params["rank"], task)
    ru = plain.mlp(rank["user_mlp"], feats["uf"], precision)
    rv_all = plain.mlp(rank["item_mlp"], item_feat, precision)
    return plain.mm("bd,bsd->bs", ru, rv_all[..., :-1], precision) \
        + rv_all[..., -1]


def rank_flops(cfg) -> int:
    """The user MLP once, the item MLP and the dot product per
    candidate."""
    d_user_in, d_item_in, item_dims = _dims(cfg)
    s = cfg.candidates_out
    return (work.mlp_flops(d_user_in, cfg.ranking_mlp)
            + s * work.mlp_flops(d_item_in, item_dims)
            + 2 * s * cfg.ranking_mlp[-1])
