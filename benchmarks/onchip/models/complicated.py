"""The target-attention ranker ("VQ Complicated", paper §3.5, Fig. 3): per
task, the candidate's item features are the query of a multi-head
attention over the user's behaviour sequence (K = V = the history's
item-table rows); the attended vector, the user feature, the item
features and the cross features ``item_id_row * hist_mean`` are
concatenated and a deep MLP scores the result:

    q = item_feat Wq + bq          (per candidate)
    k = hist Wk + bk, v = hist Wv + bv          (per user)
    a = concat over heads of softmax_H(q_h k_h^T / sqrt(hd)) v_h
    score = MLP([user_feat, item_feat, a, item_feat[:d] * user_feat[-d:]])

As in ``two_tower.py``, each candidate is ranked with the row of category
0, not its own: the program's serve zeroes the candidates' category
before ranking (``core/retriever.py: serve_stage_ranking``), and the
reference is held to what the program serves.  So ``feats["cate_rows"]``
and ``feats["rows"]`` are not read.

``reference_rank`` is not given the configuration, and the head count is
no weight's shape, so the reference is written for the paper's 4 heads
(``HEADS``); ``make_rank_params`` refuses a configuration with another
count.
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
HEADS = 4                 # attention heads, configs/svq.py: ranking_heads


def _dims(cfg):
    """(user feature width, item feature width, head input width)."""
    d_user_in = cfg.user_embed_dim + cfg.item_embed_dim
    d_item_in = 2 * cfg.item_embed_dim
    return (d_user_in, d_item_in,
            d_user_in + d_item_in + 2 * cfg.item_embed_dim)


def make_rank_params(key: jax.Array, cfg) -> dict:
    if cfg.ranking_heads != HEADS:
        raise ValueError(f"the reference ranks with {HEADS} heads, the "
                         f"configuration asks for {cfg.ranking_heads}")
    d_user_in, d_item_in, d_concat = _dims(cfg)
    d_e = cfg.item_embed_dim
    kq, kk, kv, kh = jax.random.split(key, 4)
    linear = lambda d_in: lambda k: mlp_params(k, d_in, (d_e,))["layers"][0]
    return {
        "attn": {"wq": stacked(kq, cfg.n_tasks, linear(d_item_in)),
                 "wk": stacked(kk, cfg.n_tasks, linear(d_e)),
                 "wv": stacked(kv, cfg.n_tasks, linear(d_e))},
        "head": stacked(kh, cfg.n_tasks, lambda k: mlp_params(
            k, d_concat, cfg.ranking_mlp + (1,))),
    }


def reference_rank(params, feats, task: int, precision: str):
    t = params["tables"]
    id_rows = feats["id_rows"]                          # (M, S)
    m, s = id_rows.shape
    item_feat = jnp.concatenate([
        t["item_id"][id_rows],
        jnp.broadcast_to(t["item_cate"][CATE_ROW],
                         (m, s, t["item_cate"].shape[1]))], -1)
    hist = t["item_id"][feats["hist_rows"]]             # (M, H, d_e)
    uf = feats["uf"]                                    # (M, d_user_in)
    rank = plain.task_slice(params["rank"], task)
    a = rank["attn"]
    proj = lambda x, lp: plain.mm("...i,io->...o", x, lp["w"],
                                  precision) + lp["b"]
    q, k, v = proj(item_feat, a["wq"]), proj(hist, a["wk"]), \
        proj(hist, a["wv"])
    d_e = q.shape[-1]
    hd = d_e // HEADS
    qh = q.reshape(m, s, HEADS, hd)
    kh = k.reshape(m, -1, HEADS, hd)
    vh = v.reshape(kh.shape)
    logits = plain.mm("msnd,mhnd->msnh", qh, kh, precision) / hd ** 0.5
    e = jnp.exp(logits - jnp.max(logits, -1, keepdims=True))
    w = e / jnp.sum(e, -1, keepdims=True)               # over the history
    att = plain.mm("msnh,mhnd->msnd", w, vh, precision).reshape(m, s, d_e)
    cross = item_feat[..., :d_e] * uf[:, None, -d_e:]
    cat = jnp.concatenate(
        [jnp.broadcast_to(uf[:, None], (m, s, uf.shape[-1])), item_feat,
         att, cross], -1)
    return plain.mlp(rank["head"], cat, precision)[..., 0]


def rank_flops(cfg) -> int:
    """Per served user: the query projection of every candidate, the K
    and V projections once, the logits and the weighted sum, and the head
    MLP of every candidate."""
    _, _, d_concat = _dims(cfg)
    attn, _ = attn_work(1, cfg)
    return attn + cfg.candidates_out * work.mlp_flops(
        d_concat, cfg.ranking_mlp + (1,))


def attn_work(rows, cfg):
    """(operations, bytes) of the target attention of ``rows`` users over
    their ``candidates_out`` candidates each, as the algorithm needs them:
    the query, K and V projections, the logits and the weighted sum; read
    the candidates' item features, the histories and one task's
    projection weights, write the attended vectors."""
    _, d_i, _ = _dims(cfg)
    s, h, d_e = cfg.candidates_out, cfg.user_hist_len, cfg.item_embed_dim
    flops = rows * (2 * s * d_i * d_e + 2 * 2 * h * d_e * d_e
                    + 2 * 2 * s * h * d_e)
    weights = (d_i * d_e + d_e) + 2 * (d_e * d_e + d_e)
    bytes_ = (rows * (s * d_i + h * d_e + s * d_e) + weights) * work.F32
    return flops, bytes_
