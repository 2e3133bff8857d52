"""Retrieval *ranking step* models (paper §3.5, Fig. 3).

Two architectures, both sharing the feature embeddings with the indexing
step:
  - "two_tower": DSSM towers + item popularity bias  ("VQ Two-tower")
  - "complicated": item-side embedding is the QUERY of a multi-head
    attention over the user behavior sequence (K = V = sequence item
    embeddings); the attended vector + user/item/cross features feed a
    deep MLP head  ("VQ Complicated").

Multi-task: each task owns a head (stacked parameters, vmapped apply).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import SVQConfig
from repro.models.dense import init_linear, init_mlp, linear, mlp
from repro.obs import trace

Params = Dict[str, Any]


def _stack_init(fn, key: jax.Array, n: int):
    return jax.vmap(fn)(jax.random.split(key, n))


def init_ranking(key: jax.Array, cfg: SVQConfig, d_user_in: int,
                 d_item_in: int) -> Params:
    ku, ki, ka, km = jax.random.split(key, 4)
    p: Params = {}
    if cfg.ranking == "two_tower":
        p["user_mlp"] = _stack_init(
            lambda k: init_mlp(k, d_user_in, cfg.ranking_mlp), ku, cfg.n_tasks)
        # item tower emits (embedding, popularity-bias): final width d+1
        item_dims = cfg.ranking_mlp[:-1] + (cfg.ranking_mlp[-1] + 1,)
        p["item_mlp"] = _stack_init(
            lambda k: init_mlp(k, d_item_in, item_dims), ki, cfg.n_tasks)
    else:
        d_e = cfg.item_embed_dim
        h = cfg.ranking_heads
        p["attn"] = {
            "wq": _stack_init(lambda k: init_linear(k, d_item_in, d_e), ka,
                              cfg.n_tasks),
            "wk": _stack_init(lambda k: init_linear(k, d_e, d_e), km,
                              cfg.n_tasks),
            "wv": _stack_init(lambda k: init_linear(k, d_e, d_e), ku,
                              cfg.n_tasks),
        }
        del h  # head count lives in cfg.ranking_heads, not in params
        d_concat = d_user_in + d_item_in + d_e + cfg.item_embed_dim
        p["head"] = _stack_init(
            lambda k: init_mlp(k, d_concat, cfg.ranking_mlp + (1,)),
            ki, cfg.n_tasks)
    return p


def _mha_pool(attn: Params, task_idx: int, item_feat: jax.Array,
              hist_emb: jax.Array, n_heads: int) -> jax.Array:
    """Target attention: each candidate's item query over the user's
    behaviour sequence.  item_feat (B, S, d_i), hist_emb (B, H, d_e) ->
    (B, S, d_e).  K and V are projected once per user, not per
    candidate."""
    wq = jax.tree_util.tree_map(lambda x: x[task_idx], attn["wq"])
    wk = jax.tree_util.tree_map(lambda x: x[task_idx], attn["wk"])
    wv = jax.tree_util.tree_map(lambda x: x[task_idx], attn["wv"])
    q = linear(wq, item_feat)                    # (B, S, d_e)
    k = linear(wk, hist_emb)                     # (B, H, d_e)
    v = linear(wv, hist_emb)
    b, s, d_e = q.shape
    hd = d_e // n_heads
    qh = q.reshape(b, s, n_heads, hd)
    kh = k.reshape(b, k.shape[1], n_heads, hd)
    vh = v.reshape(kh.shape)
    logits = jnp.einsum("bsnd,bhnd->bsnh", qh, kh) / jnp.sqrt(hd)
    w = jax.nn.softmax(logits, axis=-1)          # over the history
    out = jnp.einsum("bsnh,bhnd->bsnd", w, vh)
    return out.reshape(b, s, d_e)


def ranking_scores(p: Params, cfg: SVQConfig, user_feat: jax.Array,
                   item_feat: jax.Array, hist_emb: jax.Array,
                   cross_feat: jax.Array) -> jax.Array:
    """Per-task logits.

    user_feat: (B, d_u), item_feat: (B, d_i) or (B, S, d_i) for serving,
    hist_emb: (B, H, d_e), cross_feat matches item_feat's batch shape.
    Returns (P, B) or (P, B, S).
    """
    serving = item_feat.ndim == 3
    outs = []
    for t in range(cfg.n_tasks):
        if cfg.ranking == "two_tower":
            um = jax.tree_util.tree_map(lambda x: x[t], p["user_mlp"])
            im = jax.tree_util.tree_map(lambda x: x[t], p["item_mlp"])
            ru = mlp(um, user_feat)                       # (B, d)
            rv_all = mlp(im, item_feat)                   # (..., d+1)
            rv, rb = rv_all[..., :-1], rv_all[..., -1]
            if serving:
                score = jnp.einsum("bd,bsd->bs", ru, rv) + rb
            else:
                score = jnp.sum(ru * rv, axis=-1) + rb
        else:
            # training ranks one candidate per user: a unit S axis
            cand = item_feat if serving else item_feat[:, None]
            cross = cross_feat if serving else cross_feat[:, None]
            with trace.annotate("rank_attn"):
                att = _mha_pool(p["attn"], t, cand, hist_emb,
                                cfg.ranking_heads)
            with trace.annotate("rank_head"):
                uf = jnp.broadcast_to(user_feat[:, None],
                                      cand.shape[:2] + user_feat.shape[-1:])
                cat = jnp.concatenate([uf, cand, att, cross], -1)
                hm = jax.tree_util.tree_map(lambda x: x[t], p["head"])
                score = mlp(hm, cat)[..., 0]
            if not serving:
                score = score[:, 0]
        outs.append(score)
    return jnp.stack(outs)
