"""The streaming VQ retriever: indexing step + ranking step (paper Fig. 1).

Functional model:  params (gradient-trained)  +  IndexState (EMA / PS
tables, updated in the SAME jitted train step -- index immediacy, §3.1).

train_step consumes one impression-stream batch and (optionally) one
candidate-stream batch; both update the item->cluster assignment store in
real time.  serve() runs the two-step retrieval: cluster ranking
(u.Q(v_emb)), k-way merge-sort candidate generation (Alg. 1), and the
ranking-step model to produce the final ordered set.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import SVQConfig
from repro.core import assignment_store as astore
from repro.core import freq_estimator as freq
from repro.core import losses, merge_sort, ranking, vq
from repro.obs import trace
from repro.models.dense import init_mlp, mlp
from repro.models.recsys import embedding as emb
from repro.configs.base import EmbeddingSpec
from repro.utils.sharding import shard, batch_spec, current_mesh

Params = Dict[str, Any]


class IndexState(NamedTuple):
    """Non-gradient state: codebook, PS tables, step counter."""
    vq: vq.VQState
    store: astore.AssignmentStore
    freq: freq.FreqState
    step: jax.Array


def _table_specs(cfg: SVQConfig) -> Tuple[EmbeddingSpec, ...]:
    return (
        EmbeddingSpec("user_id", cfg.n_users, cfg.user_embed_dim),
        EmbeddingSpec("item_id", cfg.n_items, cfg.item_embed_dim),
        EmbeddingSpec("item_cate", 4096, cfg.item_embed_dim),
    )


def d_feature_dims(cfg: SVQConfig) -> Tuple[int, int]:
    d_user_in = cfg.user_embed_dim + cfg.item_embed_dim
    d_item_in = 2 * cfg.item_embed_dim
    return d_user_in, d_item_in


def init(key: jax.Array, cfg: SVQConfig) -> Tuple[Params, IndexState]:
    kt, ki, ku, kr, kv = jax.random.split(key, 5)
    d_user_in, d_item_in = d_feature_dims(cfg)
    params: Params = {
        "tables": emb.init_tables(kt, _table_specs(cfg)),
        # item tower outputs personality embedding + popularity bias
        "item_tower": init_mlp(ki, d_item_in,
                               cfg.item_tower[:-1] + (cfg.embed_dim + 1,)),
        # one user tower per task (stacked)
        "user_towers": jax.vmap(
            lambda k: init_mlp(k, d_user_in,
                               cfg.user_tower[:-1] + (cfg.embed_dim,)))(
            jax.random.split(ku, cfg.n_tasks)),
        "rank": ranking.init_ranking(kr, cfg, d_user_in, d_item_in),
    }
    state = IndexState(
        vq=vq.init_vq(kv, cfg.n_clusters, cfg.embed_dim),
        store=astore.init_store(cfg.n_items, cfg.embed_dim),
        freq=freq.init_freq(cfg.n_items),
        step=jnp.zeros((), jnp.int32))
    return params, state


# ---------------------------------------------------------------------------
# Feature extraction (embeddings shared by indexing + ranking steps)
# ---------------------------------------------------------------------------

def user_features(params: Params, user_id: jax.Array,
                  hist: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """-> (user_feat (B, d_u_in), hist_emb (B, H, d_e))."""
    uid = emb.lookup(params["tables"]["user_id"], user_id)
    hist_emb = emb.lookup(params["tables"]["item_id"], hist)
    hist_pool = jnp.mean(hist_emb, axis=-2)
    return jnp.concatenate([uid, hist_pool], -1), hist_emb


def item_features(params: Params, item_id: jax.Array,
                  item_cate: jax.Array) -> jax.Array:
    iid = emb.lookup(params["tables"]["item_id"], item_id)
    cat = emb.lookup(params["tables"]["item_cate"], item_cate)
    return jnp.concatenate([iid, cat], -1)


def index_forward(params: Params, cfg: SVQConfig, user_feat: jax.Array,
                  item_feat: jax.Array
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Indexing-step towers -> (u (P,B,d), v_emb (B,d), v_bias (B,))."""
    u = jax.vmap(lambda tw: mlp(tw, user_feat))(params["user_towers"])
    v_all = mlp(params["item_tower"], item_feat)
    v_emb, v_bias = v_all[..., :-1], v_all[..., -1]
    return u, v_emb, v_bias


# ---------------------------------------------------------------------------
# Training step
# ---------------------------------------------------------------------------

def train_step(params: Params, state: IndexState, cfg: SVQConfig,
               batch: Dict[str, jax.Array],
               cand_batch: Optional[Dict[str, jax.Array]] = None,
               use_kernel: bool = False):
    """One impression-stream step.  Returns (grads, new_state, metrics).

    The caller owns the optimizer (see train/loop.py); grads cover only
    ``params``.  ``batch``: user_id (B,), hist (B,H), item_id (B,),
    item_cate (B,), labels (B,P) rewards in [0, inf).
    """
    bspec = batch_spec(current_mesh())
    step = state.step + 1

    # -- streaming frequency estimation (also = popularity for Eq. 7) ----
    new_freq, delta = freq.update(state.freq, batch["item_id"], step)
    logq = freq.log_q(delta) if cfg.logq_debias else None

    def loss_fn(p):
        user_feat, hist_emb = user_features(p, batch["user_id"],
                                            batch["hist"])
        item_feat = item_features(p, batch["item_id"], batch["item_cate"])
        user_feat = shard(user_feat, P(bspec[0] if len(bspec) else None,
                                       None))
        u, v_emb, v_bias = index_forward(p, cfg, user_feat, item_feat)

        # Eq. 10 assignment (no gradient through assignment itself)
        assignment = vq.assign(state.vq, jax.lax.stop_gradient(v_emb),
                               cfg.disturbance_s, use_kernel=use_kernel)
        e_st = vq.quantize(state.vq, v_emb, assignment)

        labels = batch["labels"]                     # (B, P) rewards
        total = 0.0
        per_task = {}
        ldt = jnp.bfloat16 if cfg.logits_dtype == "bfloat16" else None
        for t in range(cfg.n_tasks):
            pos = labels[:, t] > 0
            la = losses.l_aux(u[t], v_emb, v_bias, logq, valid=pos,
                              dtype=ldt, use_kernel=use_kernel)
            li = losses.l_ind(u[t], v_emb, e_st, v_bias, logq, valid=pos,
                              dtype=ldt, use_kernel=use_kernel)
            total = total + la + li
            per_task[f"l_aux_{t}"] = la
            per_task[f"l_ind_{t}"] = li
        if cfg.use_l_sim:   # §3.2 ablation: vanilla VQ-VAE commitment
            lsim = losses.l_sim(v_emb, state.vq.embeddings()[assignment])
            total = total + lsim
            per_task["l_sim"] = lsim

        # ranking step (shared embeddings, own towers)
        cross = v_emb * u[0] if False else (
            item_feat[..., :cfg.item_embed_dim]
            * user_feat[..., -cfg.item_embed_dim:])
        rlogits = ranking.ranking_scores(p["rank"], cfg, user_feat,
                                         item_feat, hist_emb, cross)
        lrank = 0.0
        for t in range(cfg.n_tasks):
            lr = losses.bce_logits(rlogits[t], (labels[:, t] > 0)
                                   .astype(rlogits.dtype))
            lrank = lrank + lr
            per_task[f"l_rank_{t}"] = lr
        total = total + lrank
        aux = dict(assignment=assignment, v_emb=v_emb, v_bias=v_bias,
                   metrics=per_task)
        return total, aux

    (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    assignment = aux["assignment"]
    v_emb = jax.lax.stop_gradient(aux["v_emb"])
    v_bias = jax.lax.stop_gradient(aux["v_bias"])

    # -- EMA codebook update, popularity/reward weighted (Eq. 7-9, 12-13) -
    rewards = batch["labels"] if cfg.n_tasks > 1 else None
    impressed = jnp.max(batch["labels"], axis=-1) >= 0   # all impressions
    weight = vq.popularity_weight(
        delta, cfg.beta, rewards=rewards,
        eta=cfg.eta if cfg.n_tasks > 1 else None, valid=impressed)
    new_vq = vq.ema_update(state.vq, v_emb, assignment, weight,
                           cfg.ema_alpha, use_kernel=use_kernel)

    # -- real-time PS write-back (index immediacy) ------------------------
    new_store = astore.write(state.store, batch["item_id"], assignment,
                             v_emb, v_bias)

    # -- candidate stream: forward-only assignment refresh (§3.1) ---------
    if cand_batch is not None:
        c_feat = item_features(params, cand_batch["item_id"],
                               cand_batch["item_cate"])
        cv_all = mlp(params["item_tower"], c_feat)
        cv_emb, cv_bias = cv_all[..., :-1], cv_all[..., -1]
        c_assign = vq.assign(new_vq, cv_emb, cfg.disturbance_s,
                             use_kernel=use_kernel)
        new_store = astore.write(new_store, cand_batch["item_id"], c_assign,
                                 cv_emb, cv_bias)

    new_state = IndexState(vq=new_vq, store=new_store, freq=new_freq,
                           step=step)
    metrics = dict(loss=loss, **aux["metrics"],
                   **vq.cluster_usage_stats(new_vq, assignment))
    return grads, new_state, metrics


# ---------------------------------------------------------------------------
# Serving (indexing step -> merge sort -> ranking step)
# ---------------------------------------------------------------------------

def rank_codebook(e: jax.Array, u: jax.Array, n: int,
                  use_kernel: bool = False
                  ) -> Tuple[jax.Array, jax.Array]:
    """Top-n of ``u @ e.T`` per query over an arbitrary codebook slice.

    Shared by the single-device path (full codebook) and the sharded
    path (per-shard Ks rows — serving/sharding.py), so both dispatch
    through the same kernel switch and stay bit-comparable.
    """
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.cluster_rank(u, e, n)
    scores = u @ e.T                               # (B, K)
    return jax.lax.top_k(scores, n)


def rank_clusters(state: IndexState, u: jax.Array, n: int,
                  use_kernel: bool = False
                  ) -> Tuple[jax.Array, jax.Array]:
    """Eq. 5/11 cluster ranking: top-n clusters by u.e_k (per query).

    ``use_kernel=True`` routes through the blocked Pallas kernel
    (online top-n over codebook blocks, no (B, K) matrix in HBM).
    """
    return rank_codebook(state.vq.embeddings(), u, n,
                         use_kernel=use_kernel)


def serve_kernel(top_scores: jax.Array, bias: jax.Array,
                 lengths: jax.Array, chunk: int, target: int,
                 use_kernel: bool = False, exact: bool = True
                 ) -> Tuple[jax.Array, jax.Array]:
    """Single dispatch point for the batched Alg. 1 merge stage.

    (B, C) cluster scores, (B, C, L) pre-sorted bias slabs, (B, C)
    lengths -> ((B, target) flat positions, (B, target) merge scores).
    ``use_kernel=True`` runs the fused Pallas kernel (Mosaic on a TPU,
    interpret mode on the CPU backend); the fallback vmaps the lax.scan
    form.  Both are bit-identical
    to the numpy heap oracle for ``exact=True``.
    """
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.merge_serve(top_scores, bias, lengths, chunk, target,
                                exact)
    from repro.kernels import ref as kref
    return kref.merge_serve_ref(top_scores, bias, lengths, chunk, target,
                                exact)


def fused_gather_rank(u: jax.Array, top_scores: jax.Array,
                      starts: jax.Array, lengths: jax.Array,
                      limits: jax.Array, bias_flat: jax.Array,
                      ids_flat: jax.Array, emb_flat: jax.Array,
                      chunk: int, target: int, l: int,
                      use_kernel: bool = False, exact: bool = True
                      ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                 jax.Array]:
    """Dispatch point for the fused merge+gather+rank serve stage.

    Like ``serve_kernel`` but consuming FLAT index arrays: per-query
    (B, C) cluster scores / flat start addresses / lengths / clamp
    limits, plus the index's (N,) bias, (N,) ids and (N, d) embedding
    payloads.  Each pop dynamically gathers its chunk straight from the
    flat arrays — no (B, C, L) bias slab or (B, S, d) candidate slab in
    HBM.  Returns (pos, merge_scores, cand_ids, exact_scores), each
    (B, target); pos/merge_scores are bit-identical to ``serve_kernel``
    on the equivalent slab.
    """
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.fused_gather_rank(u, top_scores, starts, lengths,
                                      limits, bias_flat, ids_flat,
                                      emb_flat, chunk, target, l, exact)
    from repro.kernels import ref as kref
    return kref.fused_gather_rank_ref(u, top_scores, starts, lengths,
                                      limits, bias_flat, ids_flat,
                                      emb_flat, chunk, target, l, exact)


def serve_stage_rank(params: Params, state: IndexState, cfg: SVQConfig,
                     batch: Dict[str, jax.Array], task: int = 0,
                     use_kernel: bool = False) -> Dict[str, jax.Array]:
    """Stage 1 of serve: user tower + Eq. 11 cluster ranking.

    The serve pipeline is split into three stage functions so the
    observability layer can time each stage per request (three jit calls
    with a sync between them); ``serve`` composes them op-for-op, so the
    fused path's numerics are unchanged by the split.
    """
    user_feat, hist_emb = user_features(params, batch["user_id"],
                                        batch["hist"])
    u = jax.vmap(lambda tw: mlp(tw, user_feat))(params["user_towers"])[task]
    with trace.annotate("cluster_rank"):
        top_scores, top_clusters = rank_clusters(state, u,
                                                 cfg.clusters_per_query,
                                                 use_kernel=use_kernel)
    return dict(user_feat=user_feat, hist_emb=hist_emb, u=u,
                top_scores=top_scores, top_clusters=top_clusters)


def serve_stage_merge(cfg: SVQConfig, index: astore.ServingIndex,
                      s1: Dict[str, jax.Array],
                      items_per_cluster: int = 256,
                      use_kernel: bool = False,
                      fused: bool = False) -> Dict[str, jax.Array]:
    """Stage 2 of serve: slab fetch + Alg. 1 merge -> candidate ids.

    ``fused=True`` skips the (B, C, L) bias-slab materialization: the
    merge, candidate-id gather and exact Eq. 11 dot are fused into one
    pass over the flat index arrays (pl.ds gathers in-kernel; the lax
    fallback gathers per pop).  Bit-identical pos / merge_scores /
    cand_ids; ``exact_scores`` matches the unfused gather+einsum to
    float accumulation order.
    """
    top_scores, top_clusters = s1["top_scores"], s1["top_clusters"]
    starts = index.offsets[top_clusters]                     # (B, C)
    counts = index.counts[top_clusters]       # live prefix (tombstone-aware)
    L = items_per_cluster
    lengths = jnp.minimum(counts, L)
    S = cfg.candidates_out

    if fused:
        limits = jnp.full_like(starts, index.n_items - 1)
        with trace.annotate("fused_gather_rank"):
            pos, msort_scores, cand_ids, exact_scores = fused_gather_rank(
                s1["u"], top_scores, starts, lengths, limits,
                index.item_bias, index.item_ids, index.item_emb,
                cfg.chunk_size, S, L, use_kernel=use_kernel)
        return dict(cand_ids=cand_ids, valid=pos >= 0,
                    merge_scores=msort_scores, exact_scores=exact_scores)

    slab = starts[..., None] + jnp.arange(L)[None, None, :]  # (B, C, L)
    slab = jnp.minimum(slab, index.n_items - 1)
    bias = index.item_bias[slab]                             # (B, C, L)

    # ---- Alg. 1 merge sort over (cluster personality + item bias) ------
    with trace.annotate("merge_serve"):
        pos, msort_scores = serve_kernel(top_scores, bias, lengths,
                                         cfg.chunk_size, S,
                                         use_kernel=use_kernel)
    valid = pos >= 0
    c_idx = jnp.clip(pos, 0) // L
    i_idx = jnp.clip(pos, 0) % L
    flat = jnp.take_along_axis(
        slab.reshape(slab.shape[0], -1),
        (c_idx * L + i_idx).astype(jnp.int32), axis=1)       # (B, S)
    cand_ids = index.item_ids[flat]
    # exact Eq. 11 candidate score u.v + bias from the index payload —
    # what the fused path computes in-kernel (the ranking step still
    # re-embeds candidates from the model tables in stage 3)
    exact_scores = jnp.where(
        valid,
        jnp.einsum("bsd,bd->bs", index.item_emb[flat].astype(jnp.float32),
                   s1["u"].astype(jnp.float32))
        + index.item_bias[flat].astype(jnp.float32),
        merge_sort.NEG)
    return dict(cand_ids=cand_ids, valid=valid,
                merge_scores=msort_scores, exact_scores=exact_scores)


def serve_stage_ranking(params: Params, cfg: SVQConfig,
                        s1: Dict[str, jax.Array], s2: Dict[str, jax.Array],
                        task: int = 0) -> Dict[str, jax.Array]:
    """Stage 3 of serve: ranking step over the compact candidate set
    ("VQ Two-tower" or "VQ Complicated" per cfg.ranking, §3.5)."""
    user_feat, hist_emb = s1["user_feat"], s1["hist_emb"]
    cand_ids, valid = s2["cand_ids"], s2["valid"]
    cand_cate = jnp.zeros_like(cand_ids)      # cate refetched via tables
    item_feat = item_features(params, cand_ids, cand_cate)
    cross = (item_feat[..., :cfg.item_embed_dim]
             * user_feat[..., None, -cfg.item_embed_dim:])
    rscores = ranking.ranking_scores(params["rank"], cfg, user_feat,
                                     item_feat, hist_emb, cross)[task]
    rscores = jnp.where(valid, rscores, merge_sort.NEG)
    order = jnp.argsort(-rscores, axis=-1)
    return dict(
        item_ids=jnp.take_along_axis(cand_ids, order, axis=1),
        scores=jnp.take_along_axis(rscores, order, axis=1),
        merge_scores=s2["merge_scores"],
        exact_scores=s2["exact_scores"],
        index_ids=cand_ids,
        valid=jnp.take_along_axis(valid, order, axis=1))


def serve(params: Params, state: IndexState, cfg: SVQConfig,
          index: astore.ServingIndex, batch: Dict[str, jax.Array],
          items_per_cluster: int = 256, task: int = 0,
          use_kernel: bool = False,
          fused: bool = False) -> Dict[str, jax.Array]:
    """Full retrieval for a user batch -> final candidate ids + scores.

    Composes the three stage functions (rank -> merge -> ranking); under
    one jit this traces exactly the pre-split op sequence.  ``fused``
    selects the slab-free merge+gather+rank stage 2 (bit-identical
    candidates; exact_scores allclose).
    """
    s1 = serve_stage_rank(params, state, cfg, batch, task=task,
                          use_kernel=use_kernel)
    s2 = serve_stage_merge(cfg, index, s1,
                           items_per_cluster=items_per_cluster,
                           use_kernel=use_kernel, fused=fused)
    return serve_stage_ranking(params, cfg, s1, s2, task=task)
