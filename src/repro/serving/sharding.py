"""Cluster-major sharding of the Appendix-B serving index.

The compact serving layout (``astore.ServingIndex``) is one contiguous
item array segmented by cluster.  ``shard_serving_index`` partitions it
CLUSTER-MAJOR over ``n_shards``: shard d owns clusters
[d*Ks, (d+1)*Ks) and, because the layout is cluster-sorted, the
contiguous global item range [item_base[d], item_base[d+1]).  Per-shard
arrays are padded to a power-of-two capacity bucket so rebuilds keep a
stable shape (no recompile until a bucket overflows), and the constant
sentinel tail (empty PS slots: id -1, bias 0) is synthesized at gather
time instead of being stored D times.

``sharded_serve`` is the distributed two-step pipeline, bit-exact vs the
single-device ``retriever.serve`` on the same underlying index:

  1. per-shard indexing step — every shard ranks its own Ks codebook
     rows (``rank_codebook``: Pallas ``cluster_rank`` or the lax
     fallback, the same dispatch the single-device path uses) and emits
     its local top-n(C) cluster candidates;
  2. cross-shard cluster merge — a global top-C over the concatenated
     per-shard candidates.  Per-shard lists are sorted with ties broken
     toward lower cluster id and concatenated in shard order, so the
     merged ``lax.top_k`` reproduces the single-device tie-breaking
     exactly (first-occurrence == lowest global cluster id);
  3. routed slab fetch — the (B, C, L) pre-sorted bias slabs are
     gathered from the owning shards only (merge-then-fetch: the
     cross-shard traffic is C slabs per query, the same volume the
     single-device path reads from HBM);
  4. one ``serve_kernel`` merge (Alg. 1) over the merged slabs,
     data-parallel over the request batch on the same device axis; the
     final candidate payload gather routes each global flat position
     back to its owning shard.  The closing ranking step is pinned
     REPLICATED: a batch-partitioned MLP forward is not bitwise stable
     (gemm remainder panels), and the bit-exact contract wins over
     parallelizing the small ranking head (ROADMAP follow-up).

When a ``jax.sharding.Mesh`` is supplied (``launch/mesh.py:
make_serving_mesh``), the index arrays carry NamedShardings over the
``"shard"`` axis and the batch-stage intermediates are constrained to
the same axis, so stage 1 runs cluster-parallel and stage 4 runs
request-parallel on the same devices.  Without a mesh everything
degrades to single-device arrays with identical numerics.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import SVQConfig
from repro.core import assignment_store as astore
from repro.core import merge_sort, ranking
from repro.core.retriever import (IndexState, Params, fused_gather_rank,
                                  item_features, rank_codebook,
                                  serve_kernel, user_features)
from repro.models.dense import mlp
from repro.obs import trace
from repro.utils.sharding import constrain

SHARD_AXIS = "shard"


class ShardedServingIndex(NamedTuple):
    """Cluster-major shards of one ServingIndex generation.

    Shard d's arrays hold its real items in [0, count_d) of the padded
    capacity; ``offsets[d]`` are shard-local segment starts for its Ks
    clusters; ``item_base[d]`` maps local back to global flat positions.
    The full serve-path payload (id + bias + personality embedding) is
    sharded: the fused gather+rank stage scores candidates against the
    query from ``item_emb`` in-kernel, so each shard owns its items'
    Appendix-B embedding rows too (the ranking step still re-embeds
    final candidates from the model tables).
    """
    item_ids: jax.Array      # (D, cap) int32, -1 padded
    item_bias: jax.Array     # (D, cap) sorted desc within each segment
    item_emb: jax.Array      # (D, cap, d) personality embeddings, 0 padded
    offsets: jax.Array       # (D, Ks+1) int32 shard-local segment starts
    item_base: jax.Array     # (D,) int32 global pos of shard's first item
    n_real: jax.Array        # () int32: global end of the sharded region
    n_items: jax.Array       # () int32: global capacity incl. sentinels
    counts: jax.Array        # (D, Ks) int32 live items per local segment

    @property
    def n_shards(self) -> int:
        return self.item_ids.shape[0]

    @property
    def clusters_per_shard(self) -> int:
        return self.offsets.shape[1] - 1

    @property
    def capacity(self) -> int:
        return self.item_ids.shape[1]


def _bucket(n: int, quantum: int) -> int:
    """Smallest power-of-two multiple of quantum holding n items."""
    b = max(quantum, 1)
    while b < n:
        b *= 2
    return b


def shard_serving_index(index: astore.ServingIndex, n_clusters: int,
                        n_shards: int,
                        cap_quantum: int = 256) -> ShardedServingIndex:
    """Host-side cluster-major partition (part of the async rebuild)."""
    if n_clusters % n_shards:
        raise ValueError(f"n_clusters={n_clusters} not divisible by "
                         f"n_shards={n_shards}")
    ks = n_clusters // n_shards
    offs = np.asarray(index.offsets)
    ids = np.asarray(index.item_ids)
    bias = np.asarray(index.item_bias)
    emb = np.asarray(index.item_emb)
    live = np.asarray(index.counts)
    n_real = int(offs[n_clusters])
    # Every non-live slot (per-cluster spare capacity + the sentinel
    # tail of never-written PS slots) must be constant so the sharded
    # gather can synthesize it; guard the bit-exactness claim.
    live_mask = np.zeros(ids.shape[0], bool)
    for c in range(n_clusters):
        live_mask[offs[c]:offs[c] + live[c]] = True
    if not ((ids[~live_mask] == -1).all()
            and (bias[~live_mask] == 0.0).all()
            and (emb[~live_mask] == 0.0).all()):
        raise ValueError("non-live slots are not constant "
                         "(-1 id, 0 bias, 0 emb)")

    base = offs[np.arange(n_shards) * ks].astype(np.int32)
    ends = offs[(np.arange(n_shards) + 1) * ks].astype(np.int32)
    region = ends - base
    cap = _bucket(int(region.max(initial=0)), cap_quantum)

    s_ids = np.full((n_shards, cap), -1, np.int32)
    s_bias = np.zeros((n_shards, cap), bias.dtype)
    s_emb = np.zeros((n_shards, cap, emb.shape[1]), emb.dtype)
    s_offs = np.zeros((n_shards, ks + 1), np.int32)
    s_cnts = np.zeros((n_shards, ks), np.int32)
    for d in range(n_shards):
        lo, hi = int(base[d]), int(ends[d])
        s_ids[d, :hi - lo] = ids[lo:hi]
        s_bias[d, :hi - lo] = bias[lo:hi]
        s_emb[d, :hi - lo] = emb[lo:hi]
        s_offs[d] = offs[d * ks:(d + 1) * ks + 1] - base[d]
        s_cnts[d] = live[d * ks:(d + 1) * ks]
    return ShardedServingIndex(
        item_ids=jnp.asarray(s_ids), item_emb=jnp.asarray(s_emb),
        item_bias=jnp.asarray(s_bias), offsets=jnp.asarray(s_offs),
        item_base=jnp.asarray(base),
        n_real=jnp.int32(n_real), n_items=jnp.int32(index.n_items),
        counts=jnp.asarray(s_cnts))


def place_sharded_index(sidx: ShardedServingIndex, mesh: Mesh,
                        axis: str = SHARD_AXIS) -> ShardedServingIndex:
    """Commit the shard arrays to devices along ``axis`` of ``mesh``."""
    if sidx.n_shards % mesh.shape[axis]:
        raise ValueError(f"n_shards={sidx.n_shards} not divisible by mesh "
                         f"axis {axis}={mesh.shape[axis]}")

    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    return ShardedServingIndex(
        item_ids=put(sidx.item_ids, P(axis, None)),
        item_emb=put(sidx.item_emb, P(axis, None, None)),
        item_bias=put(sidx.item_bias, P(axis, None)),
        offsets=put(sidx.offsets, P(axis, None)),
        item_base=put(sidx.item_base, P()),       # replicated: routing table
        n_real=put(sidx.n_real, P()),
        n_items=put(sidx.n_items, P()),
        counts=put(sidx.counts, P(axis, None)))


def sharded_stage_rank(params: Params, state: IndexState, cfg: SVQConfig,
                       sidx: ShardedServingIndex,
                       batch: Dict[str, jax.Array], task: int = 0,
                       use_kernel: bool = False,
                       mesh: Optional[Mesh] = None) -> Dict[str, jax.Array]:
    """Stages 1-2: per-shard cluster ranking + cross-shard merge.

    Mirrors ``retriever.serve_stage_rank`` (same output keys), so the
    observability layer times the sharded and single-device pipelines
    through one staged interface; ``sharded_serve`` composes the stage
    functions op-for-op.
    """
    D = sidx.n_shards
    ks = sidx.clusters_per_shard
    C = cfg.clusters_per_query
    n_local = min(C, ks)

    user_feat, hist_emb = user_features(params, batch["user_id"],
                                        batch["hist"])
    u = jax.vmap(lambda tw: mlp(tw, user_feat))(params["user_towers"])[task]
    u = constrain(u, mesh, P(SHARD_AXIS, None))

    # ---- stage 1: per-shard indexing step (local cluster ranking) ------
    e_all = state.vq.embeddings()
    # The lax path scores the whole codebook in ONE dot and slices it per
    # shard: XLA's CPU dot rounds differently for different output
    # widths, so per-shard dots would drift an ulp from the single-device
    # scores.  The kernel scores fixed (block_b, block_k) tiles either way.
    scores = None if use_kernel else u @ e_all.T
    vals_l, ids_l = [], []
    with trace.annotate("cluster_rank"):
        for d in range(D):
            if use_kernel:
                e_d = jax.lax.slice_in_dim(e_all, d * ks, (d + 1) * ks)
                v, i = rank_codebook(e_d, u, n_local, use_kernel=True)
            else:
                v, i = jax.lax.top_k(
                    jax.lax.slice_in_dim(scores, d * ks, (d + 1) * ks,
                                         axis=1), n_local)
            vals_l.append(v)
            ids_l.append(i + jnp.int32(d * ks))
    # shard-order concat: ties resolve to the lower global cluster id,
    # exactly like the single-device lax.top_k over the full codebook
    vals = constrain(jnp.concatenate(vals_l, axis=1), mesh,
                      P(None, SHARD_AXIS))
    gids = constrain(jnp.concatenate(ids_l, axis=1), mesh,
                      P(None, SHARD_AXIS))

    # ---- stage 2: cross-shard cluster merge ----------------------------
    top_scores, sel = jax.lax.top_k(vals, C)
    top_clusters = jnp.take_along_axis(gids, sel, axis=1)        # (B, C)
    top_scores = constrain(top_scores, mesh, P(SHARD_AXIS, None))
    top_clusters = constrain(top_clusters, mesh, P(SHARD_AXIS, None))
    return dict(user_feat=user_feat, hist_emb=hist_emb, u=u,
                top_scores=top_scores, top_clusters=top_clusters)


def sharded_stage_merge(cfg: SVQConfig, sidx: ShardedServingIndex,
                        s1: Dict[str, jax.Array],
                        items_per_cluster: int = 256,
                        use_kernel: bool = False,
                        fused: bool = False,
                        mesh: Optional[Mesh] = None
                        ) -> Dict[str, jax.Array]:
    """Stages 3-4a: routed slab fetch + Alg. 1 merge + payload gather.

    ``fused=True`` drops the (B, C, L) bias-slab materialization: the
    merge consumes flattened shard-local addresses (``owner * cap +
    local``) whose per-lane clamp reproduces the slab path's ``cap - 1``
    clamp bit-exactly, and the exact Eq. 11 score is computed in the
    same pass from the sharded embedding payload.  Candidate ids are
    still routed OUTSIDE the kernel (searchsorted over ``item_base``),
    so the sentinel-tail synthesis stays byte-for-byte the slab path's.
    """
    D = sidx.n_shards
    ks = sidx.clusters_per_shard
    cap = sidx.capacity
    L = items_per_cluster
    top_scores, top_clusters = s1["top_scores"], s1["top_clusters"]

    # ---- stage 3: routed slab fetch from the owning shards -------------
    owner = top_clusters // ks                                   # (B, C)
    local_c = top_clusters % ks
    lstart = sidx.offsets[owner, local_c]
    counts = sidx.counts[owner, local_c]      # live prefix (tombstone-aware)
    ar = jnp.arange(L, dtype=jnp.int32)
    lengths = jnp.minimum(counts, L)
    S = cfg.candidates_out

    if fused:
        # flattened (D * cap) addressing: min(owner*cap + local + i,
        # owner*cap + cap-1) == the slab path's local ``cap - 1`` clamp
        starts = owner * cap + lstart                            # (B, C)
        limits = owner * cap + (cap - 1)
        with trace.annotate("fused_gather_rank"):
            pos, msort_scores, _, exact_scores = fused_gather_rank(
                s1["u"], top_scores, starts, lengths, limits,
                sidx.item_bias.reshape(-1), sidx.item_ids.reshape(-1),
                sidx.item_emb.reshape(-1, sidx.item_emb.shape[-1]),
                cfg.chunk_size, S, L, use_kernel=use_kernel)
        valid = pos >= 0
        c_idx = jnp.clip(pos, 0) // L
        i_idx = jnp.clip(pos, 0) % L
        owner_s = jnp.take_along_axis(owner, c_idx, axis=1)
        lstart_s = jnp.take_along_axis(lstart, c_idx, axis=1)
        flat = jnp.minimum(sidx.item_base[owner_s] + lstart_s + i_idx,
                           sidx.n_items - 1)
        cand_ids = _route_candidate_ids(sidx, flat, D, cap)
        return dict(cand_ids=cand_ids, valid=valid,
                    merge_scores=msort_scores, exact_scores=exact_scores)

    # global flat positions, identical (incl. the n-1 clamp) to the
    # single-device ``starts[..., None] + arange`` slab
    slab = jnp.minimum(sidx.item_base[owner][..., None]
                       + lstart[..., None] + ar, sidx.n_items - 1)
    # bias values come from the owning shard's local arrays; lanes past
    # ``lengths`` are padding garbage in BOTH paths and both merge
    # implementations mask them, so outputs stay bit-exact
    lslab = jnp.minimum(lstart[..., None] + ar, cap - 1)
    bias = sidx.item_bias[owner[..., None], lslab]               # (B, C, L)
    bias = constrain(bias, mesh, P(SHARD_AXIS, None, None))

    # ---- stage 4a: Alg. 1 merge (batch-parallel) -----------------------
    with trace.annotate("merge_serve"):
        pos, msort_scores = serve_kernel(top_scores, bias, lengths,
                                         cfg.chunk_size, S,
                                         use_kernel=use_kernel)
    valid = pos >= 0
    c_idx = jnp.clip(pos, 0) // L
    i_idx = jnp.clip(pos, 0) % L
    flat = jnp.take_along_axis(
        slab.reshape(slab.shape[0], -1),
        (c_idx * L + i_idx).astype(jnp.int32), axis=1)           # (B, S)

    cand_ids = _route_candidate_ids(sidx, flat, D, cap)
    # exact Eq. 11 candidate score from the sharded payload — what the
    # fused path computes in-kernel
    fowner = jnp.clip(
        jnp.searchsorted(sidx.item_base, flat, side="right") - 1, 0, D - 1)
    flocal = jnp.clip(flat - sidx.item_base[fowner], 0, cap - 1)
    exact_scores = jnp.where(
        valid,
        jnp.einsum("bsd,bd->bs",
                   sidx.item_emb[fowner, flocal].astype(jnp.float32),
                   s1["u"].astype(jnp.float32))
        + sidx.item_bias[fowner, flocal].astype(jnp.float32),
        merge_sort.NEG)
    return dict(cand_ids=cand_ids, valid=valid,
                merge_scores=msort_scores, exact_scores=exact_scores)


def _route_candidate_ids(sidx: ShardedServingIndex, flat: jax.Array,
                         D: int, cap: int) -> jax.Array:
    """Route global flat positions back to their owning shard; sentinel
    tail positions (>= n_real) synthesize the constant empty-slot id."""
    fowner = jnp.clip(
        jnp.searchsorted(sidx.item_base, flat, side="right") - 1, 0, D - 1)
    flocal = jnp.clip(flat - sidx.item_base[fowner], 0, cap - 1)
    in_tail = flat >= sidx.n_real
    return jnp.where(in_tail, jnp.int32(-1),
                     sidx.item_ids[fowner, flocal])


def sharded_stage_ranking(params: Params, cfg: SVQConfig,
                          s1: Dict[str, jax.Array],
                          s2: Dict[str, jax.Array], task: int = 0,
                          mesh: Optional[Mesh] = None,
                          rank_parallel: bool = False
                          ) -> Dict[str, jax.Array]:
    """Stage 4b: the closing ranking step over merged candidates.

    Default (``rank_parallel=False``): ranking-step inputs are pinned
    replicated — a batch-partitioned MLP forward is NOT bitwise stable
    (gemm remainder panels reorder the per-row accumulation), and the
    bit-exact contract vs the single-device serve wins by default.

    ``rank_parallel=True`` batch-partitions the ranking MLP over the
    shard axis (each device ranks B/D rows of the merged candidate
    set) under a TOLERANCE contract instead of the bit-exact one:
    per-row scores may differ from the replicated oracle by a few ulps
    of f32 (remainder-panel reordering inside the gemm), so the
    candidate-id SET per row is identical and id-aligned scores agree
    to allclose(rtol=1e-5, atol=1e-5) — the contract
    tests/test_sharded_serving.py enforces with the sequential path as
    oracle.  Tie-adjacent rows can legally reorder; consumers needing
    exact order keep the default.  Requires the batch divisible by the
    mesh size.
    """
    cand_ids, valid = s2["cand_ids"], s2["valid"]
    batch_spec = P(SHARD_AXIS) if rank_parallel else P()
    cand_ids = constrain(cand_ids, mesh, batch_spec)
    user_feat = constrain(s1["user_feat"], mesh, batch_spec)
    hist_emb = constrain(s1["hist_emb"], mesh, batch_spec)
    cand_cate = jnp.zeros_like(cand_ids)
    item_feat = item_features(params, cand_ids, cand_cate)
    cross = (item_feat[..., :cfg.item_embed_dim]
             * user_feat[..., None, -cfg.item_embed_dim:])
    rscores = ranking.ranking_scores(params["rank"], cfg, user_feat,
                                     item_feat, hist_emb, cross)[task]
    rscores = constrain(rscores, mesh, batch_spec)
    rscores = jnp.where(valid, rscores, merge_sort.NEG)
    order = jnp.argsort(-rscores, axis=-1)
    return dict(
        item_ids=jnp.take_along_axis(cand_ids, order, axis=1),
        scores=jnp.take_along_axis(rscores, order, axis=1),
        merge_scores=s2["merge_scores"],
        exact_scores=s2["exact_scores"],
        index_ids=cand_ids,
        valid=jnp.take_along_axis(valid, order, axis=1))


def sharded_serve(params: Params, state: IndexState, cfg: SVQConfig,
                  sidx: ShardedServingIndex, batch: Dict[str, jax.Array],
                  items_per_cluster: int = 256, task: int = 0,
                  use_kernel: bool = False, fused: bool = False,
                  mesh: Optional[Mesh] = None,
                  rank_parallel: bool = False) -> Dict[str, jax.Array]:
    """Distributed two-step retrieval, bit-exact vs ``retriever.serve``.

    Composes the three stage functions (rank -> merge -> ranking); under
    one jit this traces exactly the pre-split op sequence.  ``fused``
    selects the slab-free merge+gather+rank stage; ``rank_parallel``
    batch-partitions stage 4b under its tolerance contract (see
    ``sharded_stage_ranking`` — bit-exactness then holds for stages
    1-3 only).
    """
    s1 = sharded_stage_rank(params, state, cfg, sidx, batch, task=task,
                            use_kernel=use_kernel, mesh=mesh)
    s2 = sharded_stage_merge(cfg, sidx, s1,
                             items_per_cluster=items_per_cluster,
                             use_kernel=use_kernel, fused=fused, mesh=mesh)
    return sharded_stage_ranking(params, cfg, s1, s2, task=task, mesh=mesh,
                                 rank_parallel=rank_parallel)
