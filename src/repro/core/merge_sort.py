"""K-way chunked merge-sort serving (paper §3.4, Alg. 1, Fig. 2).

Per query: the indexing step scores clusters by u.Q(v_emb); items inside a
cluster share that personality score and are pre-ranked by their
popularity bias (serving index keeps segments sorted by bias desc).  The
combined score is  u.Q(v_emb) + v_bias  (Eq. 11), so each cluster's list
is already sorted by combined score, and selecting the global top-S is a
k-way merge.  Alg. 1 pops the max-head cluster and takes a whole CHUNK
(size l=8) of its items per pop ("we can stand some mistakes").

TPU adaptation (DESIGN.md §3): a binary heap is pointer-chasing and
serial; but a heap-pop is just argmax over the C head scores (C =
clusters_per_query, e.g. 128).  We implement Alg. 1 as a lax.scan of S/l
steps, each doing an argmax over C head scores carried in the scan's
state: a pop reads its chunk and the popped cluster's next head, and
refreshes that one head, so no step re-reads the other C - 1 heads.
Pops read the index's flat bias array at each cluster's offset, so no
(C, L) copy of the probed lists is built.
Bit-identical pop order to the heap (ties go to the lowest cluster, as
the heap's (-score, cluster) order), fully vectorizable and vmappable
over queries.  A numpy heapq oracle is kept for verification and the
merge-sort benchmark.
"""
from __future__ import annotations

import heapq
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30


def merge_sort_serve_np(cluster_scores: np.ndarray,
                        bias_lists: np.ndarray,
                        lengths: np.ndarray,
                        chunk: int,
                        target: int) -> Tuple[np.ndarray, np.ndarray]:
    """Faithful Alg. 1 with a real heap.

    cluster_scores: (C,) personality score per selected cluster.
    bias_lists: (C, L) per-cluster item biases sorted desc (padded).
    lengths: (C,) valid lengths.
    Returns (flat_positions, combined_scores) of <= target items; positions
    are c * L + i.
    """
    C, L = bias_lists.shape
    heap = []  # (-score, cluster, ptr)
    ptr = np.zeros(C, np.int64)
    for c in range(C):
        if lengths[c] > 0:
            heapq.heappush(
                heap, (-(cluster_scores[c] + bias_lists[c, 0]), c))
    out_pos, out_score = [], []
    while heap and len(out_pos) < target:
        _, c = heapq.heappop(heap)
        take = min(chunk, int(lengths[c]) - int(ptr[c]))
        for i in range(int(ptr[c]), int(ptr[c]) + take):
            out_pos.append(c * L + i)
            out_score.append(cluster_scores[c] + bias_lists[c, i])
        ptr[c] += take
        if ptr[c] < lengths[c]:
            heapq.heappush(
                heap, (-(cluster_scores[c] + bias_lists[c, ptr[c]]), c))
    out_pos = np.asarray(out_pos[:target], np.int64)
    out_score = np.asarray(out_score[:target], np.float64)
    return out_pos, out_score


@partial(jax.jit, static_argnames=("chunk", "target", "l", "exact"))
def merge_sort_serve(cluster_scores: jax.Array,
                     starts: jax.Array,
                     lengths: jax.Array,
                     limits: jax.Array,
                     bias_flat: jax.Array,
                     chunk: int,
                     target: int,
                     l: int,
                     exact: bool = True) -> Tuple[jax.Array, jax.Array]:
    """TPU-native Alg. 1 over a flat index: scan of (argmax over heads,
    take chunk).

    cluster_scores/starts/lengths/limits: (C,) with ``starts[c]`` the
    flat address of cluster c's first item and ``limits[c]`` the
    address its reads are clamped to; bias_flat: (N,).  Item i of
    cluster c is ``bias_flat[min(starts[c] + i, limits[c])]``.  Returns
    (positions, scores), (target,) each, padded with (-1, NEG) if fewer
    than ``target`` items exist; positions are encoded ``c * l + i`` as
    in the numpy oracle over an (C, l) list.  vmap over the leading axis
    for batched queries, closing over ``bias_flat`` where the queries
    share one index.

    A (C, L) slab, the Pallas ``merge_serve``'s input form, is the flat
    array ``slab.reshape(-1)`` with ``starts = c * L`` and ``limits =
    c * L + L - 1`` (``kernels/ref.py: merge_serve_ref``).

    The scan carries each cluster's pointer ``ptr`` and head bias
    ``head_b`` (its item ``ptr[c]``, clamped).  A pop of cluster ``c``
    reads its chunk and its next head, ``chunk + 1`` values from
    ``starts[c] + ptr[c]`` on, and refreshes ``head_b[c]`` alone: per
    pop the index is read at ``chunk + 1`` places, not at every
    cluster's head.

    ``exact=True`` budgets ceil(target/chunk) + C pops (each pop either
    yields a full chunk or exhausts one of the C clusters, so this bound
    guarantees heap-oracle-identical output); ``exact=False`` budgets only
    ceil(target/chunk) pops -- cheaper, may under-fill when many clusters
    hold < chunk items.
    """
    C = cluster_scores.shape[0]
    n_steps = -(-target // chunk) + (C if exact else 0)
    offsets = jnp.arange(chunk + 1)
    iota_c = jnp.arange(C)
    # a pop reads its cluster's start, limit and length as one row
    rows = jnp.stack([starts, limits, lengths], axis=-1)      # (C, 3)

    def step(carry, _):
        ptr, head_b, n_out = carry
        scores = jnp.where(ptr < lengths, cluster_scores + head_b, NEG)
        c = jnp.argmax(scores)
        hit = iota_c == c
        base = ptr[c]
        start, limit, length = rows[c]
        # the chunk and, in its last slot, the popped cluster's next head
        idx = base + offsets
        vals = bias_flat[jnp.minimum(start + idx, limit)]
        idx = idx[:chunk]
        valid = ((idx < length) & (scores[c] > NEG / 2)
                 & (n_out < target))
        pos = jnp.where(valid, c * l + idx, -1)
        sc = jnp.where(valid, cluster_scores[c] + vals[:chunk], NEG)
        ptr = jnp.where(hit, ptr + chunk, ptr)
        head_b = jnp.where(hit, vals[chunk], head_b)
        return (ptr, head_b, n_out + jnp.sum(valid)), (pos, sc)

    ptr0 = jnp.zeros((C,), jnp.int32)
    head0 = bias_flat[jnp.minimum(starts, limits)]
    _, (pos, sc) = jax.lax.scan(step, (ptr0, head0, jnp.int32(0)),
                                None, length=n_steps)
    pos, sc = pos.reshape(-1), sc.reshape(-1)
    # Compact valid entries forward, preserving pop order (matches the
    # heap oracle's contiguous output even when chunks were partial).
    order = jnp.argsort(pos < 0, stable=True)
    return pos[order][:target], sc[order][:target]


@partial(jax.jit, static_argnames=("chunk", "target", "l", "exact"))
def fused_gather_rank_lax(u: jax.Array, cluster_scores: jax.Array,
                          starts: jax.Array, lengths: jax.Array,
                          limits: jax.Array, bias_flat: jax.Array,
                          ids_flat: jax.Array, emb_flat: jax.Array,
                          chunk: int, target: int, l: int,
                          exact: bool = True
                          ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                     jax.Array]:
    """Single-query fused Alg. 1: merge + candidate gather + Eq. 11 score.

    The lax counterpart of ``kernels.merge_serve.fused_gather_rank_pallas``
    (vmap over queries via ``kernels/ref.py: fused_gather_rank_ref``):
    instead of materializing the (C, L) bias slab and re-gathering the
    (target, d) candidate embeddings afterwards, each pop dynamically
    gathers its chunk straight from the flat index arrays and scores it
    against ``u`` in place.  Heads are maintained incrementally — one
    O(1) refresh per pop — so per-pop work is O(C) select + O(chunk·d).

    u: (d,); cluster_scores/starts/lengths/limits: (C,) with ``starts``
    flat addresses and ``limits`` the per-lane clamp bound;
    bias_flat/ids_flat: (N,); emb_flat: (N, d).  Returns
    (pos, merge_scores, cand_ids, exact_scores), each (target,), with
    pos encoded ``c * l + idx`` like ``merge_sort_serve``, whose
    addressing this is, with the id and embedding work added.
    """
    C = cluster_scores.shape[0]
    n_steps = -(-target // chunk) + (C if exact else 0)
    ar = jnp.arange(chunk, dtype=jnp.int32)
    iota_c = jnp.arange(C, dtype=jnp.int32)
    starts = starts.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    limits = limits.astype(jnp.int32)
    cs32 = cluster_scores.astype(jnp.float32)
    u32 = u.astype(jnp.float32)
    head0 = bias_flat[jnp.minimum(starts, limits)].astype(jnp.float32)
    # invalid lanes report the clip-to-first-slot id, like the unfused
    # ``item_ids[slab[clip(pos, 0)]]`` gather
    id_clip = ids_flat[jnp.minimum(starts[0], limits[0])]

    def step(carry, _):
        ptr, head_b, n_out = carry
        head_s = jnp.where(ptr < lengths, cs32 + head_b, NEG)
        ci = jnp.argmax(head_s)
        base = ptr[ci]
        idx = base + ar
        addr = jnp.minimum(starts[ci] + idx, limits[ci])
        bias_v = bias_flat[addr].astype(jnp.float32)
        dot_v = emb_flat[addr].astype(jnp.float32) @ u32
        valid = ((idx < lengths[ci]) & (head_s[ci] > NEG / 2)
                 & (n_out < target))
        pos = jnp.where(valid, ci * l + idx, -1)
        sc = jnp.where(valid, cs32[ci] + bias_v, NEG)
        ids = jnp.where(valid, ids_flat[addr], id_clip)
        rk = jnp.where(valid, dot_v + bias_v, NEG)
        new_head = bias_flat[jnp.minimum(starts[ci] + base + chunk,
                                         limits[ci])].astype(jnp.float32)
        head_b = jnp.where(iota_c == ci, new_head, head_b)
        return ((ptr.at[ci].add(chunk), head_b, n_out + jnp.sum(valid)),
                (pos, sc, ids, rk))

    ptr0 = jnp.zeros((C,), jnp.int32)
    _, (pos, sc, ids, rk) = jax.lax.scan(
        step, (ptr0, head0, jnp.int32(0)), None, length=n_steps)
    pos, sc = pos.reshape(-1), sc.reshape(-1)
    ids, rk = ids.reshape(-1), rk.reshape(-1)
    order = jnp.argsort(pos < 0, stable=True)
    return (pos[order][:target], sc[order][:target],
            ids[order][:target], rk[order][:target])


def full_sort_topk(cluster_scores: jax.Array, bias_lists: jax.Array,
                   lengths: jax.Array, target: int
                   ) -> Tuple[jax.Array, jax.Array]:
    """Exact top-``target`` over all (cluster, item) pairs (quality ref)."""
    C, L = bias_lists.shape
    combined = cluster_scores[:, None] + bias_lists
    mask = jnp.arange(L)[None, :] < lengths[:, None]
    flat = jnp.where(mask, combined, NEG).reshape(-1)
    sc, pos = jax.lax.top_k(flat, target)
    return jnp.where(sc > NEG / 2, pos, -1), sc
