"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def vq_assign_ref(v: jax.Array, e: jax.Array, r: jax.Array) -> jax.Array:
    """Eq. 10: argmin_k ||e_k - v||^2 * r_k.  v: (B,d), e: (K,d), r: (K,)."""
    v = v.astype(jnp.float32)
    e = e.astype(jnp.float32)
    d2 = (jnp.sum(v * v, axis=-1, keepdims=True)
          - 2.0 * v @ e.T
          + jnp.sum(e * e, axis=-1)[None, :])
    scores = jnp.maximum(d2, 0.0) * r[None, :]
    return jnp.argmin(scores, axis=-1).astype(jnp.int32)


def embedding_bag_ref(table: jax.Array, ids: jax.Array,
                      combiner: str = "sum") -> jax.Array:
    """ids: (B, bag) pre-hashed row indices -> (B, d)."""
    emb = jnp.take(table, ids, axis=0)
    s = jnp.sum(emb, axis=-2)
    if combiner == "mean":
        return s / ids.shape[-1]
    return s


def topk_dot_ref(u: jax.Array, items: jax.Array, bias: jax.Array,
                 k: int) -> Tuple[jax.Array, jax.Array]:
    """scores = items @ u + bias; -> (top-k values, indices)."""
    scores = items.astype(jnp.float32) @ u.astype(jnp.float32) \
        + bias.astype(jnp.float32)
    return jax.lax.top_k(scores, k)


def inbatch_softmax_ref(u: jax.Array, v: jax.Array, bias: jax.Array,
                        log_q: Optional[jax.Array] = None) -> jax.Array:
    """Per-row L_aux (Eq. 1 + Eq. 11 + logQ): (B,) losses."""
    logits = (u.astype(jnp.float32) @ v.astype(jnp.float32).T
              + bias.astype(jnp.float32)[None, :])
    if log_q is not None:
        logits = logits - log_q.astype(jnp.float32)[None, :]
    logz = jax.nn.logsumexp(logits, axis=-1)
    return logz - jnp.diagonal(logits)


def cluster_rank_ref(u: jax.Array, e: jax.Array, n: int
                     ) -> Tuple[jax.Array, jax.Array]:
    """Eq. 5/11 cluster ranking: top-n of u @ e.T per query row."""
    scores = u.astype(jnp.float32) @ e.astype(jnp.float32).T
    vals, idx = jax.lax.top_k(scores, n)
    return vals, idx.astype(jnp.int32)


def merge_serve_ref(cluster_scores: jax.Array, bias_lists: jax.Array,
                    lengths: jax.Array, chunk: int, target: int,
                    exact: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Batched Alg. 1 merge over (B, C, L) bias slabs, the Pallas
    ``merge_serve``'s input form (the pure-lax fallback
    ``core/retriever.serve_kernel`` dispatches to).

    Each query's slab is read as a flat array by the one lax merge,
    ``core/merge_sort.merge_sort_serve``: cluster c starts at ``c * L``
    and its reads are clamped to ``c * L + L - 1``."""
    from repro.core import merge_sort   # lazy: avoid core <-> kernels cycle
    L = bias_lists.shape[-1]
    starts = jnp.arange(bias_lists.shape[-2], dtype=jnp.int32) * L
    limits = starts + (L - 1)
    return jax.vmap(lambda cs, bl, ln: merge_sort.merge_sort_serve(
        cs, starts, ln, limits, bl.reshape(-1), chunk, target, L, exact))(
        cluster_scores, bias_lists, lengths)


def fused_gather_rank_ref(u: jax.Array, cluster_scores: jax.Array,
                          starts: jax.Array, lengths: jax.Array,
                          limits: jax.Array, bias_flat: jax.Array,
                          ids_flat: jax.Array, emb_flat: jax.Array,
                          chunk: int, target: int, l: int,
                          exact: bool = True):
    """Batched fused merge+gather+rank: vmapped lax.scan reference (the
    pure-lax fallback ``core/retriever.fused_gather_rank`` dispatches
    to).  Flat index arrays are closed over (shared by every query)."""
    from repro.core import merge_sort   # lazy: avoid core <-> kernels cycle
    return jax.vmap(lambda uu, cs, st, ln, lm:
                    merge_sort.fused_gather_rank_lax(
                        uu, cs, st, ln, lm, bias_flat, ids_flat, emb_flat,
                        chunk, target, l, exact))(
        u, cluster_scores, starts, lengths, limits)


def ema_segment_sum_ref(v: jax.Array, assignment: jax.Array,
                        weight: jax.Array, k: int
                        ) -> Tuple[jax.Array, jax.Array]:
    """Eq. 7-8 batch reductions: per-cluster weighted sums of the item
    embeddings and of the weights.  v: (B, d), assignment: (B,) int,
    weight: (B,) -> ((K, d) w_add, (K,) c_add)."""
    v32 = v.astype(jnp.float32)
    w32 = weight.astype(jnp.float32)
    w_add = jax.ops.segment_sum(w32[:, None] * v32, assignment, k)
    c_add = jax.ops.segment_sum(w32, assignment, k)
    return w_add, c_add


def index_sort_ref(cluster: jax.Array, bias: jax.Array) -> jax.Array:
    """Appendix-B index order: stable (cluster asc, bias desc) argsort.

    ``cluster`` must already have empty slots mapped to the sentinel id
    (n_clusters).  The two-key lexsort is the oracle the fused
    radix-key ``ops.index_sort`` must reproduce exactly.
    """
    return jnp.lexsort((-bias, cluster)).astype(jnp.int32)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True) -> jax.Array:
    """q,k,v: (S,hd) single head. -> (S,hd)."""
    s = q.shape[0]
    scale = q.shape[-1] ** -0.5
    logits = (q.astype(jnp.float32) @ k.astype(jnp.float32).T) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    return (w @ v.astype(jnp.float32)).astype(q.dtype)
