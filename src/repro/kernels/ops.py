"""Jit'd public wrappers for the Pallas kernels.

On a TPU backend the kernels compile with Mosaic.  On the CPU backend
(``JAX_PLATFORMS=cpu``, the test suite) they run in interpret mode, which
executes the kernel body with XLA ops and is what the per-kernel parity
tests check against ref.py.  Any other backend raises: a kernel never
falls back to interpret mode or to its lax path silently.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.inbatch_softmax import (inbatch_softmax_bwd_pallas,
                                           inbatch_softmax_pallas)
from repro.kernels.merge_serve import (cluster_rank_pallas,
                                       fused_gather_rank_pallas,
                                       merge_serve_ds_pallas,
                                       merge_serve_pallas)
from repro.kernels.topk_dot import topk_dot_pallas
from repro.kernels.vq_assign import vq_assign_pallas
from repro.kernels.vq_ema import ema_segment_sum_pallas


def _interpret() -> bool:
    """False on a TPU (Mosaic), True on the CPU backend, else an error."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"Pallas kernels run on a TPU, or in interpret "
                           f"mode on the CPU backend; not on {backend!r}")
    return backend == "cpu"


@partial(jax.jit, static_argnames=("block_b", "block_k"))
def vq_assign(v: jax.Array, e: jax.Array, r: jax.Array,
              block_b: int = 256, block_k: int = 512) -> jax.Array:
    return vq_assign_pallas(v, e, r, block_b, block_k,
                            interpret=_interpret())


@partial(jax.jit, static_argnames=("combiner", "block_b"))
def embedding_bag(table: jax.Array, ids: jax.Array, combiner: str = "sum",
                  block_b: int = 8) -> jax.Array:
    return embedding_bag_pallas(table, ids, combiner, block_b,
                                interpret=_interpret())


@partial(jax.jit, static_argnames=("k", "block_n"))
def topk_dot(u: jax.Array, items: jax.Array, bias: jax.Array, k: int,
             block_n: int = 4096) -> Tuple[jax.Array, jax.Array]:
    return topk_dot_pallas(u, items, bias, k, block_n,
                           interpret=_interpret())


@partial(jax.jit, static_argnames=("n", "block_b", "block_k"))
def cluster_rank(u: jax.Array, e: jax.Array, n: int,
                 block_b: int = 128, block_k: int = 512
                 ) -> Tuple[jax.Array, jax.Array]:
    return cluster_rank_pallas(u, e, n, block_b, block_k,
                               interpret=_interpret())


@partial(jax.jit, static_argnames=("chunk", "target", "exact"))
def merge_serve(cluster_scores: jax.Array, bias_lists: jax.Array,
                lengths: jax.Array, chunk: int, target: int,
                exact: bool = True) -> Tuple[jax.Array, jax.Array]:
    return merge_serve_pallas(cluster_scores, bias_lists, lengths,
                              chunk, target, exact,
                              interpret=_interpret())


@partial(jax.jit, static_argnames=("chunk", "target", "exact"))
def merge_serve_ds(cluster_scores: jax.Array, bias_lists: jax.Array,
                   lengths: jax.Array, chunk: int, target: int,
                   exact: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Dynamic-slice pop-loop variant of ``merge_serve`` (bit-identical;
    O(C + chunk^2) per pop instead of O(C·L))."""
    return merge_serve_ds_pallas(cluster_scores, bias_lists, lengths,
                                 chunk, target, exact,
                                 interpret=_interpret())


@partial(jax.jit, static_argnames=("chunk", "target", "l", "exact"))
def fused_gather_rank(u: jax.Array, cluster_scores: jax.Array,
                      starts: jax.Array, lengths: jax.Array,
                      limits: jax.Array, bias_flat: jax.Array,
                      ids_flat: jax.Array, emb_flat: jax.Array,
                      chunk: int, target: int, l: int, exact: bool = True
                      ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                 jax.Array]:
    """Fused serve hot path: Alg. 1 merge + in-kernel ``pl.ds`` candidate
    gathers + exact Eq. 11 scoring, no (B, C, L) / (B, S, d) slab in
    HBM.  -> (pos, merge_scores, cand_ids, exact_scores)."""
    return fused_gather_rank_pallas(u, cluster_scores, starts, lengths,
                                    limits, bias_flat, ids_flat, emb_flat,
                                    chunk, target, l, exact,
                                    interpret=_interpret())


@partial(jax.jit, static_argnames=("k", "block_b"))
def ema_segment_sum(v: jax.Array, assignment: jax.Array, weight: jax.Array,
                    k: int, block_b: int = 256
                    ) -> Tuple[jax.Array, jax.Array]:
    """Eq. 7-8 EMA batch reductions as a blocked one-hot matmul."""
    return ema_segment_sum_pallas(v, assignment, weight, k, block_b,
                                  interpret=_interpret())


@jax.jit
def index_sort(cluster: jax.Array, bias: jax.Array) -> jax.Array:
    """Fused (cluster asc, bias desc) order via ONE integer-key sort.

    The lexsort oracle compares a float key with total-order semantics;
    here the bias is bit-mapped to a monotone uint32 (sign-flip trick,
    then inverted for descending) so the whole order is a single
    two-integer-key ``lax.sort`` — integer comparators, radix-friendly
    on TPU, and no float total-order special cases in the hot loop.
    Bit-identical to ``ref.index_sort_ref`` (ties keep submission
    order; +/-0.0 are collapsed to preserve the IEEE-equality tie
    behavior of lexsort, and NaN biases take the largest descending
    key so they land LAST in their segment, like numpy sorts them).
    """
    bias = jnp.where(bias == 0.0, jnp.float32(0.0), bias.astype(jnp.float32))
    b = jax.lax.bitcast_convert_type(bias, jnp.uint32)
    asc = jnp.where((b >> 31) == 1, ~b, b | jnp.uint32(0x80000000))
    desc = jnp.where(jnp.isnan(bias), jnp.uint32(0xFFFFFFFF), ~asc)
    iota = jnp.arange(cluster.shape[0], dtype=jnp.int32)
    return jax.lax.sort((cluster.astype(jnp.int32), desc, iota),
                        num_keys=2, is_stable=True)[2]


@partial(jax.jit, static_argnames=("causal", "block_q", "block_kv"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block_q: int = 256,
                    block_kv: int = 256) -> jax.Array:
    return flash_attention_pallas(q, k, v, causal, block_q, block_kv,
                                  interpret=_interpret())


@partial(jax.jit, static_argnames=("block_b", "block_c"))
def inbatch_softmax(u: jax.Array, v: jax.Array, bias: jax.Array,
                    log_q: Optional[jax.Array] = None,
                    block_b: int = 256, block_c: int = 256) -> jax.Array:
    return inbatch_softmax_pallas(u, v, bias, log_q, block_b, block_c,
                                  interpret=_interpret())


@partial(jax.jit, static_argnames=("block_b", "block_c"))
def inbatch_softmax_stats(u: jax.Array, v: jax.Array, bias: jax.Array,
                          log_q: Optional[jax.Array] = None,
                          block_b: int = 256, block_c: int = 256
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Forward that also returns the online (m, l) softmax stats the
    flash-style backward recomputes logits blocks from."""
    return inbatch_softmax_pallas(u, v, bias, log_q, block_b, block_c,
                                  interpret=_interpret(),
                                  return_stats=True)


@partial(jax.jit, static_argnames=("block_b", "block_c"))
def inbatch_softmax_bwd(u: jax.Array, v: jax.Array, bias: jax.Array,
                        log_q: jax.Array, lse: jax.Array, g: jax.Array,
                        block_b: int = 256, block_c: int = 256):
    """Blocked VJP of the in-batch CE -> (du, dv, dbias, dlogq)."""
    return inbatch_softmax_bwd_pallas(u, v, bias, log_q, lse, g,
                                      block_b, block_c,
                                      interpret=_interpret())
