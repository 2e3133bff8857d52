"""Pallas TPU kernel: the train-step EMA codebook reductions (Eq. 7-9).

``vq.ema_update`` needs two scatter-adds per streaming batch:

    w_add[k] = sum_{j: a_j == k} weight_j * v_j        (K, d)
    c_add[k] = sum_{j: a_j == k} weight_j              (K,)

Scatter is the wrong shape for the TPU; the kernel instead tiles the
codebook into K-blocks (the parallel grid axis) and streams the batch
through VMEM in B-blocks (the reduction axis).  Each step expands the
block's assignment row into a (bK, bB) transposed one-hot and accumulates
``one_hot @ [weight * v | weight]`` on the MXU into a (bK, dp) output
block carried across the B steps — the standard segment-sum-as-matmul
trick.  ``c_add`` rides along as the extra ``weight`` column, so one
matmul yields both reductions; the wrapper slices them apart.

Summation ORDER differs from ``jax.ops.segment_sum`` (blocked matmul vs
sequential scatter), so parity vs ``ref.ema_segment_sum_ref`` is
allclose, not bitwise — same contract as the other reduction kernels.

Padding rows carry assignment == K and weight 0; one-hot rows at and
past K are sliced off, so padding contributes nothing.  The one-hot is a
(bK, bB) tile, never (B, K), so VMEM stays bounded at any codebook size.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ema_segment_kernel(x_ref, a_ref, out_ref, *, bk: int):
    kb = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...]                                       # (1, bB)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bk, a.shape[1]), 0)
    onehot = (rows + kb * bk == a).astype(jnp.float32)   # (bK, bB)
    out_ref[...] += jax.lax.dot_general(
        onehot, x_ref[...], (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)              # (bK, dp) MXU


def ema_segment_sum_pallas(v: jax.Array, assignment: jax.Array,
                           weight: jax.Array, k: int,
                           block_b: int = 256, block_k: int = 1024,
                           interpret: bool = True
                           ) -> Tuple[jax.Array, jax.Array]:
    """v: (B, d), assignment: (B,), weight: (B,) -> ((K, d), (K,))."""
    b, d = v.shape
    w32 = weight.astype(jnp.float32)
    # [weight * v | weight | 0...]: lane-dense, one matmul for both sums
    dp = -(-(d + 1) // 128) * 128
    x = jnp.concatenate([w32[:, None] * v.astype(jnp.float32),
                         w32[:, None],
                         jnp.zeros((b, dp - d - 1), jnp.float32)], axis=1)
    pb = (-b) % block_b
    a = assignment.astype(jnp.int32)
    if pb:
        x = jnp.pad(x, ((0, pb), (0, 0)))
        # padded rows: cluster K, which no kept one-hot row matches
        a = jnp.pad(a, (0, pb), constant_values=k)
    block_k = min(block_k, -(-k // 8) * 8)
    kp = -(-k // block_k) * block_k
    bp = b + pb

    out = pl.pallas_call(
        functools.partial(_ema_segment_kernel, bk=block_k),
        grid=(kp // block_k, bp // block_b),
        in_specs=[
            pl.BlockSpec((block_b, dp), lambda j, i: (i, 0)),
            pl.BlockSpec((1, block_b), lambda j, i: (0, i)),
        ],
        out_specs=pl.BlockSpec((block_k, dp), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((kp, dp), jnp.float32),
        interpret=interpret,
    )(x, a[None, :])
    return out[:k, :d], out[:k, d]
