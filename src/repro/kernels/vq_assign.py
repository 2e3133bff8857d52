"""Pallas TPU kernel: disturbance-weighted nearest-cluster assignment.

The paper's Eq. 10 hot path: for each item embedding v, find
``argmin_k ||e_k - v||^2 * r_k`` over K = 16K-32K clusters.  Rewritten as
a (B, d) x (d, K) MXU matmul plus fused norm/disturbance epilogue with an
ONLINE (value, index) running minimum over K blocks — one pass over the
codebook, no (B, K) score matrix ever hits HBM (the same online-reduction
trick as flash attention).

VMEM working set per grid step (defaults bB=256, bK=512, d<=256 fp32):
  v tile 256x256x4 = 256 KiB, e tile 512x256x4 = 512 KiB,
  scores 256x512x4 = 512 KiB  -> ~1.3 MiB, comfortably inside 16 MiB VMEM,
with the (8,128)-aligned tile shapes the MXU wants.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _vq_assign_kernel(v_ref, e_ref, ee_ref, r_ref, idx_ref, val_ref,
                      *, bk: int):
    kt = pl.program_id(1)
    v = v_ref[...].astype(jnp.float32)                  # (bB, d)
    e = e_ref[...].astype(jnp.float32)                  # (bK, d)
    vv = jnp.sum(v * v, axis=-1, keepdims=True)         # (bB, 1)
    d2 = vv - 2.0 * jax.lax.dot_general(
        v, e, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) + ee_ref[...]   # (bB, bK) MXU
    scores = jnp.maximum(d2, 0.0) * r_ref[...]
    local_val = jnp.min(scores, axis=1, keepdims=True)  # (bB, 1)
    # first index attaining the min == jnp.argmin's tie-break
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    local_idx = jnp.min(jnp.where(scores == local_val, col, bk), axis=1,
                        keepdims=True) + kt * bk

    @pl.when(kt == 0)
    def _init():
        val_ref[...] = local_val
        idx_ref[...] = local_idx

    @pl.when(kt > 0)
    def _update():
        prev_val = val_ref[...]
        better = local_val < prev_val                   # strict: keeps
        val_ref[...] = jnp.where(better, local_val, prev_val)   # first-min
        idx_ref[...] = jnp.where(better, local_idx, idx_ref[...])


def vq_assign_pallas(v: jax.Array, e: jax.Array, r: jax.Array,
                     block_b: int = 256, block_k: int = 512,
                     interpret: bool = True) -> jax.Array:
    """v: (B, d), e: (K, d), r: (K,) -> assignment (B,) int32.

    B and K are padded to block multiples; padded clusters get r = +inf
    scores via a huge norm so they never win.  Per-cluster terms
    (||e_k||^2, r_k) enter as (1, K) rows and the running (min, argmin)
    leaves as (B, 1) columns, the layouts Mosaic tiles without relayout.
    """
    b, d = v.shape
    k = e.shape[0]
    pb = (-b) % block_b
    pk = (-k) % block_k
    if pb:
        v = jnp.pad(v, ((0, pb), (0, 0)))
    if pk:
        # padded clusters: enormous distance so they are never selected
        e = jnp.pad(e, ((0, pk), (0, 0)), constant_values=1e15)
        r = jnp.pad(r, (0, pk), constant_values=1.0)
    bp, kp = b + pb, k + pk
    e32 = e.astype(jnp.float32)
    ee = jnp.sum(e32 * e32, axis=-1)[None, :]            # (1, Kp)
    r = r.astype(jnp.float32)[None, :]                   # (1, Kp)

    grid = (bp // block_b, kp // block_k)
    idx, _ = pl.pallas_call(
        functools.partial(_vq_assign_kernel, bk=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_k, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_k), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_k), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, 1), jnp.int32),
            jax.ShapeDtypeStruct((bp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(v, e, ee, r)
    return idx[:b, 0]
