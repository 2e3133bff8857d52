"""Pallas TPU kernel: fused EmbeddingBag (gather + bag reduce).

JAX has no native EmbeddingBag; the substrate version is
``jnp.take`` + sum (embedding.py).  This kernel is the TPU-native fused
form: bag indices ride the scalar-prefetch channel (SMEM), the table stays
in HBM (``pltpu.MemorySpace.ANY``), and each grid step DMAs exactly the
``bag`` rows a batch row needs into a VMEM scratch slab before one
vectorized reduce — the table is never densified or re-laid-out, so HBM
traffic is the optimal  B * bag * d * 4 bytes  of actual row payload.

Grid: one batch tile per step, double-buffer-friendly row DMAs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _embag_kernel(ids_ref, table_ref, out_ref, scratch, sem,
                  *, bb: int, bag: int):
    i = pl.program_id(0)

    def load_row(slot, row_idx):
        copy = pltpu.make_async_copy(
            table_ref.at[pl.ds(row_idx, 1), :],
            scratch.at[pl.ds(slot, 1), :],
            sem)
        copy.start()
        copy.wait()

    def body(b, _):
        base = i * bb + b

        def bag_body(t, _):
            load_row(t, ids_ref[base, t])
            return ()

        jax.lax.fori_loop(0, bag, bag_body, ())
        acc = jnp.sum(scratch[...].astype(jnp.float32), axis=0)
        out_ref[b, :] = acc.astype(out_ref.dtype)
        return ()

    jax.lax.fori_loop(0, bb, body, ())


def embedding_bag_pallas(table: jax.Array, ids: jax.Array,
                         combiner: str = "sum", block_b: int = 8,
                         interpret: bool = True) -> jax.Array:
    """table: (V, d); ids: (B, bag) pre-hashed row indices -> (B, d)."""
    b, bag = ids.shape
    v, d = table.shape
    pb = (-b) % block_b
    if pb:
        ids = jnp.pad(ids, ((0, pb), (0, 0)))
    bp = b + pb

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bp // block_b,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)],
        out_specs=pl.BlockSpec((block_b, d), lambda i, ids: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bag, d), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    out = pl.pallas_call(
        functools.partial(_embag_kernel, bb=block_b, bag=bag),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bp, d), jnp.float32),
        interpret=interpret,
    )(ids, table.astype(jnp.float32))
    out = out[:b]
    if combiner == "mean":
        out = out / bag
    return out
