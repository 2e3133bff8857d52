"""Pallas TPU kernels: fused in-batch softmax CE (L_aux / L_ind hot path).

Forward: per-row  logsumexp_r(u_o . v_r + bias_r - logQ_r) - logit_oo
without materializing the (B, B) logits matrix in HBM: the column axis is
blocked and reduced with the online-logsumexp recurrence; the diagonal
(positive) logit is captured when the row block meets the column block.
``return_stats=True`` additionally returns the online (m, l) carries, the
softmax statistics the flash-style backward recomputes blocks from.

Backward (flash-style, like attention's dq/dkv split): with
z = u v^T + bias - logq, p = softmax(z) rowwise and cotangent g,

    du_o    = g_o (sum_r p_or v_r - v_o)
    dv_r    = sum_o g_o p_or u_o  -  g_r u_r
    dbias_r = sum_o g_o p_or  -  g_r          (dlogq = -dbias)

Two kernels recompute z blockwise from the saved lse = m + log(l):
``_du_kernel`` accumulates the row-sums over column blocks (rows outer),
``_dv_kernel`` accumulates the column-sums over row blocks (cols outer).
The rank-deficient -g v / -g u / -g diagonal terms are cheap elementwise
corrections applied by the wrapper — the (B, B) probability matrix never
exists outside one (bB, bC) VMEM tile.

VMEM per step (bB=bC=256, d<=256): three 256 KiB tiles + 256 KiB logits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -1e30


def _inbatch_kernel(u_ref, v_ref, bias_ref, logq_ref,
                    loss_ref, m_ref, l_ref, diag_ref,
                    *, bb: int, bc: int, n_col: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    u = u_ref[...].astype(jnp.float32)                   # (bB, d)
    v = v_ref[...].astype(jnp.float32)                   # (bC, d)
    logits = jax.lax.dot_general(
        u, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # (bB, bC)
    logits = logits + bias_ref[...]                      # (1, bC) rows
    logits = logits - logq_ref[...]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full((bb, 1), NEG, jnp.float32)
        l_ref[...] = jnp.zeros((bb, 1), jnp.float32)
        diag_ref[...] = jnp.zeros((bb, 1), jnp.float32)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_blk = jnp.max(logits, axis=1, keepdims=True)       # (bB, 1)
    m_new = jnp.maximum(m_prev, m_blk)
    l_new = l_prev * jnp.exp(m_prev - m_new) \
        + jnp.sum(jnp.exp(logits - m_new), axis=1, keepdims=True)
    m_ref[...] = m_new
    l_ref[...] = l_new

    # diagonal capture: global row index == global col index
    rows = i * bb + jax.lax.broadcasted_iota(jnp.int32, (bb, bc), 0)
    cols = j * bc + jax.lax.broadcasted_iota(jnp.int32, (bb, bc), 1)
    diag_ref[...] = diag_ref[...] + jnp.sum(
        jnp.where(rows == cols, logits, 0.0), axis=1, keepdims=True)

    @pl.when(j == n_col - 1)
    def _finish():
        loss_ref[...] = m_ref[...] + jnp.log(l_ref[...]) - diag_ref[...]


def inbatch_softmax_pallas(u: jax.Array, v: jax.Array, bias: jax.Array,
                           log_q: jax.Array | None = None,
                           block_b: int = 256, block_c: int = 256,
                           interpret: bool = True,
                           return_stats: bool = False):
    """u: (B,d), v: (B,d), bias: (B,), log_q: (B,) -> per-row loss (B,).

    ``return_stats=True`` -> (loss, m, l): the online-logsumexp carries
    (lse = m + log l), saved by the custom_vjp forward for the
    flash-style backward.  Per-column terms enter as (1, B) rows and
    per-row results leave as (B, 1) columns."""
    b, d = u.shape
    if log_q is None:
        log_q = jnp.zeros((b,), jnp.float32)
    pb = (-b) % block_b
    pc = (-b) % block_c
    u_p = jnp.pad(u, ((0, pb), (0, 0)))
    # padded columns get -inf logits via huge logQ
    v_p = jnp.pad(v, ((0, pc), (0, 0)))
    bias_p = jnp.pad(bias, (0, pc))[None, :]
    logq_p = jnp.pad(log_q, (0, pc), constant_values=-NEG)[None, :]
    bp, cp = b + pb, b + pc
    grid = (bp // block_b, cp // block_c)
    row = pl.BlockSpec((1, block_c), lambda i, j: (0, j))
    col = pl.BlockSpec((block_b, 1), lambda i, j: (i, 0))

    out = pl.pallas_call(
        functools.partial(_inbatch_kernel, bb=block_b, bc=block_c,
                          n_col=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, d), lambda i, j: (j, 0)),
            row, row,
        ],
        out_specs=[col, col, col, col],
        out_shape=[jax.ShapeDtypeStruct((bp, 1), jnp.float32)] * 4,
        interpret=interpret,
    )(u_p, v_p, bias_p, logq_p)
    loss, m, l = (x[:b, 0] for x in out[:3])
    if return_stats:
        return loss, m, l
    return loss


# ---------------------------------------------------------------------------
# flash-style backward
# ---------------------------------------------------------------------------

def _du_kernel(u_ref, v_ref, bias_ref, logq_ref, lse_ref, acc_ref,
               *, n_col: int):
    j = pl.program_id(1)
    u = u_ref[...].astype(jnp.float32)                   # (bB, d)
    v = v_ref[...].astype(jnp.float32)                   # (bC, d)
    z = jax.lax.dot_general(
        u, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # (bB, bC)
    z = z + bias_ref[...] - logq_ref[...]
    p = jnp.exp(z - lse_ref[...])                        # lse: (bB, 1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # (bB, d)


def _dv_kernel(u_ref, v_ref, bias_ref, logq_ref, lse_ref, g_ref,
               dv_ref, db_ref, *, n_row: int):
    i = pl.program_id(1)
    u = u_ref[...].astype(jnp.float32)                   # (bB, d)
    v = v_ref[...].astype(jnp.float32)                   # (bC, d)
    z = jax.lax.dot_general(
        u, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # (bB, bC)
    z = z + bias_ref[...] - logq_ref[...]
    gp = g_ref[...] * jnp.exp(z - lse_ref[...])          # (bB, bC)

    @pl.when(i == 0)
    def _init():
        dv_ref[...] = jnp.zeros_like(dv_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    dv_ref[...] += jax.lax.dot_general(
        gp, u, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # (bC, d)
    db_ref[...] += jnp.sum(gp, axis=0, keepdims=True)    # (1, bC)


def inbatch_softmax_bwd_pallas(u: jax.Array, v: jax.Array, bias: jax.Array,
                               log_q: jax.Array, lse: jax.Array,
                               g: jax.Array, block_b: int = 256,
                               block_c: int = 256, interpret: bool = True):
    """Blocked VJP of the per-row in-batch CE.

    Inputs as the forward, plus lse = m + log(l) (the saved forward
    stats) and the per-row cotangent g.  Returns (du, dv, dbias, dlogq)
    in f32 — the custom_vjp wrapper casts back to input dtypes.
    """
    b, d = u.shape
    pb = (-b) % block_b
    pc = (-b) % block_c
    u_p = jnp.pad(u, ((0, pb), (0, 0)))
    # padded rows: lse=+huge makes every p row exp(z - huge) == 0
    lse_p = jnp.pad(lse, (0, pb), constant_values=-NEG)[:, None]
    g_p = jnp.pad(g, (0, pb))[:, None]
    # padded cols: huge logQ makes z == -huge, p == 0 (as in the fwd)
    v_p = jnp.pad(v, ((0, pc), (0, 0)))
    bias_p = jnp.pad(bias, (0, pc))[None, :]
    logq_p = jnp.pad(log_q, (0, pc), constant_values=-NEG)[None, :]
    bp, cp = b + pb, b + pc
    n_row, n_col = bp // block_b, cp // block_c

    # du: rows outer, accumulate sum_r p_or v_r over column blocks
    du_acc = pl.pallas_call(
        functools.partial(_du_kernel, n_col=n_col),
        grid=(n_row, n_col),
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
            pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, d), jnp.float32),
        interpret=interpret,
    )(u_p, v_p, bias_p, logq_p, lse_p)

    # dv/dbias: cols outer, accumulate sum_o g_o p_or (u_o | 1) over rows
    dv_acc, db_acc = pl.pallas_call(
        functools.partial(_dv_kernel, n_row=n_row),
        grid=(n_col, n_row),
        in_specs=[
            pl.BlockSpec((block_b, d), lambda j, i: (i, 0)),
            pl.BlockSpec((block_c, d), lambda j, i: (j, 0)),
            pl.BlockSpec((1, block_c), lambda j, i: (0, j)),
            pl.BlockSpec((1, block_c), lambda j, i: (0, j)),
            pl.BlockSpec((block_b, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda j, i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_c, d), lambda j, i: (j, 0)),
            pl.BlockSpec((1, block_c), lambda j, i: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((cp, d), jnp.float32),
            jax.ShapeDtypeStruct((1, cp), jnp.float32),
        ],
        interpret=interpret,
    )(u_p, v_p, bias_p, logq_p, lse_p, g_p)

    u32 = u.astype(jnp.float32)
    v32 = v.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    du = g32[:, None] * (du_acc[:b] - v32)     # -delta: p minus identity
    dv = dv_acc[:b] - g32[:, None] * u32
    dbias = db_acc[0, :b] - g32
    return du, dv, dbias, -dbias
