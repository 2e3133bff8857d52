"""Pallas TPU kernels for the fused serving path (§3.4, Alg. 1, Fig. 2).

Four kernels cover the latency-critical indexing step of serving:

cluster_rank — blocked cluster scoring + top-n over the codebook.  Eq. 5 /
    Eq. 11 ranks clusters by ``u . e_k``; instead of materializing the
    full (B, K) score matrix in HBM and running a global ``lax.top_k``,
    the codebook is streamed through VMEM in K-blocks and a running
    top-n carry in the output refs absorbs each block by n
    max-extractions over (carry U block) — Mosaic has no ``top_k``.
    Bitwise equal to ``lax.top_k`` over the kernel's own scores, ties
    included (the lower cluster index first).

merge_serve — batched k-way chunked merge (Alg. 1).  One grid step per
    query; the per-query head pointers live in registers as a
    ``fori_loop`` carry (the SMEM-resident analog of Alg. 1's heap), and
    the kernel emits top-S positions + combined scores in one pass with
    no intermediate round-trip to HBM.  Block mapping to Alg. 1:

      Alg. 1 line               kernel block
      -----------------------   -------------------------------------
      l1  heap <- cluster heads  ``head_b`` masked gather of
                                 ``bias[c, ptr[c]]`` + ``head_s`` score
      l2  pop max head           first lane holding ``max(head_s)``
                                 (== heap's smallest-cluster tie-break)
      l3  emit CHUNK items       masked column gather -> ``vals``; write
                                 row t of the (n_steps, chunk) emissions
      l4  advance head pointer   ``ptr[c] += chunk`` (loop carry)
      l5  re-push if non-empty   implicit: exhausted heads score NEG
      stop at S items            ``n_out`` carry gates validity

    ``exact=True`` budgets ceil(target/chunk) + C pops (identical to
    ``core.merge_sort.merge_sort_serve``), guaranteeing heap-oracle-
    identical output; the wrapper compacts the chunked emissions
    forward (stable) exactly like the lax.scan reference.

merge_serve_ds — the dynamic-slice variant of the same merge.  The
    original kernel's per-pop head/column gathers are iota-mask
    reductions (pure VPU selects/adds, but O(C·L) work per pop); this
    variant keeps a cached head-value carry and reads a (chunk, C)
    window and a (1, C) head row of the lists at dynamic sublane
    offsets, so per-pop work is O(C + chunk^2) regardless of L.
    Bit-identical outputs; both are benchmarked in bench_merge_sort.

fused_gather_rank — the whole serve() indexing hot path in ONE kernel:
    the k-way merge pops candidate positions AND consumes them in-kernel,
    fetching each pop's window of the flat serving-index arrays (bias /
    ids / personality embeddings) from HBM by DMA and scoring each
    candidate against the query (Eq. 11 exact score ``u . v_emb +
    v_bias``) as it is emitted.  The (B, C, L) bias slab and the
    (B, S, d) candidate embedding slab never materialize in HBM — the
    unfused path gathers both between `merge_serve` and the ranking
    step.  Per-lane addresses are ``min(start_c + idx, limit_c)`` — with
    ``limit = n_items - 1`` (plain) or ``owner*cap + cap - 1`` (sharded
    flat layout) this reproduces the unfused slab clamp bit-exactly, so
    pop order and all outputs match the unfused serve().  Each pop
    waits on its three DMAs before the next starts.

Layouts follow Mosaic's (8, 128) tiling: per-query inputs enter as
(B, 1, X) arrays whose (1, X) blocks equal the trailing dims, the merge
lists enter transposed (B, L, C) so clusters sit on lanes, and every
vector in a kernel body is rank 2.  With C=128, L=256 f32 a query's
lists are 128 KiB of VMEM.

The pure-lax fallbacks (``kernels/ref.py: merge_serve_ref`` /
``fused_gather_rank_ref``) vmap the ``lax.scan`` implementations;
``core/retriever.serve_kernel`` / ``retriever.fused_gather_rank`` are
the single dispatch points that pick Pallas vs fallback via
``use_kernel``.  ``kernels/ops.py`` compiles these with Mosaic on a TPU
and runs them in interpret mode on the CPU backend.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


# ---------------------------------------------------------------------------
# shared Mosaic idioms: every vector is rank 2 (rows on sublanes, clusters
# or candidates on lanes), and "gathers" are masked reductions
# ---------------------------------------------------------------------------

_BIG = jnp.iinfo(jnp.int32).max


def _first_max(x: jax.Array, lane: jax.Array, width: int
               ) -> Tuple[jax.Array, jax.Array]:
    """Row max of (R, W) ``x`` and the lowest lane attaining it, (R, 1)
    each: ``jnp.argmax``'s first-max tie-break without a 1-D argmax."""
    mx = jnp.max(x, axis=1, keepdims=True)
    return mx, jnp.min(jnp.where(x == mx, lane, width), axis=1,
                       keepdims=True)


def _pick(sel: jax.Array, x: jax.Array) -> jax.Array:
    """The lane of (1, C) ``x`` that one-hot ``sel`` marks, as (1, 1)."""
    return jnp.sum(jnp.where(sel, x, jnp.zeros_like(x)), axis=1,
                   keepdims=True)


def _col_to_row(col: jax.Array, n: int) -> jax.Array:
    """(n, 1) -> (1, n) through a diagonal select (no transpose op)."""
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, col, jnp.zeros_like(col)), axis=0,
                   keepdims=True)


def _compact(pos: jax.Array, *xs: jax.Array, target: int):
    """(B, n_steps, chunk) chunked emissions -> stable forward compaction
    of the valid lanes to (B, target), identical to the lax.scan form."""
    bsz = pos.shape[0]
    pos = pos.reshape(bsz, -1)
    order = jnp.argsort(pos < 0, axis=-1, stable=True)
    take = lambda x: jnp.take_along_axis(x.reshape(bsz, -1), order,
                                         axis=-1)[:, :target]
    return (take(pos),) + tuple(take(x) for x in xs)


def _row_spec(c: int) -> pl.BlockSpec:
    """One query's (1, c) row of a (B, 1, c) array."""
    return pl.BlockSpec((None, 1, c), lambda b: (b, 0, 0))


def _emit_spec(n_steps: int, chunk: int) -> pl.BlockSpec:
    """One query's (n_steps, chunk) emission block: pop t writes row t."""
    return pl.BlockSpec((None, n_steps, chunk), lambda b: (b, 0, 0))


# ---------------------------------------------------------------------------
# cluster_rank: blocked scoring + online top-n over the codebook
# ---------------------------------------------------------------------------

def _cluster_rank_kernel(u_ref, e_ref, mask_ref, val_ref, idx_ref,
                         *, bk: int, n: int):
    kt = pl.program_id(1)
    u = u_ref[...].astype(jnp.float32)                   # (bB, d)
    e = e_ref[...].astype(jnp.float32)                   # (bK, d)
    scores = jax.lax.dot_general(
        u, e, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # (bB, bK) MXU
    scores = scores + mask_ref[...]                      # NEG on padded K
    bb = scores.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (bb, bk), 1) + kt * bk
    lane_n = jax.lax.broadcasted_iota(jnp.int32, (bb, n), 1)

    @pl.when(kt == 0)
    def _init():
        val_ref[...] = jnp.full((bb, n), -jnp.inf, jnp.float32)
        idx_ref[...] = jnp.full((bb, n), _BIG, jnp.int32)

    # top-n of (carry U block) by n max-extractions.  The carry holds the
    # earlier blocks' top-n, whose indices are all below this block's, so
    # the lowest index among equal maxima is lax.top_k's tie-break.
    def extract(t, st):
        cv, ci, bv, out_v, out_i = st
        mx = jnp.maximum(jnp.max(cv, axis=1, keepdims=True),
                         jnp.max(bv, axis=1, keepdims=True))
        pick = jnp.minimum(
            jnp.min(jnp.where(cv == mx, ci, _BIG), axis=1, keepdims=True),
            jnp.min(jnp.where(bv == mx, col, _BIG), axis=1, keepdims=True))
        hit = lane_n == t
        return (jnp.where(ci == pick, -jnp.inf, cv), ci,
                jnp.where(col == pick, -jnp.inf, bv),
                jnp.where(hit, mx, out_v), jnp.where(hit, pick, out_i))

    carry_v, carry_i = val_ref[...], idx_ref[...]
    _, _, _, out_v, out_i = jax.lax.fori_loop(
        0, n, extract, (carry_v, carry_i, scores, carry_v, carry_i))
    val_ref[...] = out_v
    idx_ref[...] = out_i


def cluster_rank_pallas(u: jax.Array, e: jax.Array, n: int,
                        block_b: int = 128, block_k: int = 512,
                        interpret: bool = True
                        ) -> Tuple[jax.Array, jax.Array]:
    """u: (B, d), e: (K, d) -> (top-n scores (B, n), cluster ids (B, n))."""
    b, d = u.shape
    k = e.shape[0]
    if n > k:
        raise ValueError(f"top-n {n} exceeds codebook size {k}")
    block_k = max(block_k, n)           # local top-n needs n <= block
    pb = (-b) % block_b
    pk = (-k) % block_k
    if pb:
        u = jnp.pad(u, ((0, pb), (0, 0)))
    mask = jnp.zeros((1, k), jnp.float32)
    if pk:
        e = jnp.pad(e, ((0, pk), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, pk)), constant_values=NEG)
    bp, kp = b + pb, k + pk

    grid = (bp // block_b, kp // block_k)
    vals, idxs = pl.pallas_call(
        functools.partial(_cluster_rank_kernel, bk=block_k, n=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_k, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_k), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, n), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, n), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, n), jnp.float32),
            jax.ShapeDtypeStruct((bp, n), jnp.int32),
        ],
        interpret=interpret,
    )(u, e, mask)
    return vals[:b], idxs[:b]


# ---------------------------------------------------------------------------
# merge_serve: batched Alg. 1 k-way chunked merge
# ---------------------------------------------------------------------------

def _merge_serve_kernel(cs_ref, bl_ref, ln_ref, pos_ref, sc_ref,
                        *, c: int, l: int, chunk: int, target: int,
                        n_steps: int):
    cs = cs_ref[...].astype(jnp.float32)                 # (1, C)
    bl = bl_ref[...].astype(jnp.float32)                 # (L, C) lists
    ln = ln_ref[...]                                     # (1, C)
    lane_c = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
    row_l = jax.lax.broadcasted_iota(jnp.int32, (l, c), 0)
    lane_k = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    row_lk = jax.lax.broadcasted_iota(jnp.int32, (l, chunk), 0)
    lane_lk = jax.lax.broadcasted_iota(jnp.int32, (l, chunk), 1)

    def step(t, carry):
        ptr, n_out = carry                               # (1, C), (1, 1)
        # Alg. 1 l1: current head of every cluster list (masked gather)
        head_b = jnp.sum(jnp.where(row_l == ptr, bl, 0.0), axis=0,
                         keepdims=True)
        head_s = jnp.where(ptr < ln, cs + head_b, NEG)   # exhausted -> NEG
        # Alg. 1 l2: pop the max head (first-max == heap tie-break)
        mx, ci = _first_max(head_s, lane_c, c)
        sel = lane_c == ci
        base = _pick(sel, ptr)
        len_c = _pick(sel, ln)
        cs_c = _pick(sel, cs)
        # Alg. 1 l3: emit a CHUNK of the popped cluster's items
        col = jnp.sum(jnp.where(sel, bl, 0.0), axis=1, keepdims=True)
        vals = jnp.sum(jnp.where(row_lk == base + lane_lk, col, 0.0),
                       axis=0, keepdims=True)            # (1, chunk)
        idx = base + lane_k
        valid = (idx < len_c) & (mx > NEG / 2) & (n_out < target)
        pos_ref[pl.ds(t, 1), :] = jnp.where(valid, ci * l + idx, -1)
        sc_ref[pl.ds(t, 1), :] = jnp.where(valid, cs_c + vals, NEG)
        # Alg. 1 l4/l5: advance the popped head; re-push is implicit
        return (jnp.where(sel, ptr + chunk, ptr),
                n_out + jnp.sum(valid.astype(jnp.int32), axis=1,
                                keepdims=True))

    jax.lax.fori_loop(0, n_steps, step,
                      (jnp.zeros((1, c), jnp.int32),
                       jnp.zeros((1, 1), jnp.int32)))


def _merge_call(kernel, cluster_scores, lists_t, lengths, chunk, target,
                exact, interpret, **kw):
    """Shared pallas_call of the two merge kernels: one grid step per
    query over its (1, C) scores/lengths rows and (L, C) transposed lists
    (clusters on lanes), emitting (n_steps, chunk) pops."""
    bsz, c = cluster_scores.shape
    lp = lists_t.shape[1]
    n_steps = -(-target // chunk) + (c if exact else 0)
    emit = _emit_spec(n_steps, chunk)
    pos, sc = pl.pallas_call(
        functools.partial(kernel, c=c, chunk=chunk, target=target,
                          n_steps=n_steps, **kw),
        grid=(bsz,),
        in_specs=[_row_spec(c),
                  pl.BlockSpec((None, lp, c), lambda b: (b, 0, 0)),
                  _row_spec(c)],
        out_specs=[emit, emit],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, n_steps, chunk), jnp.int32),
            jax.ShapeDtypeStruct((bsz, n_steps, chunk), jnp.float32),
        ],
        interpret=interpret,
    )(cluster_scores[:, None, :], lists_t,
      lengths.astype(jnp.int32)[:, None, :])
    return _compact(pos, sc, target=target)


def merge_serve_pallas(cluster_scores: jax.Array, bias_lists: jax.Array,
                       lengths: jax.Array, chunk: int, target: int,
                       exact: bool = True, interpret: bool = True
                       ) -> Tuple[jax.Array, jax.Array]:
    """Batched Alg. 1: (B,C), (B,C,L), (B,C) -> ((B,target) pos, scores).

    Bit-identical to ``vmap(core.merge_sort.merge_sort_serve)`` (and, for
    ``exact=True``, to the numpy heap oracle): same pop order, same
    (-1, NEG) padding, same stable forward compaction.
    """
    l = bias_lists.shape[-1]
    return _merge_call(_merge_serve_kernel, cluster_scores,
                       jnp.swapaxes(bias_lists, 1, 2), lengths, chunk,
                       target, exact, interpret, l=l)


# ---------------------------------------------------------------------------
# merge_serve_ds: dynamic-slice pop loop (O(C + chunk^2) per pop)
# ---------------------------------------------------------------------------

def _merge_serve_ds_kernel(cs_ref, bl_ref, ln_ref, pos_ref, sc_ref,
                           *, c: int, l: int, lp: int, chunk: int,
                           target: int, n_steps: int):
    cs = cs_ref[...].astype(jnp.float32)                 # (1, C)
    ln = ln_ref[...]                                     # (1, C)
    lane_c = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
    lane_k = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    row_kk = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)

    def step(t, carry):
        ptr, head_b, n_out = carry
        head_s = jnp.where(ptr < ln, cs + head_b, NEG)
        mx, ci = _first_max(head_s, lane_c, c)
        sel = lane_c == ci
        base = _pick(sel, ptr)                           # (1, 1)
        len_c = _pick(sel, ln)
        cs_c = _pick(sel, cs)
        idx = base + lane_k
        # dynamic-slice window read (replaces the O(C*L) masked scan):
        # window start clamped so [w, w+chunk) stays inside the lists,
        # the popped cluster's lane and the window rows realigned by
        # one-hot selects
        b0 = jnp.sum(base)
        w = jnp.clip(b0, 0, lp - chunk)
        win = bl_ref[pl.ds(w, chunk), :].astype(jnp.float32)  # (chunk, C)
        col = jnp.sum(jnp.where(sel, win, 0.0), axis=1, keepdims=True)
        d = jnp.minimum(idx, l - 1) - w                  # (1, chunk)
        vals = jnp.sum(jnp.where(row_kk == d, col, 0.0), axis=0,
                       keepdims=True)
        valid = (idx < len_c) & (mx > NEG / 2) & (n_out < target)
        pos_ref[pl.ds(t, 1), :] = jnp.where(valid, ci * l + idx, -1)
        sc_ref[pl.ds(t, 1), :] = jnp.where(valid, cs_c + vals, NEG)
        # O(1) head refresh: only the popped cluster's head is re-read
        h = bl_ref[pl.ds(jnp.minimum(b0 + chunk, lp - 1), 1), :]
        return (jnp.where(sel, ptr + chunk, ptr),
                jnp.where(sel, h.astype(jnp.float32), head_b),
                n_out + jnp.sum(valid.astype(jnp.int32), axis=1,
                                keepdims=True))

    head0 = bl_ref[pl.ds(0, 1), :].astype(jnp.float32)  # ptr==0 everywhere
    jax.lax.fori_loop(0, n_steps, step,
                      (jnp.zeros((1, c), jnp.int32), head0,
                       jnp.zeros((1, 1), jnp.int32)))


def merge_serve_ds_pallas(cluster_scores: jax.Array, bias_lists: jax.Array,
                          lengths: jax.Array, chunk: int, target: int,
                          exact: bool = True, interpret: bool = True
                          ) -> Tuple[jax.Array, jax.Array]:
    """Dynamic-slice variant of ``merge_serve_pallas`` — same signature,
    bit-identical outputs, O(C + chunk^2) work per pop instead of O(C·L).
    """
    l = bias_lists.shape[-1]
    lp = max(l, chunk)          # window reads need L >= chunk
    if lp != l:
        bias_lists = jnp.pad(bias_lists, ((0, 0), (0, 0), (0, lp - l)))
    return _merge_call(_merge_serve_ds_kernel, cluster_scores,
                       jnp.swapaxes(bias_lists, 1, 2), lengths, chunk,
                       target, exact, interpret, l=l, lp=lp)


# ---------------------------------------------------------------------------
# fused_gather_rank: merge + DMA candidate gather + exact Eq. 11 scoring
# ---------------------------------------------------------------------------

_LANES = 128
_SUB = 8


def _fused_gather_rank_kernel(u_ref, cs_ref, st_ref, ln_ref, lim_ref,
                              h0_ref, idc_ref, bias_hbm, ids_hbm, emb_hbm,
                              pos_ref, sc_ref, id_ref, rk_ref,
                              bwin, iwin, ewin, sem,
                              *, c: int, l: int, chunk: int, target: int,
                              n_steps: int, dp: int):
    u = u_ref[...].astype(jnp.float32)                   # (1, 128) tiled
    cs = cs_ref[...].astype(jnp.float32)                 # (1, C)
    st = st_ref[...]                                     # (1, C) starts
    ln = ln_ref[...]                                     # (1, C)
    lim = lim_ref[...]                                   # (1, C) clamps
    id_clip = idc_ref[...]                               # (1, 1)
    n_rows, n_groups = bias_hbm.shape[0], emb_hbm.shape[0]
    ng = ewin.shape[0]
    per = _LANES // dp                   # embeddings per 128-lane row
    ipg = _SUB * per                     # embeddings per (8, 128) group
    lane_c = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
    row_k = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) // dp
    lane_e = jax.lax.broadcasted_iota(jnp.int32, (chunk, ng * _SUB), 1)
    lane_w = jax.lax.broadcasted_iota(jnp.int32, (chunk, _LANES), 1)
    lane_1w = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

    def window_at(win, off, lanes):
        """Values of the two-row (2, 128) window at flat offsets ``off``."""
        lo = jnp.where(lanes == off, win[0:1, :], jnp.zeros_like(win[0:1]))
        hi = jnp.where(lanes + _LANES == off, win[1:2, :],
                       jnp.zeros_like(win[1:2]))
        return jnp.sum(lo + hi, axis=1, keepdims=True)

    def step(t, carry):
        ptr, head_b, n_out = carry
        head_s = jnp.where(ptr < ln, cs + head_b, NEG)
        mx, ci = _first_max(head_s, lane_c, c)
        sel = lane_c == ci
        base = _pick(sel, ptr)                           # (1, 1)
        len_c = _pick(sel, ln)
        cs_c = _pick(sel, cs)
        st_c = _pick(sel, st)
        lim_c = _pick(sel, lim)
        # the chunk's flat window [w, w+chunk) covers every clamped lane.
        # bias/ids come by DMA as the two 128-lane rows holding it, the
        # embeddings as the (8, 128) groups holding it, all from HBM
        w = jnp.maximum(jnp.minimum(jnp.sum(st_c + base),
                                    jnp.sum(lim_c) - chunk + 1), 0)
        r0 = jnp.minimum(w // _LANES, n_rows - 2)
        g0 = jnp.minimum(w // ipg, n_groups - ng)
        copies = (pltpu.make_async_copy(bias_hbm.at[pl.ds(r0, 2)], bwin,
                                        sem.at[0]),
                  pltpu.make_async_copy(ids_hbm.at[pl.ds(r0, 2)], iwin,
                                        sem.at[1]),
                  pltpu.make_async_copy(emb_hbm.at[pl.ds(g0, ng)], ewin,
                                        sem.at[2]))
        for cp in copies:
            cp.start()
        for cp in copies:
            cp.wait()
        idx = base + row_k                               # (chunk, 1)
        tlane = jnp.minimum(st_c + idx, lim_c)           # clamped address
        off = tlane - r0 * _LANES
        bias_v = window_at(bwin[...].astype(jnp.float32), off, lane_w)
        ids_v = window_at(iwin[...], off, lane_w)
        # row r of the window holds embeddings r*per .. r*per+per-1, each
        # dp lanes wide: per-slot lane sums give their dots with u
        prod = ewin[...].astype(jnp.float32).reshape(ng * _SUB, _LANES) * u
        eo = tlane - g0 * ipg                            # (chunk, 1)
        dot_v = jnp.zeros((chunk, 1), jnp.float32)
        for p in range(per):
            dots = _col_to_row(jnp.sum(jnp.where(slot == p, prod, 0.0),
                                       axis=1, keepdims=True), ng * _SUB)
            dot_v += jnp.sum(jnp.where(lane_e * per + p == eo, dots, 0.0),
                             axis=1, keepdims=True)
        valid = (idx < len_c) & (mx > NEG / 2) & (n_out < target)
        out = (jnp.where(valid, ci * l + idx, -1),
               jnp.where(valid, cs_c + bias_v, NEG),
               jnp.where(valid, ids_v, id_clip),
               jnp.where(valid, dot_v + bias_v, NEG))
        for ref, col in zip((pos_ref, sc_ref, id_ref, rk_ref), out):
            ref[pl.ds(t, 1), :] = _col_to_row(col, chunk)
        # O(1) head refresh for the popped cluster; its address lies in
        # the same two-row window
        a = jnp.minimum(st_c + base + chunk, lim_c) - r0 * _LANES
        h = window_at(bwin[...].astype(jnp.float32), a, lane_1w)
        return (jnp.where(sel, ptr + chunk, ptr),
                jnp.where(sel, h, head_b),
                n_out + jnp.sum(valid.astype(jnp.int32), axis=0,
                                keepdims=True))

    jax.lax.fori_loop(0, n_steps, step,
                      (jnp.zeros((1, c), jnp.int32),
                       h0_ref[...].astype(jnp.float32),
                       jnp.zeros((1, 1), jnp.int32)))


def fused_gather_rank_pallas(u: jax.Array, cluster_scores: jax.Array,
                             starts: jax.Array, lengths: jax.Array,
                             limits: jax.Array, bias_flat: jax.Array,
                             ids_flat: jax.Array, emb_flat: jax.Array,
                             chunk: int, target: int, l: int,
                             exact: bool = True, interpret: bool = True
                             ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                        jax.Array]:
    """Fused Alg. 1 merge + candidate gather + exact Eq. 11 scoring.

    u: (B, d) queries; cluster_scores/starts/lengths/limits: (B, C) with
    ``starts`` flat addresses into the 1-D index arrays and ``limits``
    the per-lane clamp bound (``n_items - 1`` plain, shard-row end in
    the flattened sharded layout); bias_flat/ids_flat: (N,),
    emb_flat: (N, d).  ``l`` is the per-cluster slab width the flat
    positions are encoded against (``pos = c * l + idx``).

    Returns (pos, merge_scores, cand_ids, exact_scores), each
    (B, target).  pos/merge_scores are bit-identical to
    ``merge_serve_pallas`` over the equivalent slab; cand_ids is
    bit-identical to the unfused ``item_ids[slab-gather]`` (including
    the clip-to-first-slot semantics on invalid lanes); exact_scores is
    ``u . emb + bias`` on valid lanes and NEG elsewhere — the (B, C, L)
    bias slab and (B, S, d) embedding slab never round-trip HBM.
    """
    bsz, c = cluster_scores.shape
    n, dim = emb_flat.shape
    if chunk > _LANES:
        raise ValueError(f"chunk {chunk} exceeds one {_LANES}-lane row")
    n_steps = -(-target // chunk) + (c if exact else 0)
    starts = starts.astype(jnp.int32)
    limits = limits.astype(jnp.int32)
    # the heads every query starts from, and the id an invalid lane
    # reports (the unfused path clips pos to 0, i.e. reads cluster 0's
    # first slab slot), are plain XLA gathers
    first = jnp.minimum(starts, limits)
    head0 = bias_flat[first]
    id_clip = ids_flat[first[:, 0]].astype(jnp.int32)
    if dim > _LANES:
        raise ValueError(f"embedding width {dim} exceeds {_LANES} lanes")
    # HBM views the DMAs slice on their leading axis only, every one
    # lane-dense: bias/ids as (N/128, 128) rows, the embeddings (widths
    # padded to dp, a power of two) packed 128/dp to a row in (8, 128)
    # groups.  N is padded to whole groups of both.
    dp = pl.next_power_of_2(dim)
    ipg = _SUB * _LANES // dp
    ng = -(-chunk // ipg) + 1
    quantum = max(_LANES, ipg)
    np_ = max(-(-n // quantum) * quantum, ng * ipg, 2 * _LANES)
    rows = lambda x: jnp.pad(x, (0, np_ - n)).reshape(-1, _LANES)
    emb = jnp.pad(emb_flat, ((0, np_ - n), (0, dp - dim))).reshape(
        -1, _SUB, _LANES)
    u_lanes = jnp.tile(jnp.pad(u, ((0, 0), (0, dp - dim))),
                       (1, _LANES // dp))
    row3 = lambda x: x[:, None, :]
    emit = _emit_spec(n_steps, chunk)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    outs = pl.pallas_call(
        functools.partial(_fused_gather_rank_kernel, c=c, l=l,
                          chunk=chunk, target=target, n_steps=n_steps,
                          dp=dp),
        grid=(bsz,),
        in_specs=[_row_spec(_LANES)] + [_row_spec(c)] * 5 + [
            pl.BlockSpec((None, 1, 1), lambda b: (b, 0, 0)),
            hbm, hbm, hbm,
        ],
        out_specs=[emit] * 4,
        out_shape=[
            jax.ShapeDtypeStruct((bsz, n_steps, chunk), jnp.int32),
            jax.ShapeDtypeStruct((bsz, n_steps, chunk), jnp.float32),
            jax.ShapeDtypeStruct((bsz, n_steps, chunk), jnp.int32),
            jax.ShapeDtypeStruct((bsz, n_steps, chunk), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, _LANES), bias_flat.dtype),
            pltpu.VMEM((2, _LANES), jnp.int32),
            pltpu.VMEM((ng, _SUB, _LANES), emb_flat.dtype),
            pltpu.SemaphoreType.DMA((3,)),
        ],
        interpret=interpret,
    )(row3(u_lanes), row3(cluster_scores), row3(starts),
      row3(lengths.astype(jnp.int32)), row3(limits), row3(head0),
      id_clip[:, None, None], rows(bias_flat),
      rows(ids_flat.astype(jnp.int32)), emb)
    return _compact(*outs, target=target)
