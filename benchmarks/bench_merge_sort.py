"""§3.4 / Alg. 1 — merge-sort serving: cost and quality vs full sort.

Chunk-size sweep (1/4/8/16): larger chunks cut pops (cost) at a small
quality loss ('we can stand some mistakes'), exactly Fig. 2's trade-off.
Compares the Alg. 1 implementations — python heap oracle, the lax.scan
TPU form, the fused Pallas merge_serve kernel and its dynamic-slice pop
variant (``merge_serve_ds``), plus the FUSED gather+rank serve stage:
the lax fused pipeline (merge + per-pop candidate gather + exact Eq. 11
dot, no (C, L) slab or (S, d) re-gather) vs the unfused slab pipeline,
and the Pallas ``fused_gather_rank`` kernel.  Off TPU the Pallas rows
run in interpret mode, so their wall time measures the Python
interpreter, NOT the kernel — those rows are correctness-only; the
speed claim for the fused stage is carried by the lax-vs-lax pair.
Results land in ``BENCH_merge_serve.json`` at the repo root.
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import out_json, sz, timed
from repro.core import merge_sort
from repro.kernels import ops, ref

C, L, TARGET = sz(64, 8), sz(256, 32), sz(512, 48)
B = sz(8, 2)                           # batched comparison width
D_EMB = sz(32, 8)                      # fused-stage embedding dim
OUT_JSON = out_json("BENCH_merge_serve.json")


def _flat_index(rng, bl):
    """Flat (N,) index arrays matching the (C, L) bias slab layout."""
    n = C * L
    bias_flat = jnp.asarray(bl.reshape(-1))
    ids_flat = jnp.arange(n, dtype=jnp.int32)
    emb_flat = jnp.asarray(rng.normal(size=(n, D_EMB)).astype(np.float32))
    return bias_flat, ids_flat, emb_flat


def _unfused_pipeline(u, cs, starts, ln, bias_flat, ids_flat, emb_flat):
    """The slab path the fused stage replaces: (B, C, L) bias slab
    materialization + merge + flat/id gathers + (B, S, d) exact einsum."""
    n = bias_flat.shape[0]
    slab = jnp.minimum(starts[..., None] + jnp.arange(L)[None, None, :],
                       n - 1)                                # (B, C, L)
    bias = bias_flat[slab]
    pos, sc = ref.merge_serve_ref(cs, bias, ln, 8, TARGET)
    valid = pos >= 0
    flat = jnp.take_along_axis(
        slab.reshape(slab.shape[0], -1),
        (jnp.clip(pos, 0)).astype(jnp.int32), axis=1)        # (B, S)
    ids = ids_flat[flat]
    rk = jnp.where(valid,
                   jnp.einsum("bsd,bd->bs", emb_flat[flat], u)
                   + bias_flat[flat], merge_sort.NEG)
    return pos, sc, jnp.where(valid, ids, ids_flat[flat]), rk


def run() -> list:
    rng = np.random.default_rng(3)
    cs = rng.normal(size=(C,)).astype(np.float32)
    bl = -np.sort(-rng.normal(size=(C, L)).astype(np.float32), axis=1)
    ln = rng.integers(L // 2, L + 1, size=(C,)).astype(np.int32)
    jcs, jbl, jln = map(jnp.asarray, (cs, bl, ln))
    pos_exact, _ = merge_sort.full_sort_topk(jcs, jbl, jln, TARGET)
    want = set(np.asarray(pos_exact)[np.asarray(pos_exact) >= 0].tolist())
    rows = []
    record = {"shape": dict(C=C, L=L, target=TARGET, batch=B, d=D_EMB),
              "backend": jax.default_backend(), "rows": {}}
    for chunk in (1, 4, 8, 16):
        fn = jax.jit(lambda a, b, c, ch=chunk: ref.merge_serve_ref(
            a[None], b[None], c[None], ch, TARGET)[0])
        us, (pos, _) = timed(fn, jcs, jbl, jln, n=5)
        got = set(np.asarray(pos)[np.asarray(pos) >= 0].tolist())
        overlap = len(got & want) / max(len(want), 1)
        rows.append((f"merge_sort/chunk{chunk}_us", round(us, 1),
                     f"overlap_vs_exact={overlap:.4f}"))
        record["rows"][f"lax_scan_chunk{chunk}_us"] = round(us, 1)
        record["rows"][f"lax_scan_chunk{chunk}_overlap"] = round(overlap,
                                                                 4)
    # heap oracle (python) timing for context
    t0 = time.perf_counter()
    merge_sort.merge_sort_serve_np(cs, bl, ln, 8, TARGET)
    heap_us = round((time.perf_counter() - t0) * 1e6, 1)
    rows.append(("merge_sort/python_heap_us", heap_us,
                 "faithful Alg. 1 reference"))
    record["rows"]["python_heap_us"] = heap_us
    us_full, _ = timed(jax.jit(
        lambda a, b, c: merge_sort.full_sort_topk(a, b, c, TARGET)),
        jcs, jbl, jln, n=5)
    rows.append(("merge_sort/full_sort_us", round(us_full, 1),
                 "exact top-k over all pairs"))
    record["rows"]["full_sort_us"] = round(us_full, 1)

    # ---- batched lax-scan vs Pallas kernels (chunk=8) ------------------
    bcs = jnp.asarray(rng.normal(size=(B, C)).astype(np.float32))
    bbl = jnp.asarray(-np.sort(
        -rng.normal(size=(B, C, L)).astype(np.float32), axis=-1))
    bln = jnp.asarray(rng.integers(L // 2, L + 1, (B, C))
                      .astype(np.int32))
    scan_fn = jax.jit(
        lambda a, b, c: ref.merge_serve_ref(a, b, c, 8, TARGET))
    us_scan, (pos_s, sc_s) = timed(scan_fn, bcs, bbl, bln, n=3)
    rows.append((f"merge_sort/lax_scan_B{B}_us", round(us_scan, 1),
                 "vmapped scan, chunk=8"))
    record["rows"][f"lax_scan_B{B}_us"] = round(us_scan, 1)
    on_tpu = jax.default_backend() == "tpu"
    mode = "native" if on_tpu else "interpret"
    us_pal, (pos_p, sc_p) = timed(
        lambda a, b, c: ops.merge_serve(a, b, c, 8, TARGET),
        bcs, bbl, bln, n=3)
    parity = bool(jnp.all(pos_s == pos_p) and jnp.all(sc_s == sc_p))
    rows.append((f"merge_sort/pallas_B{B}_us", round(us_pal, 1),
                 f"fused kernel ({mode}), bit_parity={parity}"))
    record["rows"][f"pallas_B{B}_us"] = round(us_pal, 1)
    record["rows"]["pallas_interpret_mode"] = not on_tpu
    record["rows"]["pallas_bit_parity_vs_lax_scan"] = parity
    rows.append(("merge_sort/pallas_bit_parity", None, parity))

    # dynamic-slice pop-loop variant: O(C + chunk^2) per pop vs the
    # O(C*L) masked scan of the original kernel (same outputs)
    us_ds, (pos_d, sc_d) = timed(
        lambda a, b, c: ops.merge_serve_ds(a, b, c, 8, TARGET),
        bcs, bbl, bln, n=3)
    parity_ds = bool(jnp.all(pos_s == pos_d) and jnp.all(sc_s == sc_d))
    rows.append((f"merge_sort/pallas_ds_B{B}_us", round(us_ds, 1),
                 f"pl.ds pop loop ({mode}), bit_parity={parity_ds}"))
    record["rows"][f"pallas_ds_B{B}_us"] = round(us_ds, 1)
    record["rows"]["pallas_ds_bit_parity_vs_lax_scan"] = parity_ds

    # ---- fused gather+rank stage: lax pipeline comparison --------------
    # the lax-vs-lax pair carries the speed claim off TPU; the Pallas
    # fused kernel row is correctness-only in interpret mode
    bias_flat, ids_flat, emb_flat = _flat_index(rng, np.asarray(bbl[0]))
    n_flat = int(bias_flat.shape[0])
    bu = jnp.asarray(rng.normal(size=(B, D_EMB)).astype(np.float32))
    starts = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32) * L, (B, C))
    limits = jnp.full((B, C), n_flat - 1, jnp.int32)
    unfused = jax.jit(lambda u, a, st, c: _unfused_pipeline(
        u, a, st, c, bias_flat, ids_flat, emb_flat))
    us_unf, (pos_u, sc_u, ids_u, rk_u) = timed(
        unfused, bu, bcs, starts, bln, n=3)
    rows.append((f"merge_sort/unfused_pipeline_B{B}_us", round(us_unf, 1),
                 f"slab+merge+gather+einsum, N={n_flat}"))
    record["rows"][f"unfused_pipeline_B{B}_us"] = round(us_unf, 1)
    fused_lax = jax.jit(lambda u, a, st, c, lm: ref.fused_gather_rank_ref(
        u, a, st, c, lm, bias_flat, ids_flat, emb_flat, 8, TARGET, L))
    us_fl, (pos_f, sc_f, ids_f, rk_f) = timed(
        fused_lax, bu, bcs, starts, bln, limits, n=3)
    par_f = bool(jnp.all(pos_u == pos_f) and jnp.all(sc_u == sc_f)
                 and jnp.all(ids_u == ids_f))
    close_rk = bool(jnp.allclose(rk_u, rk_f, rtol=1e-5, atol=1e-5))
    speedup = us_unf / max(us_fl, 1e-9)
    rows.append((f"merge_sort/fused_lax_B{B}_us", round(us_fl, 1),
                 f"speedup_vs_unfused={speedup:.2f}x "
                 f"bit_parity={par_f} rank_close={close_rk}"))
    record["rows"][f"fused_lax_B{B}_us"] = round(us_fl, 1)
    record["rows"]["fused_lax_speedup_vs_unfused_x"] = round(speedup, 2)
    record["rows"]["fused_lax_bit_parity"] = par_f
    record["rows"]["fused_lax_rank_allclose"] = close_rk
    us_fp, (pos_k, sc_k, ids_k, rk_k) = timed(
        lambda u, a, st, c, lm: ops.fused_gather_rank(
            u, a, st, c, lm, bias_flat, ids_flat, emb_flat, 8, TARGET, L),
        bu, bcs, starts, bln, limits, n=1)
    par_k = bool(jnp.all(pos_u == pos_k) and jnp.all(sc_u == sc_k)
                 and jnp.all(ids_u == ids_k))
    close_k = bool(jnp.allclose(rk_u, rk_k, rtol=1e-5, atol=1e-5))
    rows.append((f"merge_sort/fused_pallas_B{B}_us", round(us_fp, 1),
                 f"{mode} — correctness-only off TPU; "
                 f"bit_parity={par_k} rank_close={close_k}"))
    record["rows"][f"fused_pallas_B{B}_us"] = round(us_fp, 1)
    record["rows"]["fused_pallas_bit_parity"] = par_k
    record["rows"]["fused_pallas_rank_allclose"] = close_k
    rows.append(("merge_sort/fused_bit_parity", None, par_f and par_k))

    with open(OUT_JSON, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    return rows
