"""Fused Pallas serving path vs oracles: bit-exact Alg. 1 parity.

Three-way parity on pop order for every case: numpy heap oracle
(`merge_sort_serve_np`) == lax.scan (`merge_sort_serve`, exact=True) ==
Pallas kernel (`ops.merge_serve`, interpret mode), plus cluster_rank
against `lax.top_k(u @ e.T, n)` and the `retriever.serve_kernel`
dispatch equivalence.
"""
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypo import given, settings, st

from repro.core import merge_sort, retriever
from repro.kernels import ops, ref


def _assert_three_way(cs, bl, ln, chunk, target):
    """np heap == lax.scan == pallas, bit-for-bit on pop order."""
    jcs, jbl, jln = map(jnp.asarray, (cs, bl, ln))
    pos_np, sc_np = merge_sort.merge_sort_serve_np(cs, bl, ln, chunk,
                                                   target)
    pos_j, sc_j = merge_sort.merge_sort_serve(jcs, jbl, jln, chunk,
                                              target, exact=True)
    pos_p, sc_p = ops.merge_serve(jcs[None], jbl[None], jln[None],
                                  chunk, target)
    pos_p, sc_p = np.asarray(pos_p[0]), np.asarray(sc_p[0])
    n = len(pos_np)
    for name, pos, sc in (("lax", np.asarray(pos_j), np.asarray(sc_j)),
                          ("pallas", pos_p, sc_p)):
        np.testing.assert_array_equal(pos_np, pos[:n], err_msg=name)
        assert np.all(pos[n:] == -1), name
        np.testing.assert_allclose(sc_np, sc[:n], rtol=1e-5,
                                   err_msg=name)
        assert np.all(sc[n:] <= merge_sort.NEG / 2), name
    # pallas == lax bit-for-bit including padding
    np.testing.assert_array_equal(np.asarray(pos_j), pos_p)
    np.testing.assert_array_equal(np.asarray(sc_j), sc_p)


def _random_case(rng, c, l, tied=False):
    if tied:
        # few distinct values -> heavy score ties across and within
        # clusters; exercises the argmax-vs-heap tie-break equivalence
        cs = rng.integers(0, 2, size=(c,)).astype(np.float32)
        bl = rng.integers(0, 3, size=(c, l)).astype(np.float32)
    else:
        cs = rng.normal(size=(c,)).astype(np.float32)
        bl = rng.normal(size=(c, l)).astype(np.float32)
    bl = -np.sort(-bl, axis=1)
    ln = rng.integers(0, l + 1, size=(c,)).astype(np.int32)
    return cs, bl, ln


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 12), st.integers(1, 24), st.integers(1, 8),
       st.integers(1, 48), st.integers(0, 10 ** 6))
def test_property_grid_matches_heap_oracle(c, l, chunk, target, seed):
    rng = np.random.default_rng(seed)
    cs, bl, ln = _random_case(rng, c, l, tied=bool(seed % 3 == 0))
    _assert_three_way(cs, bl, ln, chunk, target)


@pytest.mark.parametrize("c,l,chunk,target", [
    (1, 1, 1, 1),                     # degenerate single item
    (5, 7, 3, 10 ** 4),               # target >> total items
    (6, 3, 8, 12),                    # ALL clusters shorter than chunk
    (9, 11, 5, 9 * 11),               # target == total capacity
    (13, 17, 4, 40),                  # non-power-of-two everything
])
def test_edge_shapes_match_heap_oracle(rng, c, l, chunk, target):
    cs, bl, ln = _random_case(rng, c, l)
    _assert_three_way(cs, bl, ln, chunk, target)


def test_tied_scores_bit_exact(rng):
    """Heap tie-break (-score, cluster) == argmax first-max: same pops."""
    for seed in range(8):
        r = np.random.default_rng(seed)
        cs, bl, ln = _random_case(r, 10, 12, tied=True)
        _assert_three_way(cs, bl, ln, 4, 50)


def test_empty_clusters(rng):
    cs, bl, ln = _random_case(rng, 8, 16)
    ln[::2] = 0                        # half the clusters empty
    _assert_three_way(cs, bl, ln, 4, 40)
    ln[:] = 0                          # ALL clusters empty
    _assert_three_way(cs, bl, ln, 4, 40)


def test_batched_queries_independent(rng):
    """Grid-over-queries == per-query loop (no cross-query leakage)."""
    B, C, L, chunk, target = 5, 6, 10, 3, 25
    cs = rng.normal(size=(B, C)).astype(np.float32)
    bl = -np.sort(-rng.normal(size=(B, C, L)).astype(np.float32), axis=-1)
    ln = rng.integers(0, L + 1, size=(B, C)).astype(np.int32)
    pos_b, sc_b = ops.merge_serve(jnp.asarray(cs), jnp.asarray(bl),
                                  jnp.asarray(ln), chunk, target)
    for b in range(B):
        pos_1, sc_1 = ops.merge_serve(
            jnp.asarray(cs[b:b + 1]), jnp.asarray(bl[b:b + 1]),
            jnp.asarray(ln[b:b + 1]), chunk, target)
        np.testing.assert_array_equal(np.asarray(pos_b[b]),
                                      np.asarray(pos_1[0]))
        np.testing.assert_array_equal(np.asarray(sc_b[b]),
                                      np.asarray(sc_1[0]))


def test_inexact_budget_subset_of_exact(rng):
    """exact=False pops fewer times; its valid output is a prefix-safe
    subset of the exact pop order (may under-fill, never reorders)."""
    cs, bl, ln = _random_case(rng, 10, 6)   # short clusters -> underfill
    jcs, jbl, jln = map(jnp.asarray, (cs, bl, ln))
    pos_e, _ = ops.merge_serve(jcs[None], jbl[None], jln[None], 4, 30,
                               exact=True)
    pos_i, _ = ops.merge_serve(jcs[None], jbl[None], jln[None], 4, 30,
                               exact=False)
    got_e = np.asarray(pos_e[0])
    got_i = np.asarray(pos_i[0])
    n_i = int((got_i >= 0).sum())
    np.testing.assert_array_equal(got_i[:n_i], got_e[:n_i])
    assert n_i <= int((got_e >= 0).sum())


def _assert_lax_batch_bit_exact(cs, bl, ln, chunk, target):
    """Batched lax merge (``ref.merge_serve_ref``) == heap oracle per
    query, positions and scores bit for bit, padding included."""
    pos_b, sc_b = ref.merge_serve_ref(jnp.asarray(cs), jnp.asarray(bl),
                                      jnp.asarray(ln), chunk, target)
    pos_b, sc_b = np.asarray(pos_b), np.asarray(sc_b)
    for b in range(cs.shape[0]):
        pos_np, sc_np = merge_sort.merge_sort_serve_np(cs[b], bl[b], ln[b],
                                                       chunk, target)
        n = len(pos_np)
        np.testing.assert_array_equal(pos_b[b, :n], pos_np, err_msg=b)
        np.testing.assert_array_equal(sc_b[b, :n].astype(np.float64),
                                      sc_np, err_msg=b)
        assert np.all(pos_b[b, n:] == -1), b
        assert np.all(sc_b[b, n:] == np.float32(merge_sort.NEG)), b


def _sorted_desc(x):
    return -np.sort(-x, axis=-1)


def _case_dry_mid_merge(rng):
    """64 queries whose short clusters run dry while the merge still
    has items to take: exhausted heads must leave the argmax."""
    B, C, L, chunk = 64, 16, 32, 4
    cs = rng.normal(size=(B, C)).astype(np.float32)
    bl = _sorted_desc(rng.normal(size=(B, C, L)).astype(np.float32))
    ln = rng.integers(0, 13, size=(B, C)).astype(np.int32)
    return cs, bl, ln, chunk, 64


def _case_edge_lengths(rng):
    """Lengths 0, 1, chunk - 1, chunk and L (the cap), in every query."""
    B, C, L, chunk = 16, 10, 24, 5
    cs = rng.normal(size=(B, C)).astype(np.float32)
    bl = _sorted_desc(rng.normal(size=(B, C, L)).astype(np.float32))
    edges = np.array([0, 1, chunk - 1, chunk, L], np.int32)
    ln = np.stack([rng.permutation(np.resize(edges, C))
                   for _ in range(B)]).astype(np.int32)
    return cs, bl, ln, chunk, C * L


def _case_tied_heads(rng):
    """Every cluster's head score equal: the lowest cluster wins each
    tie, as in the heap's (-score, cluster) order."""
    B, C, L, chunk = 16, 12, 16, 4
    cs = np.full((B, C), 0.5, np.float32)
    bl = _sorted_desc(rng.integers(0, 3, size=(B, C, L)).astype(np.float32))
    bl[:, :, 0] = 3.0
    ln = rng.integers(1, L + 1, size=(B, C)).astype(np.int32)
    return cs, bl, ln, chunk, 96


def _case_repop_after_overtake(rng):
    """Chunk k of cluster c scores -(k * C + rank(c)): each cluster's
    head is overtaken by every other cluster's after each pop, so pops
    go round robin and every cluster is popped again later."""
    B, C, L, chunk = 8, 6, 24, 4
    rank = np.stack([rng.permutation(C) for _ in range(B)])
    k = np.arange(L) // chunk
    bl = -(k[None, None, :] * C + rank[:, :, None]).astype(np.float32)
    cs = np.zeros((B, C), np.float32)
    ln = np.full((B, C), L, np.int32)
    target = 3 * C * chunk
    pos_np, _ = merge_sort.merge_sort_serve_np(cs[0], bl[0], ln[0], chunk,
                                               target)
    popped = pos_np[::chunk] // L
    np.testing.assert_array_equal(popped, np.tile(np.argsort(rank[0]), 3))
    return cs, bl, ln, chunk, target


@pytest.mark.parametrize("case", [_case_dry_mid_merge, _case_edge_lengths,
                                  _case_tied_heads,
                                  _case_repop_after_overtake],
                         ids=lambda f: f.__name__[len("_case_"):])
def test_carried_heads_match_heap_oracle(rng, case):
    _assert_lax_batch_bit_exact(*case(rng))


_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_HLO_CALLEE = re.compile(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)")
_HLO_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_HLO_GATHER = re.compile(
    r"= \w+\[([\d,]*)\]\S* gather\(.*slice_sizes=\{([\d,]*)\}")


def _while_body_gathers(hlo: str):
    """(n_slices, slice_elems) of every gather that a ``while`` body of
    the compiled module ``hlo`` reaches, fusions and calls included."""
    comps, name = {}, None
    for line in hlo.splitlines():
        m = _HLO_COMPUTATION.match(line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    todo = [b for lines in comps.values() for line in lines
            if " while(" in line
            for b in re.findall(r"body=%([\w.\-]+)", line)]
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += _HLO_CALLEE.findall(line)
            for group in _HLO_BRANCHES.findall(line):
                todo += re.findall(r"%([\w.\-]+)", group)
    gathers = []
    for c in seen:
        for line in comps[c]:
            m = _HLO_GATHER.search(line)
            if m:
                out = int(np.prod([int(d) for d in m.group(1).split(",")]))
                win = int(np.prod([int(d) for d in m.group(2).split(",")]))
                gathers.append((out // win, win))
    return gathers


def test_merge_loop_gathers_no_per_cluster_heads():
    """At the serve cells' shapes, no pop re-gathers every cluster's
    head: the compiled loop body holds no gather of B x C or more
    single-element slices (the carried heads make it chunk + 1 per
    query)."""
    B, C, L = 512, 128, 256
    f = jax.jit(partial(ref.merge_serve_ref, chunk=8, target=512,
                        exact=True))
    hlo = f.lower(jax.ShapeDtypeStruct((B, C), jnp.float32),
                  jax.ShapeDtypeStruct((B, C, L), jnp.float32),
                  jax.ShapeDtypeStruct((B, C), jnp.int32)
                  ).compile().as_text()
    gathers = _while_body_gathers(hlo)
    assert gathers, "no gather found in the merge's while body"
    scalar = [n for n, win in gathers if win == 1]
    assert max(scalar) < B * C, gathers


# ---------------------------------------------------------------------------
# cluster_rank
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,k,d,n,bb,bk", [
    (8, 64, 16, 8, 4, 32),
    (33, 500, 24, 16, 16, 128),       # non-divisible B and K
    (5, 100, 8, 100, 4, 32),          # n == K (> block_k: block grows)
    (128, 256, 32, 32, 128, 256),     # single K block
])
def test_cluster_rank_matches_topk(rng, b, k, d, n, bb, bk):
    u = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    e = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
    vals, idx = ops.cluster_rank(u, e, n, block_b=bb, block_k=bk)
    vref, iref = ref.cluster_rank_ref(u, e, n)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(vref))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(iref))


def test_cluster_rank_rejects_n_above_k(rng):
    u = jnp.zeros((2, 4))
    e = jnp.zeros((8, 4))
    with pytest.raises(ValueError):
        ops.cluster_rank(u, e, 9)


# ---------------------------------------------------------------------------
# serve_kernel dispatch
# ---------------------------------------------------------------------------

def test_serve_kernel_dispatch_paths_identical(rng):
    B, C, L, chunk, target = 4, 8, 12, 4, 30
    cs = jnp.asarray(rng.normal(size=(B, C)).astype(np.float32))
    bl = jnp.asarray(-np.sort(
        -rng.normal(size=(B, C, L)).astype(np.float32), axis=-1))
    ln = jnp.asarray(rng.integers(0, L + 1, size=(B, C)).astype(np.int32))
    pos_f, sc_f = retriever.serve_kernel(cs, bl, ln, chunk, target,
                                         use_kernel=False)
    pos_k, sc_k = retriever.serve_kernel(cs, bl, ln, chunk, target,
                                         use_kernel=True)
    np.testing.assert_array_equal(np.asarray(pos_f), np.asarray(pos_k))
    np.testing.assert_array_equal(np.asarray(sc_f), np.asarray(sc_k))
