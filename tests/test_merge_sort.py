"""Alg. 1 k-way chunked merge sort: TPU scan form vs heap oracle."""
import jax.numpy as jnp
import numpy as np
from _hypo import given, settings, st

from repro.core import merge_sort
from repro.kernels import ref


def _case(rng, c, l, chunk, target):
    cs = rng.normal(size=(c,)).astype(np.float32)
    bl = -np.sort(-rng.normal(size=(c, l)).astype(np.float32), axis=1)
    ln = rng.integers(0, l + 1, size=(c,)).astype(np.int32)
    return cs, bl, ln


def _slab_merge(cs, bl, ln, chunk, target):
    """``merge_sort_serve`` over one (C, L) slab, read as a flat array
    through the slab adapter ``ref.merge_serve_ref``."""
    pos, sc = ref.merge_serve_ref(jnp.asarray(cs)[None],
                                  jnp.asarray(bl)[None],
                                  jnp.asarray(ln)[None], chunk, target)
    return pos[0], sc[0]


def test_matches_heap_oracle_basic(rng):
    cs, bl, ln = _case(rng, 8, 32, 4, 20)
    pos_np, sc_np = merge_sort.merge_sort_serve_np(cs, bl, ln, 4, 20)
    pos_j, sc_j = _slab_merge(cs, bl, ln, 4, 20)
    n = len(pos_np)
    np.testing.assert_array_equal(pos_np, np.asarray(pos_j)[:n])
    np.testing.assert_allclose(sc_np, np.asarray(sc_j)[:n], rtol=1e-5)
    assert np.all(np.asarray(pos_j)[n:] == -1)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 12), st.integers(1, 24), st.integers(1, 8),
       st.integers(1, 40), st.integers(0, 10 ** 6))
def test_matches_heap_oracle_property(c, l, chunk, target, seed):
    rng = np.random.default_rng(seed)
    cs, bl, ln = _case(rng, c, l, chunk, target)
    pos_np, sc_np = merge_sort.merge_sort_serve_np(cs, bl, ln, chunk,
                                                   target)
    pos_j, sc_j = _slab_merge(cs, bl, ln, chunk, target)
    n = len(pos_np)
    np.testing.assert_array_equal(pos_np, np.asarray(pos_j)[:n])
    np.testing.assert_allclose(sc_np, np.asarray(sc_j)[:n], rtol=1e-4)


def test_every_cluster_can_contribute(rng):
    """The paper's §3.4 claim: merge sort lets ALL clusters contribute."""
    c, l = 16, 8
    cs = np.zeros((c,), np.float32)          # equal personality scores
    bl = -np.sort(-rng.normal(size=(c, l)).astype(np.float32), axis=1)
    ln = np.full((c,), l, np.int32)
    pos, _ = _slab_merge(cs, bl, ln, 1, c * l)
    clusters_hit = set((np.asarray(pos)[np.asarray(pos) >= 0] // l)
                       .tolist())
    assert len(clusters_hit) == c


def test_chunking_approximation_bounded(rng):
    """Chunked pops ('we can stand some mistakes') stay close to exact."""
    cs, bl, ln = _case(rng, 12, 64, 8, 64)
    pos_c, sc_c = _slab_merge(cs, bl, ln, 8, 64)
    pos_e, sc_e = merge_sort.full_sort_topk(
        jnp.asarray(cs), jnp.asarray(bl), jnp.asarray(ln), 64)
    valid_c = np.asarray(pos_c) >= 0
    valid_e = np.asarray(pos_e) >= 0
    got = set(np.asarray(pos_c)[valid_c].tolist())
    want = set(np.asarray(pos_e)[valid_e].tolist())
    overlap = len(got & want) / max(len(want), 1)
    assert overlap >= 0.7        # chunk=8 approximation quality
