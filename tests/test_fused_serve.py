"""Fused gather+rank serve stage vs the staged pipeline: bit parity.

The fused path (`retriever.fused_gather_rank` -> `ops.fused_gather_rank`
/ `ref.fused_gather_rank_ref`) consumes merge pops in-kernel via dynamic
-slice gathers and scores candidates against the query without the
(B, S, d) slab re-gather.  Contract, everywhere: `pos`, `merge_scores`,
`index_ids`/`item_ids`, `valid` and the stage-3 sorted outputs are
BIT-exact against the unfused staged path; `exact_scores` is allclose
only (dot accumulation order differs).

Covered here: the kernel/lax unit parity (±0.0 ties, NaN in the dead
tail, non-pow2 shapes), plain `serve(fused=...)` over both `use_kernel`
settings, `sharded_serve` over a sharded index (this file also runs in
the tier-2 8-host-device pass, where the mesh is real), and the
`RetrievalService` front door including the staged span path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SVQConfig
from repro.core import assignment_store as astore
from repro.core import retriever
from repro.kernels import ops, ref
from repro.serving import RetrievalService, sharding

# keys that must match bit-for-bit between any two serve paths; the
# remaining key (exact_scores) is allclose-only
ALLCLOSE_KEYS = ("exact_scores",)


def _assert_outputs_match(want, got, tag):
    assert set(want) == set(got), tag
    for k in want:
        a, b = np.asarray(want[k]), np.asarray(got[k])
        if k in ALLCLOSE_KEYS:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5,
                                       err_msg=f"{tag}:{k}")
        else:
            np.testing.assert_array_equal(a, b, err_msg=f"{tag}:{k}")


# ---------------------------------------------------------------------------
# unit: fused kernel vs lax oracle vs the unfused composition
# ---------------------------------------------------------------------------

def _fused_case(rng, b, c, l, d, zeros=False, nan_tail=False):
    tail = int(rng.integers(1, 5))          # flat tail beyond the slabs
    n = c * l + tail
    cs = rng.normal(size=(b, c)).astype(np.float32)
    if zeros:
        # heavy ±0.0 merge-score ties: IEEE equality must collapse them
        slab = -np.sort(-rng.integers(-1, 2, (c, l)).astype(np.float32),
                        axis=1)
        zmask = slab == 0.0
        slab[zmask] = np.where(rng.random(int(zmask.sum())) < 0.5,
                               0.0, -0.0)
        cs[:] = 0.0
    else:
        # Alg. 1 precondition: each cluster's list sorted descending
        slab = -np.sort(-rng.normal(size=(c, l)).astype(np.float32),
                        axis=1)
    starts = np.broadcast_to(np.arange(c, dtype=np.int32) * l,
                             (b, c)).copy()
    # lengths shared across batch rows so the dead tail of the SHARED
    # flat bias array is well-defined for nan_tail poisoning
    lengths = np.broadcast_to(
        rng.integers(0, l + 1, (c,)).astype(np.int32), (b, c)).copy()
    if nan_tail:
        # poison every dead lane (>= length) in every slab: pops and
        # scores must be untouched because dead lanes never win
        for ci in range(c):
            slab[ci, lengths[0, ci]:] = np.nan
    bias = np.concatenate(
        [slab.reshape(-1), rng.normal(size=(tail,)).astype(np.float32)])
    limits = np.full((b, c), n - 1, np.int32)
    ids = rng.permutation(n).astype(np.int32)
    emb = rng.normal(size=(n, d)).astype(np.float32)
    u = rng.normal(size=(b, d)).astype(np.float32)
    return tuple(map(jnp.asarray,
                     (u, cs, starts, lengths, limits, bias, ids, emb)))


@pytest.mark.parametrize("b,c,l,d,chunk,target,zeros", [
    (2, 6, 10, 8, 4, 25, False),
    (3, 13, 17, 12, 3, 70, False),         # non-pow2 everything
    (1, 5, 3, 4, 8, 9, False),             # chunk > every list
    (2, 9, 12, 8, 4, 30, True),            # ±0.0 tie storm
])
def test_fused_gather_rank_kernel_vs_ref(rng, b, c, l, d, chunk, target,
                                         zeros):
    u, cs, st, ln, lm, bias, ids, emb = _fused_case(rng, b, c, l, d,
                                                    zeros=zeros)
    out_r = ref.fused_gather_rank_ref(u, cs, st, ln, lm, bias, ids, emb,
                                      chunk, target, l)
    out_k = ops.fused_gather_rank(u, cs, st, ln, lm, bias, ids, emb,
                                  chunk, target, l)
    for a, b_, name in zip(out_r, out_k,
                           ("pos", "merge_scores", "ids", "rank")):
        if name == "rank":
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-5, atol=1e-5, err_msg=name)
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b_),
                                          err_msg=name)
    # the merge decisions must equal the standalone merge kernel's over
    # the equivalent (B, C, L) bias slab
    slab = jnp.minimum(st[..., None] + jnp.arange(l)[None, None, :],
                       bias.shape[0] - 1)
    pos_m, sc_m = ref.merge_serve_ref(cs, bias[slab], ln, chunk, target)
    np.testing.assert_array_equal(np.asarray(out_r[0]), np.asarray(pos_m))
    np.testing.assert_array_equal(np.asarray(out_r[1]), np.asarray(sc_m))


def test_fused_gather_rank_nan_dead_tail(rng):
    """NaNs poisoning the dead (beyond-length) lanes change nothing."""
    b, c, l, d, chunk, target = 2, 7, 9, 8, 4, 30
    u, cs, st, ln, lm, bias, ids, emb = _fused_case(rng, b, c, l, d)
    rng2 = np.random.default_rng(7)
    un, csn, stn, lnn, lmn, biasn, idsn, embn = _fused_case(
        rng2, b, c, l, d, nan_tail=True)
    # same case, NaN tail: rebuild with identical live data
    clean = np.asarray(biasn).copy()
    live = ~np.isnan(clean)
    clean[~live] = 0.0
    out_nan_r = ref.fused_gather_rank_ref(un, csn, stn, lnn, lmn, biasn,
                                          idsn, embn, chunk, target, l)
    out_nan_k = ops.fused_gather_rank(un, csn, stn, lnn, lmn, biasn,
                                      idsn, embn, chunk, target, l)
    out_clean = ref.fused_gather_rank_ref(un, csn, stn, lnn, lmn,
                                          jnp.asarray(clean), idsn, embn,
                                          chunk, target, l)
    for got, tag in ((out_nan_r, "ref"), (out_nan_k, "kernel")):
        for a, b_, name in zip(out_clean, got,
                               ("pos", "merge_scores", "ids", "rank")):
            if name == "rank":
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b_), rtol=1e-5, atol=1e-5,
                    err_msg=f"{tag}:{name}")
            else:
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b_),
                    err_msg=f"{tag}:{name}")


# ---------------------------------------------------------------------------
# end-to-end: serve / sharded_serve / RetrievalService
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def trained():
    cfg = SVQConfig(n_users=500, n_items=800, n_clusters=24, embed_dim=16,
                    user_embed_dim=8, item_embed_dim=8,
                    user_tower=(32, 16), item_tower=(32, 17),
                    clusters_per_query=6, candidates_out=48, chunk_size=8)
    key = jax.random.PRNGKey(0)
    params, state = retriever.init(key, cfg)
    rng = np.random.default_rng(0)
    for _ in range(3):
        batch = dict(
            user_id=jnp.asarray(rng.integers(0, cfg.n_users, 64)),
            hist=jnp.asarray(rng.integers(0, cfg.n_items, (64, 5))),
            item_id=jnp.asarray(rng.integers(0, cfg.n_items, 64)),
            item_cate=jnp.asarray(rng.integers(0, 4096, 64)),
            labels=jnp.asarray(rng.random((64, cfg.n_tasks))
                               .astype(np.float32)))
        _, state, _ = retriever.train_step(params, state, cfg, batch)
    index = astore.build_serving_index(state.store, cfg.n_clusters)
    sbatch = dict(user_id=jnp.asarray(rng.integers(0, cfg.n_users, 9)),
                  hist=jnp.asarray(rng.integers(0, cfg.n_items, (9, 5))))
    return cfg, params, state, index, sbatch


def test_serve_fused_parity(trained):
    """serve(fused=..., use_kernel=...): all four combos == unfused."""
    cfg, params, state, index, sbatch = trained
    want = jax.tree.map(np.asarray, retriever.serve(
        params, state, cfg, index, sbatch, items_per_cluster=32))
    assert int(np.asarray(want["valid"]).sum()) > 0
    for fused in (False, True):
        for uk in (False, True):
            got = jax.tree.map(np.asarray, retriever.serve(
                params, state, cfg, index, sbatch, items_per_cluster=32,
                use_kernel=uk, fused=fused))
            _assert_outputs_match(want, got, f"fused={fused},uk={uk}")


def test_sharded_serve_fused_parity(trained):
    """sharded_serve over 4 shards == plain serve, fused x use_kernel.

    Under the tier-2 8-host-device pass the shards land on distinct
    devices; on one device they are logical — the parity contract is
    identical either way.
    """
    cfg, params, state, index, sbatch = trained
    sidx = sharding.shard_serving_index(index, cfg.n_clusters, 4)
    want = jax.tree.map(np.asarray, retriever.serve(
        params, state, cfg, index, sbatch, items_per_cluster=32))
    for fused in (False, True):
        for uk in (False, True):
            got = jax.tree.map(np.asarray, sharding.sharded_serve(
                params, state, cfg, sidx, sbatch, items_per_cluster=32,
                use_kernel=uk, fused=fused))
            _assert_outputs_match(want, got,
                                  f"sharded,fused={fused},uk={uk}")


def test_service_fused_parity(trained):
    """RetrievalService(fused=True): plain and traced (span sink) serves
    match the unfused service bit-for-bit, and the serve spans still
    land in the sink."""
    cfg, params, state, _, sbatch = trained
    batch = {k: np.asarray(v) for k, v in sbatch.items()}
    svc = RetrievalService(cfg, params, state)
    svc_f = RetrievalService(cfg, params, state, fused=True)
    want = svc.serve_batch(batch)
    got = svc_f.serve_batch(batch)
    _assert_outputs_match(want, got, "service")
    sink = []
    got_traced = svc_f.serve_batch(batch, span_sink=sink)
    _assert_outputs_match(want, got_traced, "service-traced")
    assert [s.name for s in sink] == ["serve.put", "serve.dispatch",
                                      "serve.fetch"]
