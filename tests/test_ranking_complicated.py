"""Target-attention ranking ("VQ Complicated", paper §3.5, Fig. 3).

The serving path (B, S candidates per user), the training path (one
candidate per user) and a plain loop over the candidates agree; the
service ranks the two-tower service's candidates with the loop's
scores, through the micro-batcher; and no (B, S, H, ·) intermediate,
the history copied per candidate, comes back into the serve step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core import assignment_store as astore
from repro.core import ranking, retriever, vq
from repro.serving import RetrievalService, sharding

# H = 6 histories, 2 heads of hd = 8 over d_e = 16, S = 64 candidates:
# sizes apart from one another, so a shape names its axes
CFG = get_smoke("svq").with_(ranking="complicated", ranking_heads=2,
                             ranking_mlp=(32, 16, 8), user_hist_len=6,
                             n_items=2000, n_users=500, embed_dim=16,
                             n_clusters=64, clusters_per_query=16,
                             candidates_out=64)
D_USER_IN, D_ITEM_IN = retriever.d_feature_dims(CFG)


def _inputs(cfg, b=3, s=5, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    d_e = cfg.item_embed_dim
    params = ranking.init_ranking(ks[0], cfg, D_USER_IN, D_ITEM_IN)
    uf = jax.random.normal(ks[1], (b, D_USER_IN))
    item = jax.random.normal(ks[2], (b, s, D_ITEM_IN))
    hist = jax.random.normal(ks[3], (b, cfg.user_hist_len, d_e))
    cross = jax.random.normal(ks[4], (b, s, d_e))
    return params, uf, item, hist, cross


def _plain_score(p, t, heads, uf, item, hist, cross):
    """One user's one candidate, written out: uf (d_u,), item (d_i,),
    hist (H, d_e), cross (d_e,)."""
    at = jax.tree_util.tree_map(lambda x: x[t], p["attn"])
    q = item @ at["wq"]["w"] + at["wq"]["b"]
    k = hist @ at["wk"]["w"] + at["wk"]["b"]
    v = hist @ at["wv"]["w"] + at["wv"]["b"]
    hd = q.shape[0] // heads
    parts = []
    for h in range(heads):
        sl = slice(h * hd, (h + 1) * hd)
        logits = k[:, sl] @ q[sl] / np.sqrt(hd)
        w = jnp.exp(logits - logits.max())
        parts.append((w / w.sum()) @ v[:, sl])
    x = jnp.concatenate([uf, item, jnp.concatenate(parts), cross])
    layers = p["head"]["layers"]
    for i, lp in enumerate(layers):
        x = x @ lp["w"][t] + lp["b"][t]
        if i < len(layers) - 1:
            x = jax.nn.relu(x)
    return x[0]


def _plain_scores(p, cfg, uf, item, hist, cross):
    """(P, B, S) by a loop over tasks, users and candidates."""
    b, s = item.shape[:2]
    with jax.default_matmul_precision("highest"):
        return np.array([[[_plain_score(p, t, cfg.ranking_heads, uf[i],
                                        item[i, j], hist[i], cross[i, j])
                           for j in range(s)] for i in range(b)]
                         for t in range(cfg.n_tasks)])


def _close(got, want, rtol=1e-6):
    """Equal to ``rtol`` of the scores' largest magnitude."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    np.testing.assert_array_less(np.abs(got - want),
                                 rtol * np.abs(want).max() + 1e-30)


@pytest.mark.parametrize("n_tasks", [1, 2])
def test_serving_scores_match_a_loop_over_candidates(n_tasks):
    cfg = CFG.with_(n_tasks=n_tasks, eta=(1.0,) * n_tasks)
    p, uf, item, hist, cross = _inputs(cfg)
    with jax.default_matmul_precision("highest"):
        got = ranking.ranking_scores(p, cfg, uf, item, hist, cross)
    assert got.shape == (n_tasks, 3, 5)
    _close(got, _plain_scores(p, cfg, uf, item, hist, cross))


@pytest.mark.parametrize("n_tasks", [1, 2])
def test_serving_matches_the_training_path_per_candidate(n_tasks):
    cfg = CFG.with_(n_tasks=n_tasks, eta=(1.0,) * n_tasks)
    p, uf, item, hist, cross = _inputs(cfg, seed=1)
    with jax.default_matmul_precision("highest"):
        served = ranking.ranking_scores(p, cfg, uf, item, hist, cross)
        for j in range(item.shape[1]):
            trained = ranking.ranking_scores(p, cfg, uf, item[:, j], hist,
                                             cross[:, j])
            assert trained.shape == (n_tasks, 3)
            _close(served[..., j], trained)


def _index_state(cfg, key):
    """An IndexState whose store holds 1,500 items with random clusters,
    embeddings and biases."""
    ke, kc, kb, kw = jax.random.split(key, 4)
    n = 1500
    ids = jnp.arange(n, dtype=jnp.int32)
    store = astore.write(
        astore.init_store(cfg.n_items, cfg.embed_dim), ids,
        jax.random.randint(kc, (n,), 0, cfg.n_clusters, jnp.int32),
        jax.random.normal(ke, (n, cfg.embed_dim)),
        jax.random.normal(kb, (n,)))
    _, state = retriever.init(key, cfg)
    return state._replace(store=store,
                          vq=vq.init_vq(kw, cfg.n_clusters, cfg.embed_dim))


@pytest.fixture(scope="module")
def services():
    key = jax.random.PRNGKey(7)
    params, _ = retriever.init(key, CFG)
    two = CFG.with_(ranking="two_tower")
    params_two, _ = retriever.init(key, two)
    state = _index_state(CFG, jax.random.PRNGKey(8))
    return (RetrievalService(CFG, params, state),
            RetrievalService(two, params_two, state), params)


def test_the_service_ranks_the_two_tower_candidates_with_plain_scores(
        services):
    svc, two, params = services
    rng = np.random.default_rng(3)
    users = rng.integers(0, CFG.n_users, 6)
    hist = rng.integers(0, 1500, (6, CFG.user_hist_len))
    batcher = svc.make_batcher(max_batch=8, max_delay_s=0.001)
    futs = [batcher.submit(dict(user_id=users[i:i + 1],
                                hist=hist[i:i + 1])) for i in range(6)]
    out = {k: np.concatenate([f.result(timeout=120)[k] for f in futs])
           for k in ("index_ids", "item_ids", "scores", "valid")}
    batcher.close()
    ref = two.serve_batch(dict(user_id=users, hist=hist))
    # the same index, the same user towers: the same merge
    np.testing.assert_array_equal(out["index_ids"], ref["index_ids"])
    assert out["valid"].sum() == ref["valid"].sum() > 0
    for i in range(6):
        assert set(out["item_ids"][i][out["valid"][i]]) \
            == set(ref["item_ids"][i][ref["valid"][i]])
    # scores: the plain loop over what was served, category 0 as served
    p = jax.tree_util.tree_map(jnp.asarray, params)
    uf, hemb = retriever.user_features(p, jnp.asarray(users),
                                       jnp.asarray(hist))
    ids = jnp.asarray(out["item_ids"])
    item = retriever.item_features(p, ids, jnp.zeros_like(ids))
    cross = item[..., :CFG.item_embed_dim] \
        * uf[:, None, -CFG.item_embed_dim:]
    want = _plain_scores(p["rank"], CFG, uf, item, hemb, cross)[0]
    _close(out["scores"][out["valid"]], want[out["valid"]], rtol=1e-5)


def _shapes(jaxpr):
    """Every intermediate's shape in a jaxpr and its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield tuple(getattr(v.aval, "shape", ()))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _shapes(sub)


@pytest.mark.parametrize("stage", ["retriever", "sharding"])
def test_no_history_copy_per_candidate(stage):
    """The serving rank stage holds no intermediate with the B, S and H
    axes and at least B * S * H * hd elements: K and V are projected once
    per (B, H), not per candidate."""
    b, s, h = 3, CFG.candidates_out, CFG.user_hist_len
    hd = CFG.item_embed_dim // CFG.ranking_heads
    params, _ = retriever.init(jax.random.PRNGKey(0), CFG)
    s1 = dict(user_feat=jnp.zeros((b, D_USER_IN)),
              hist_emb=jnp.zeros((b, h, CFG.item_embed_dim)))
    s2 = dict(cand_ids=jnp.zeros((b, s), jnp.int32),
              valid=jnp.ones((b, s), bool),
              merge_scores=jnp.zeros((b, s)),
              exact_scores=jnp.zeros((b, s)))
    fn = retriever.serve_stage_ranking if stage == "retriever" \
        else sharding.sharded_stage_ranking
    jaxpr = jax.make_jaxpr(lambda p, a, c: fn(p, CFG, a, c))(params, s1,
                                                             s2).jaxpr
    shapes = list(_shapes(jaxpr))
    # the attention ran: its (B, S, heads, H) logits are there
    assert (b, s, CFG.ranking_heads, h) in shapes
    per_candidate = [sh for sh in shapes
                     if {b, s, h} <= set(sh) and np.prod(sh) >= b * s * h * hd]
    assert not per_candidate, per_candidate
