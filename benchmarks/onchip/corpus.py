"""Seeded weights and a fully indexed corpus, made on the device.

The benchmark, not the program, makes the weights and the corpus, so the
plain reference (``reference.py``) can read them without taking anything
the program produced.  One jitted call per seed makes:

- the retriever's parameters in the program's own layout (embedding
  tables, item tower, one user tower per task, and the ranking model's
  weights from ``models/<ranking>.py``), drawn as ``retriever.init``
  draws them: normal weights scaled by fan-in, zero biases, tables scaled
  by their width;
- the corpus: every item id that owns its assignment-store slot alone
  (the store hashes ids into ``n_items`` slots, so only these can be held
  at once), its category, its item-tower embedding and popularity bias;
- a codebook of ``n_clusters`` corpus embeddings drawn from the seed (the
  data-initialised stand-in for a balanced, deployed index) and each
  item's nearest cluster.

``index_state`` then writes the corpus into the program's assignment
store, from which ``RetrievalService`` builds its serving index.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

import rankers

N_CATES = 4096            # rows of the program's item_cate table
ASSIGN_CHUNK = 4096       # corpus rows per nearest-cluster block
TOWER_CHUNK = 65536       # corpus rows per item-tower block


def hash_ids_np(ids: np.ndarray, capacity: int) -> np.ndarray:
    """Multiply-shift hash of ids into [0, capacity): the store's and the
    embedding tables' slot of an id."""
    with np.errstate(over="ignore"):
        h = np.asarray(ids).astype(np.uint32) * np.uint32(2654435761)
        h = h ^ (h >> np.uint32(16))
        return (h % np.uint32(capacity)).astype(np.int64)


def corpus_ids(n_items: int) -> np.ndarray:
    """The smallest id of every store slot that some id in
    [0, n_items) hashes to, ascending."""
    slots = hash_ids_np(np.arange(n_items), n_items)
    _, first = np.unique(slots, return_index=True)
    return np.sort(first).astype(np.int32)


def key_of(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also one over 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                              seed // 2 ** 32)


class Corpus(NamedTuple):
    ids: jax.Array          # (N,) int32 item ids, ascending
    cate: jax.Array         # (N,) int32 category ids
    emb: jax.Array          # (N, d) float32 personality embeddings
    bias: jax.Array         # (N,) float32 popularity biases
    codebook: jax.Array     # (K, d) float32 cluster embeddings
    cluster: jax.Array      # (N,) int32 nearest cluster of each item


def mlp_params(key, d_in, dims):
    """Dense layers d_in -> dims: normal weights scaled by fan-in, zero
    biases."""
    layers = []
    for k, h in zip(jax.random.split(key, len(dims)), dims):
        layers.append({"w": jax.random.normal(k, (d_in, h), jnp.float32)
                       * d_in ** -0.5,
                       "b": jnp.zeros((h,), jnp.float32)})
        d_in = h
    return {"layers": layers}


def stacked(key, n, fn):
    """``fn(key)`` for each of n keys split from ``key``, stacked."""
    return jax.vmap(fn)(jax.random.split(key, n))


def _mlp(p, x):
    n = len(p["layers"])
    for i, lp in enumerate(p["layers"]):
        x = x @ lp["w"] + lp["b"]
        if i < n - 1:
            x = jax.nn.relu(x)
    return x


def make_params(key: jax.Array, cfg, here: Path = rankers.HERE) -> Dict:
    """The retriever's parameters, in the layout ``retriever.serve``
    reads; ``"rank"`` from the configuration's ranking model
    (``models/<cfg.ranking>.py`` under ``here``)."""
    kt, ki, ku, kr = jax.random.split(key, 4)
    d_user_in = cfg.user_embed_dim + cfg.item_embed_dim
    d_item_in = 2 * cfg.item_embed_dim
    tables = {}
    for k, (name, rows, dim) in zip(
            jax.random.split(kt, 3),
            (("user_id", cfg.n_users, cfg.user_embed_dim),
             ("item_id", cfg.n_items, cfg.item_embed_dim),
             ("item_cate", N_CATES, cfg.item_embed_dim))):
        tables[name] = jax.random.normal(k, (rows, dim), jnp.float32) \
            * dim ** -0.5
    return {
        "tables": tables,
        "item_tower": mlp_params(
            ki, d_item_in, cfg.item_tower[:-1] + (cfg.embed_dim + 1,)),
        "user_towers": stacked(ku, cfg.n_tasks, lambda k: mlp_params(
            k, d_user_in, cfg.user_tower[:-1] + (cfg.embed_dim,))),
        "rank": rankers.ranking_model(cfg.ranking, here).make_rank_params(
            kr, cfg),
    }


def _pad_rows(x, chunk):
    pad = (-x.shape[0]) % chunk
    return jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)]) \
        if pad else x


def _blocks(fn, x, chunk):
    """fn over row blocks of x, results concatenated back to x's rows."""
    n = x.shape[0]
    xb = _pad_rows(x, chunk).reshape((-1, chunk) + x.shape[1:])
    out = jax.lax.map(fn, xb)
    return out.reshape((-1,) + out.shape[2:])[:n]


def make_corpus(key: jax.Array, params: Dict, ids: jax.Array,
                n_clusters: int) -> Corpus:
    kc, kk = jax.random.split(key)
    n = ids.shape[0]
    t = params["tables"]
    cate = jax.random.randint(kc, (n,), 0, N_CATES, jnp.int32)
    n_items = t["item_id"].shape[0]
    # rows of the hashed embedding tables, as the program looks them up
    slot = _hash(ids, n_items)
    cslot = _hash(cate, N_CATES)
    feat = jnp.concatenate([t["item_id"][slot], t["item_cate"][cslot]], -1)
    out = _blocks(lambda f: _mlp(params["item_tower"], f), feat,
                  TOWER_CHUNK)
    emb, bias = out[:, :-1], out[:, -1]
    codebook = emb[jax.random.permutation(kk, n)[:n_clusters]]
    e2 = jnp.sum(codebook * codebook, -1)

    def nearest(v):
        d2 = jnp.sum(v * v, -1, keepdims=True) - 2.0 * v @ codebook.T \
            + e2[None, :]
        return jnp.argmin(d2, -1).astype(jnp.int32)

    cluster = _blocks(nearest, emb, ASSIGN_CHUNK)
    return Corpus(ids=ids, cate=cate, emb=emb, bias=bias,
                  codebook=codebook, cluster=cluster)


def _hash(ids: jax.Array, capacity: int) -> jax.Array:
    h = ids.astype(jnp.uint32) * jnp.uint32(2654435761)
    h = h ^ (h >> jnp.uint32(16))
    return (h % jnp.uint32(capacity)).astype(jnp.int32)


def make_all(seed: int, cfg):
    """-> (params, corpus), made on the device in one jitted call."""
    ids = jnp.asarray(corpus_ids(cfg.n_items))

    @jax.jit
    def build(key, ids):
        kp, kc = jax.random.split(key)
        params = make_params(kp, cfg)
        return params, make_corpus(kc, params, ids, cfg.n_clusters)

    return build(key_of(seed), ids)


def index_state(cfg, corpus: Corpus):
    """The program's IndexState holding the corpus in its store."""
    from repro.core import assignment_store as astore
    from repro.core import freq_estimator, retriever, vq

    @jax.jit
    def build(c):
        store = astore.write(astore.init_store(cfg.n_items, cfg.embed_dim),
                             c.ids, c.cluster, c.emb, c.bias)
        return retriever.IndexState(
            vq=vq.VQState(w=c.codebook,
                          c=jnp.ones((cfg.n_clusters,), jnp.float32)),
            store=store, freq=freq_estimator.init_freq(cfg.n_items),
            step=jnp.zeros((), jnp.int32))

    return build(corpus)


@dataclasses.dataclass
class IndexReport:
    live_clusters: int
    largest_cluster: int
    share_beyond_cap: float
    corpus_items: int

    def line(self) -> str:
        return (f"index: corpus_items={self.corpus_items} live_clusters="
                f"{self.live_clusters} largest_cluster="
                f"{self.largest_cluster} share_beyond_L="
                f"{self.share_beyond_cap}")


def index_report(cluster: np.ndarray, n_clusters: int,
                 cap: int) -> IndexReport:
    """Cluster-size distribution of the seeded index; ``cap`` is the
    per-cluster list length the merge reads (``items_per_cluster``)."""
    counts = np.bincount(np.asarray(cluster), minlength=n_clusters)
    return IndexReport(
        live_clusters=int((counts > 0).sum()),
        largest_cluster=int(counts.max()),
        share_beyond_cap=float(np.maximum(counts - cap, 0).sum()
                               / max(counts.sum(), 1)),
        corpus_items=int(counts.sum()))
