"""Sharded serving: bit-exact parity vs the single-device serve path.

The sharded pipeline (serving/sharding.py) must reproduce
``retriever.serve`` BITWISE — every output array, including the padded
garbage lanes behind ``valid`` — for any shard count and both kernel
dispatches.

Device topology: this file runs in tier-1 on the default single CPU
device (shards are then logical), and scripts/test.sh re-runs it in a
SEPARATE process with ``XLA_FLAGS=--xla_force_host_platform_device_count
=8`` so the same assertions cross real device boundaries through the
("shard",) mesh.  The tests adapt to whatever ``jax.device_count()``
they find.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core import assignment_store as astore
from repro.core import ranking as ranking_lib
from repro.core import retriever
from repro.data import RecsysStream, StreamConfig
from repro.launch.mesh import make_serving_mesh
from repro.launch.train import train_svq
from repro.serving import (RetrievalService, place_sharded_index,
                           shard_serving_index, sharded_serve)


def _cfg():
    return get_smoke("svq").with_(n_clusters=64, n_items=2000,
                                  n_users=500, embed_dim=16,
                                  clusters_per_query=16,
                                  candidates_out=128)


@pytest.fixture(scope="module")
def trained():
    cfg = _cfg()
    stream = RecsysStream(StreamConfig(n_items=cfg.n_items,
                                       n_users=cfg.n_users,
                                       hist_len=cfg.user_hist_len))
    params, index, _ = train_svq(cfg, stream, n_steps=20, batch=128)
    idx = astore.build_serving_index(index.store, cfg.n_clusters)
    users = np.arange(24) % cfg.n_users
    batch = dict(user_id=jnp.asarray(users, jnp.int32),
                 hist=jnp.asarray(stream.user_hist[users], jnp.int32))
    return cfg, params, index, idx, batch, stream, users


def _assert_same_outputs(ref, got, msg=""):
    assert set(ref.keys()) == set(got.keys())
    for k in ref:
        np.testing.assert_array_equal(np.asarray(ref[k]),
                                      np.asarray(got[k]),
                                      err_msg=f"{msg} key={k}")


def test_shard_partition_roundtrip(trained):
    """Concatenating the shards' real regions recovers the global index."""
    cfg, params, index, idx, batch, stream, users = trained
    D = 8
    sidx = shard_serving_index(idx, cfg.n_clusters, D, cap_quantum=64)
    ks = cfg.n_clusters // D
    offs = np.asarray(idx.offsets)
    n_real = int(offs[cfg.n_clusters])
    base = np.asarray(sidx.item_base)
    assert base[0] == 0 and int(sidx.n_real) == n_real
    got_ids, got_bias = [], []
    for d in range(D):
        end = int(base[d + 1]) if d + 1 < D else n_real
        cnt = end - int(base[d])
        got_ids.append(np.asarray(sidx.item_ids)[d, :cnt])
        got_bias.append(np.asarray(sidx.item_bias)[d, :cnt])
        # shard-local offsets are the global ones rebased
        np.testing.assert_array_equal(
            np.asarray(sidx.offsets)[d],
            offs[d * ks:(d + 1) * ks + 1] - base[d])
    np.testing.assert_array_equal(np.concatenate(got_ids),
                                  np.asarray(idx.item_ids)[:n_real])
    np.testing.assert_array_equal(np.concatenate(got_bias),
                                  np.asarray(idx.item_bias)[:n_real])


def test_shard_requires_divisible_clusters(trained):
    cfg, params, index, idx, batch, stream, users = trained
    with pytest.raises(ValueError):
        shard_serving_index(idx, cfg.n_clusters, 7)


def _ranked_by(trained, ranking):
    """The trained retriever with its ranking step replaced by a freshly
    drawn ``ranking`` model (the same towers, tables and index)."""
    cfg, params, index, idx, batch, stream, users = trained
    if ranking != cfg.ranking:
        cfg = cfg.with_(ranking=ranking)
        params = dict(params, rank=ranking_lib.init_ranking(
            jax.random.PRNGKey(5), cfg, *retriever.d_feature_dims(cfg)))
    return cfg, params, index, idx, batch


@pytest.mark.parametrize("n_shards", [1, 4, 8])
@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("ranking", ["two_tower", "complicated"])
def test_sharded_serve_bitexact(trained, n_shards, use_kernel, ranking):
    cfg, params, index, idx, batch = _ranked_by(trained, ranking)
    ref = retriever.serve(params, index, cfg, idx, batch,
                          use_kernel=use_kernel)
    sidx = shard_serving_index(idx, cfg.n_clusters, n_shards,
                               cap_quantum=64)
    got = sharded_serve(params, index, cfg, sidx, batch,
                        use_kernel=use_kernel)
    _assert_same_outputs(ref, got,
                         f"D={n_shards} uk={use_kernel} {ranking}")


@pytest.mark.parametrize("use_kernel", [False, True])
def test_sharded_serve_bitexact_on_mesh(trained, use_kernel):
    """Same contract with the index committed to a ("shard",) mesh.

    On the default tier-1 run the mesh has one device; under the
    multi-device tier (scripts/test.sh) it spans 8 host-platform
    devices and the serve crosses real device boundaries.
    """
    cfg, params, index, idx, batch, stream, users = trained
    mesh = make_serving_mesh()
    sidx = place_sharded_index(
        shard_serving_index(idx, cfg.n_clusters, 8, cap_quantum=64), mesh)
    ref = retriever.serve(params, index, cfg, idx, batch,
                          use_kernel=use_kernel)
    got = jax.jit(lambda p, s, i, b: sharded_serve(
        p, s, cfg, i, b, use_kernel=use_kernel, mesh=mesh))(
        params, index, sidx, batch)
    _assert_same_outputs(ref, got, f"mesh={mesh.shape} uk={use_kernel}")


def test_sharded_service_parity_through_lifecycle(trained):
    """Facade parity holds across rebuilds and model swaps."""
    cfg, params, index, idx, batch, stream, users = trained
    mesh = make_serving_mesh()
    svc_single = RetrievalService(cfg, params, index)
    svc_shard = RetrievalService(cfg, params, index, n_shards=8,
                                 mesh=mesh)
    b_np = dict(user_id=users.astype(np.int32),
                hist=stream.user_hist[users].astype(np.int32))
    _assert_same_outputs(svc_single.serve_batch(b_np),
                         svc_shard.serve_batch(b_np), "initial")
    # mutate the live store (simulated training write), rebuild both
    new_store = astore.write(
        index.store,
        jnp.arange(16, dtype=jnp.int32),
        jnp.zeros((16,), jnp.int32),
        jnp.ones((16, cfg.embed_dim), jnp.float32),
        jnp.full((16,), 3.0, jnp.float32))
    new_state = index._replace(store=new_store)
    svc_single.swap_model(params, new_state)
    svc_shard.swap_model(params, new_state)
    svc_single.rebuild_index()
    svc_shard.rebuild_index()
    _assert_same_outputs(svc_single.serve_batch(b_np),
                         svc_shard.serve_batch(b_np), "after rebuild")
    assert svc_shard.index_generation.epoch == 1
    assert svc_shard.stats.index_rebuilds == 2
    assert svc_shard.stats.index_swaps == 1


def test_sharded_service_concurrent_serves(trained):
    """Sharded serve_batch is thread-safe and stays bit-stable."""
    cfg, params, index, idx, batch, stream, users = trained
    svc = RetrievalService(cfg, params, index, n_shards=4)
    b_np = dict(user_id=users.astype(np.int32),
                hist=stream.user_hist[users].astype(np.int32))
    want = svc.serve_batch(b_np)
    errors, outs = [], []
    lock = threading.Lock()

    def worker():
        try:
            for _ in range(3):
                o = svc.serve_batch(b_np)
                with lock:
                    outs.append(o)
        except Exception as e:                     # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    for o in outs:
        _assert_same_outputs(want, o, "concurrent")
    assert svc.stats.n_batches == 1 + 4 * 3
    assert svc.stats.latency.count == svc.stats.n_batches
