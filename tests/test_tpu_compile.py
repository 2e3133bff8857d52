"""Compile the main path for a TPU v5e chip that is described, not attached.

Every Pallas kernel that ``use_kernel=True`` or ``fused=True`` reaches is
lowered through Mosaic (``interpret=False``) at ``configs/svq.py:CONFIG``
widths and at the train batch ``chip_smoke.py`` uses, and the default
(lax) serve jit is compiled at the ``serve_p99`` batch.  Nothing runs:
a pass says the TPU compiler accepts the program and that it fits one
chip's memory, nothing about results or times.

The topology is described inside a module fixture, never at import: the
TPU library admits one process at a time, and every test worker imports
this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.svq import COMPLICATED, CONFIG
from repro.core import assignment_store as astore
from repro.core import retriever
from repro.kernels import inbatch_softmax, merge_serve, vq_assign, vq_ema

TRAIN_B = 8192                  # chip_smoke.py's train batch
SERVE_B = 512                   # configs/svq.py serve_p99
K, D = CONFIG.n_clusters, CONFIG.embed_dim
C, S, CHUNK = CONFIG.clusters_per_query, CONFIG.candidates_out, \
    CONFIG.chunk_size
L = 256                         # RetrievalService items_per_cluster
N = CONFIG.n_items
HBM_BYTES = 16 * 10 ** 9        # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled


f32, i32 = jnp.float32, jnp.int32
KERNELS = {
    "vq_assign": (functools.partial(vq_assign.vq_assign_pallas,
                                    interpret=False),
                  [((TRAIN_B, D), f32), ((K, D), f32), ((K,), f32)]),
    "ema_segment_sum": (
        lambda v, a, w: vq_ema.ema_segment_sum_pallas(v, a, w, K,
                                                      interpret=False),
        [((TRAIN_B, D), f32), ((TRAIN_B,), i32), ((TRAIN_B,), f32)]),
    "inbatch_softmax_fwd": (
        functools.partial(inbatch_softmax.inbatch_softmax_pallas,
                          interpret=False, return_stats=True),
        [((TRAIN_B, D), f32), ((TRAIN_B, D), f32), ((TRAIN_B,), f32),
         ((TRAIN_B,), f32)]),
    "inbatch_softmax_bwd": (
        functools.partial(inbatch_softmax.inbatch_softmax_bwd_pallas,
                          interpret=False),
        [((TRAIN_B, D), f32), ((TRAIN_B, D), f32)]
        + [((TRAIN_B,), f32)] * 4),
    "cluster_rank": (
        lambda u, e: merge_serve.cluster_rank_pallas(u, e, C,
                                                     interpret=False),
        [((SERVE_B, D), f32), ((K, D), f32)]),
    "merge_serve": (
        lambda cs, bl, ln: merge_serve.merge_serve_pallas(
            cs, bl, ln, CHUNK, S, interpret=False),
        [((SERVE_B, C), f32), ((SERVE_B, C, L), f32), ((SERVE_B, C), i32)]),
    "merge_serve_ds": (
        lambda cs, bl, ln: merge_serve.merge_serve_ds_pallas(
            cs, bl, ln, CHUNK, S, interpret=False),
        [((SERVE_B, C), f32), ((SERVE_B, C, L), f32), ((SERVE_B, C), i32)]),
    "fused_gather_rank": (
        lambda u, cs, st, ln, lm, b, i, e:
            merge_serve.fused_gather_rank_pallas(
                u, cs, st, ln, lm, b, i, e, CHUNK, S, L, interpret=False),
        [((SERVE_B, D), f32), ((SERVE_B, C), f32)]
        + [((SERVE_B, C), i32)] * 3
        + [((N,), f32), ((N,), i32), ((N, D), f32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, shapes = KERNELS[name]
    compiled = _compile(fn, shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_default_serve_compiles_for_v5e(one_chip, no_compile_cache):
    """The lax serve jit at full width, over the index a service with
    delta headroom builds."""
    params, state = jax.eval_shape(
        lambda: retriever.init(jax.random.PRNGKey(0), CONFIG))
    index = jax.eval_shape(
        lambda s: astore.build_serving_index(s, K, spare_per_cluster=16),
        state.store)
    batch = dict(user_id=jax.ShapeDtypeStruct((SERVE_B,), i32),
                 hist=jax.ShapeDtypeStruct(
                     (SERVE_B, CONFIG.user_hist_len), i32))
    place = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    compiled = jax.jit(
        lambda p, s, i, b: retriever.serve(p, s, CONFIG, i, b)).lower(
        place(params), place(state), place(index), place(batch)).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < HBM_BYTES)
    assert "tpu_custom_call" not in compiled.as_text()


def test_target_attention_serve_compiles_for_v5e(one_chip, no_compile_cache):
    """The serve jit with the target-attention ranker at full width and
    float32 precision, as the benchmark runs it: it fits one chip with
    room, since K and V are projected once per user (projected per
    candidate, the step's temporaries were 4.1 GB at 512 users)."""
    params, state = jax.eval_shape(
        lambda: retriever.init(jax.random.PRNGKey(0), COMPLICATED))
    index = jax.eval_shape(
        lambda s: astore.build_serving_index(s, K), state.store)
    batch = dict(user_id=jax.ShapeDtypeStruct((SERVE_B,), i32),
                 hist=jax.ShapeDtypeStruct(
                     (SERVE_B, COMPLICATED.user_hist_len), i32))
    place = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(
            lambda p, s, i, b: retriever.serve(p, s, COMPLICATED, i, b)
        ).lower(place(params), place(state), place(index),
                place(batch)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 10 ** 9
