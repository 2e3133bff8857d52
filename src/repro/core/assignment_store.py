"""Parameter-server analog: the real-time item -> cluster assignment table.

The paper writes (ItemID -> ClusterID) into a PS the moment the training
(or candidate) stream produces an assignment.  On TPU we model the PS as
fixed-capacity device arrays indexed by a multiplicative hash of the item
id, updated by scatter inside the jitted train step -- the write happens
in the SAME step that computes the assignment, which is precisely the
"index immediacy" property (§3.1).

Besides the cluster id we persist the item's serving payload (personality
embedding + popularity bias, Eq. 11) so a serving index (Appendix B layout:
compact item list + cluster segment offsets, items sorted by bias inside a
cluster) can be built at any moment without a training pause.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.freq_estimator import hash_ids
from repro.obs import trace


class AssignmentStore(NamedTuple):
    item_id: jax.Array       # (capacity,) int32 stored id (collision check)
    cluster: jax.Array       # (capacity,) int32 cluster id, -1 = empty
    item_emb: jax.Array      # (capacity, d) personality embedding v_emb
    item_bias: jax.Array     # (capacity,) popularity bias v_bias

    @property
    def capacity(self) -> int:
        return self.cluster.shape[0]


def init_store(capacity: int, dim: int) -> AssignmentStore:
    return AssignmentStore(
        item_id=jnp.full((capacity,), -1, jnp.int32),
        cluster=jnp.full((capacity,), -1, jnp.int32),
        item_emb=jnp.zeros((capacity, dim), jnp.float32),
        item_bias=jnp.zeros((capacity,), jnp.float32))


def write(store: AssignmentStore, ids: jax.Array, cluster: jax.Array,
          v_emb: jax.Array, v_bias: jax.Array,
          valid: jax.Array | None = None) -> AssignmentStore:
    """Real-time assignment write-back (impression or candidate stream)."""
    slots = hash_ids(ids, store.capacity)
    if valid is None:
        valid = jnp.ones(ids.shape, bool)
    # Invalid rows re-write their current content (scatter no-op).
    cur_id = store.item_id[slots]
    cur_cl = store.cluster[slots]
    cur_emb = store.item_emb[slots]
    cur_bias = store.item_bias[slots]
    wid = jnp.where(valid, ids.astype(jnp.int32), cur_id)
    wcl = jnp.where(valid, cluster.astype(jnp.int32), cur_cl)
    wemb = jnp.where(valid[:, None], v_emb.astype(jnp.float32), cur_emb)
    wbias = jnp.where(valid, v_bias.astype(jnp.float32), cur_bias)
    return AssignmentStore(
        item_id=store.item_id.at[slots].set(wid),
        cluster=store.cluster.at[slots].set(wcl),
        item_emb=store.item_emb.at[slots].set(wemb),
        item_bias=store.item_bias.at[slots].set(wbias))


def read_cluster(store: AssignmentStore, ids: jax.Array) -> jax.Array:
    return store.cluster[hash_ids(ids, store.capacity)]


class ServingIndex(NamedTuple):
    """Appendix-B layout: compact item list segmented by cluster.

    Items inside a cluster are sorted by descending popularity bias, which
    is exactly the pre-sorted per-cluster list the merge-sort serving
    stage (Alg. 1) consumes.

    Tombstone-aware contract: a cluster's segment occupies
    ``[offsets[c], offsets[c+1])`` but only its first ``counts[c]`` slots
    are LIVE; the rest is spare capacity holding the constant sentinel
    payload (id -1, bias 0).  With ``spare_per_cluster=0`` (the default
    build) ``counts[c] == offsets[c+1] - offsets[c]`` and the layout is
    bit-identical to the pre-delta dense one.  Spare capacity is what the
    incremental delta path (serving/deltas.py) appends into, and a
    tombstone is a slot compacted out of the live prefix.
    """
    item_ids: jax.Array      # (n,) int32, -1 in spare / sentinel slots
    item_emb: jax.Array      # (n, d)
    item_bias: jax.Array     # (n,) sorted desc within each live prefix
    cluster_of: jax.Array    # (n,) int32 (n_clusters in non-live slots)
    offsets: jax.Array       # (K+1,) int32 segment starts (incl. spare)
    counts: jax.Array        # (K,) int32 live items per segment

    @property
    def n_items(self) -> int:
        return self.item_ids.shape[0]


def build_serving_index(store: AssignmentStore, n_clusters: int,
                        use_kernel: bool = False,
                        spare_per_cluster: int = 0) -> ServingIndex:
    """Sort occupied slots by (cluster asc, bias desc) -> segments.

    Empty slots (cluster == -1) sort to the end of a sentinel segment and
    are excluded via the offsets table.  Runs fully on device; in prod
    this is the asynchronous "candidate scanning" step (§3.1), which never
    blocks training.

    The composite sort goes through the kernel-dispatch pattern:
    ``use_kernel=True`` runs the fused integer-radix-key sort
    (``kernels/ops.index_sort``) and derives offsets by binary search on
    the sorted cluster ids (O(K log N) instead of an O(N) segment-sum);
    the default is the ``kernels/ref.index_sort_ref`` lexsort oracle.
    Both produce bit-identical indexes.

    ``spare_per_cluster > 0`` spreads the segments apart so every cluster
    owns that many sentinel spare slots after its live prefix (the
    delta-append headroom); total layout size grows by K * spare and the
    empty-slot sentinel tail moves to the very end.  Serving reads only
    live prefixes (via ``counts``), so outputs are bit-identical across
    spare settings.
    """
    occupied = store.cluster >= 0
    cl = jnp.where(occupied, store.cluster, n_clusters)
    if use_kernel:
        from repro.kernels import ops as kops
        with trace.span("index_sort"):
            order = kops.index_sort(cl, store.item_bias)
        cl_sorted = cl[order]
        offsets = jnp.searchsorted(
            cl_sorted, jnp.arange(n_clusters + 1), side="left")
    else:
        from repro.kernels import ref as kref
        with trace.span("index_sort"):
            order = kref.index_sort_ref(cl, store.item_bias)
        cl_sorted = cl[order]
        counts = jax.ops.segment_sum(
            jnp.ones_like(cl_sorted, jnp.int32), cl_sorted, n_clusters + 1)
        offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(counts[:n_clusters])])
    offsets = offsets.astype(jnp.int32)
    live_counts = (offsets[1:] - offsets[:-1]).astype(jnp.int32)
    ids_s = store.item_id[order]
    emb_s = store.item_emb[order]
    bias_s = store.item_bias[order]
    cl_sorted = cl_sorted.astype(jnp.int32)
    if spare_per_cluster == 0:
        return ServingIndex(item_ids=ids_s, item_emb=emb_s,
                            item_bias=bias_s, cluster_of=cl_sorted,
                            offsets=offsets, counts=live_counts)
    # Spread segments: sorted position i moves to i + cluster_i * spare.
    # Positions are strictly increasing (cl_sorted is non-decreasing), so
    # the scatter is a permutation into a larger sentinel-initialized
    # buffer; the empty-slot tail (sentinel cluster K) lands after the
    # last spare gap.
    n = ids_s.shape[0]
    spare = int(spare_per_cluster)
    total = n + n_clusters * spare
    newpos = jnp.arange(n, dtype=jnp.int32) \
        + jnp.minimum(cl_sorted, n_clusters) * jnp.int32(spare)
    ids_sp = jnp.full((total,), -1, jnp.int32).at[newpos].set(ids_s)
    bias_sp = jnp.zeros((total,), bias_s.dtype).at[newpos].set(bias_s)
    emb_sp = jnp.zeros((total, emb_s.shape[1]),
                       emb_s.dtype).at[newpos].set(emb_s)
    clof_sp = jnp.full((total,), n_clusters,
                       jnp.int32).at[newpos].set(cl_sorted)
    offsets_sp = offsets + jnp.arange(n_clusters + 1,
                                      dtype=jnp.int32) * jnp.int32(spare)
    return ServingIndex(item_ids=ids_sp, item_emb=emb_sp,
                        item_bias=bias_sp, cluster_of=clof_sp,
                        offsets=offsets_sp, counts=live_counts)


def collision_rate(store: AssignmentStore, ids: jax.Array) -> jax.Array:
    """Fraction of ids whose slot currently holds a DIFFERENT id."""
    slots = hash_ids(ids, store.capacity)
    held = store.item_id[slots]
    return jnp.mean(((held >= 0) & (held != ids.astype(jnp.int32)))
                    .astype(jnp.float32))
