"""Plain reference of the served path, and the numbers that decide
``correct``.

The reference follows the paper's serve (Fig. 1, Eq. 11, Alg. 1) from the
benchmark's own weights and corpus (``corpus.py``), importing nothing of
the program:

1. user feature: the user's id row and the mean of its history rows of
   the hashed embedding tables; the task's user tower;
2. cluster rank: ``u . e_k`` over the codebook, the top
   ``clusters_per_query``;
3. Alg. 1: a binary heap over those clusters' item lists (bias
   descending, ties by store slot, at most ``items_per_cluster`` each),
   popping ``chunk_size`` items at a time up to ``candidates_out``;
4. exact score ``u . v + b`` and the two-tower ranking score of each
   candidate.

``precision="highest"`` is the reference.  ``precision="high"`` is the
control that has to fail: every product in three bfloat16 passes
(``_mm``), what the TPU's "high" precision does, one step below the
float32 the configurations state.

``compare`` holds what the program served against the reference's own
serve of the same requests, request by request, and returns the numbers
checked against ``LIMITS``.
"""
from __future__ import annotations

import functools
import heapq
from typing import Dict, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from corpus import N_CATES, hash_ids_np

NEG = -1e30


class Served(NamedTuple):
    """What one serve path gave for M requests, all (M, S) host arrays.

    ``ids``, ``merge``, ``exact`` are in merge order (invalid lanes carry
    merge score below NEG/2); ``ranked_ids``/``ranked`` are in ranking
    order, valid ones first."""
    ids: np.ndarray
    merge: np.ndarray
    exact: np.ndarray
    ranked_ids: np.ndarray
    ranked: np.ndarray
    ranked_valid: np.ndarray


class Lists(NamedTuple):
    """Corpus rows grouped by cluster: (bias desc, slot asc) inside."""
    order: np.ndarray       # corpus rows
    start: np.ndarray       # (K + 1,) offsets into order


def build_lists(cluster: np.ndarray, bias: np.ndarray, ids: np.ndarray,
                n_clusters: int, n_slots: int) -> Lists:
    slot = hash_ids_np(ids, n_slots)
    order = np.lexsort((slot, -bias.astype(np.float64), cluster))
    counts = np.bincount(cluster, minlength=n_clusters)
    start = np.concatenate([[0], np.cumsum(counts)])
    return Lists(order=order, start=start)


def heap_merge(scores: np.ndarray, clusters: np.ndarray, lists: Lists,
               bias: np.ndarray, cap: int, chunk: int, target: int,
               add) -> tuple:
    """Alg. 1 over the probed clusters -> (corpus rows, merge scores).

    ``add(a, b)`` forms a merge score in the path's own arithmetic."""
    heads = []
    segs = []
    for j, c in enumerate(clusters):
        a, b = lists.start[c], lists.start[c + 1]
        seg = lists.order[a:a + min(b - a, cap)]
        segs.append(seg)
        if seg.size:
            heads.append((-float(add(scores[j], bias[seg[0]])), j))
    heapq.heapify(heads)
    ptr = np.zeros(len(segs), np.int64)
    rows, merged = [], []
    while heads and len(rows) < target:
        _, j = heapq.heappop(heads)
        seg = segs[j]
        take = seg[ptr[j]:ptr[j] + chunk]
        for r in take:
            if len(rows) == target:
                break
            rows.append(int(r))
            merged.append(float(add(scores[j], bias[r])))
        ptr[j] += len(take)
        if ptr[j] < seg.size:
            heapq.heappush(heads, (-float(add(scores[j], bias[seg[ptr[j]]])),
                                   j))
    return np.asarray(rows, np.int64), np.asarray(merged, np.float64)


def _bf16(x):
    """x rounded to bfloat16, kept in float32 (``reduce_precision`` is not
    folded away under jit, as a float32 -> bfloat16 -> float32 round trip
    may be)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _mm(spec: str, a, b, precision: str):
    """einsum at ``precision``: "highest" is float32; "high" is the TPU's
    three-pass bfloat16 product, emulated alike on every backend: each
    operand split into a bfloat16 head and tail, and head*head +
    head*tail + tail*head summed in float32."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision="highest")
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    ah, bh = _bf16(a), _bf16(b)
    al, bl = _bf16(a - ah), _bf16(b - bh)
    mm = lambda x, y: jnp.einsum(spec, x, y, precision="highest")
    return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))


def _mlp(p, x, precision):
    n = len(p["layers"])
    for i, lp in enumerate(p["layers"]):
        x = _mm("...i,io->...o", x, lp["w"], precision) + lp["b"]
        if i < n - 1:
            x = jax.nn.relu(x)
    return x


def _task(tree, t):
    return jax.tree_util.tree_map(lambda a: a[t], tree)


class Reference:
    """The reference (or, at "high", the control) over one corpus."""

    def __init__(self, cfg, params, corpus, precision: str = "highest",
                 items_per_cluster: int = 256):
        self.cfg = cfg
        self.precision = precision
        self.cap = items_per_cluster
        self.params = params
        self.codebook = corpus.codebook
        self.emb = corpus.emb
        self.ids = np.asarray(corpus.ids)
        self.cluster = np.asarray(corpus.cluster)
        self.bias = np.asarray(corpus.bias, np.float32)
        self.lists = build_lists(self.cluster, self.bias, self.ids,
                                 cfg.n_clusters, cfg.n_items)
        # each corpus row's place in its cluster's list
        self.list_pos = np.empty(self.ids.size, np.int64)
        self.list_pos[self.lists.order] = np.arange(self.ids.size) \
            - np.repeat(self.lists.start[:-1], np.diff(self.lists.start))

    def rows_of(self, item_ids: np.ndarray) -> np.ndarray:
        """Corpus row of each id (ids not in the corpus -> -1)."""
        r = np.searchsorted(self.ids, item_ids)
        r = np.minimum(r, self.ids.size - 1)
        return np.where(self.ids[r] == item_ids, r, -1)

    @staticmethod
    def _add(a, b):
        return np.float32(np.float32(a) + np.float32(b))

    def user(self, user_id: np.ndarray, hist: np.ndarray, task: int):
        """(user feature, user vector) of every request, for one task."""
        return _user(self.params, hash_ids_np(user_id, self.cfg.n_users),
                     hash_ids_np(hist, self.cfg.n_items), task,
                     self.precision)

    def cluster_scores(self, u):
        return _mm("bd,kd->bk", u, self.codebook, self.precision)

    def score_items(self, uf, u, item_ids: np.ndarray, task: int):
        """(exact u.v + b, ranking score) of each candidate id; ids
        outside the corpus get NEG."""
        rows = self.rows_of(item_ids)
        exact, rank = _score(self.params, self.emb, jnp.asarray(self.bias),
                             uf, u, np.maximum(rows, 0),
                             hash_ids_np(item_ids, self.cfg.n_items),
                             int(hash_ids_np(np.zeros(1), N_CATES)[0]),
                             task, self.precision)
        ok = rows >= 0
        return (np.where(ok, np.asarray(exact, np.float64), NEG),
                np.where(ok, np.asarray(rank, np.float64), NEG))

    def serve(self, user_id, hist, tasks) -> Served:
        """The reference's own serve of M requests (its Served)."""
        cfg = self.cfg
        m, s = len(user_id), cfg.candidates_out
        ids = np.full((m, s), -1, np.int64)
        merge = np.full((m, s), NEG)
        exact = np.full((m, s), NEG)
        ranked = np.full((m, s), NEG)
        for task in np.unique(tasks):
            sel = tasks == task
            uf, u = self.user(user_id, hist, int(task))
            top_s, top_c = jax.lax.top_k(self.cluster_scores(u),
                                         cfg.clusters_per_query)
            top_s, top_c = np.asarray(top_s), np.asarray(top_c)
            for q in np.flatnonzero(sel):
                rows, sc = heap_merge(top_s[q], top_c[q], self.lists,
                                      self.bias, self.cap, cfg.chunk_size,
                                      s, self._add)
                ids[q, :rows.size] = self.ids[rows]
                merge[q, :rows.size] = sc
            ex, rk = self.score_items(uf, u, np.maximum(ids, 0), int(task))
            valid = sel[:, None] & (ids >= 0)
            exact = np.where(valid, ex, exact)
            ranked = np.where(valid, rk, ranked)
        order = np.argsort(-ranked, axis=1, kind="stable")
        take = lambda a: np.take_along_axis(a, order, 1)
        return Served(ids=ids, merge=merge, exact=exact,
                      ranked_ids=take(ids), ranked=take(ranked),
                      ranked_valid=take(ids >= 0))


@functools.partial(jax.jit, static_argnames=("task", "precision"))
def _user(params, uid_rows, hist_rows, task, precision):
    t = params["tables"]
    uf = jnp.concatenate([t["user_id"][uid_rows],
                          jnp.mean(t["item_id"][hist_rows], axis=-2)], -1)
    return uf, _mlp(_task(params["user_towers"], task), uf, precision)


@functools.partial(jax.jit, static_argnames=("task", "precision"))
def _score(params, emb, bias, uf, u, rows, id_rows, cate_row, task,
           precision):
    exact = _mm("bsd,bd->bs", emb[rows], u, precision) + bias[rows]
    t = params["tables"]
    item_feat = jnp.concatenate([
        t["item_id"][id_rows],
        jnp.broadcast_to(t["item_cate"][cate_row],
                         id_rows.shape + (t["item_cate"].shape[1],))], -1)
    ru = _mlp(_task(params["rank"]["user_mlp"], task), uf, precision)
    rv_all = _mlp(_task(params["rank"]["item_mlp"], task), item_feat,
                  precision)
    rank = _mm("bd,bsd->bs", ru, rv_all[..., :-1], precision) \
        + rv_all[..., -1]
    return exact, rank


def served_from_outputs(outs: List[Dict[str, np.ndarray]]) -> Served:
    """Stack the program's per-request outputs (serve_batch row slices)."""
    cat = lambda k: np.concatenate([o[k] for o in outs], 0)
    return Served(ids=cat("index_ids"), merge=cat("merge_scores"),
                  exact=cat("exact_scores"), ranked_ids=cat("item_ids"),
                  ranked=cat("scores"), ranked_valid=cat("valid"))


# The numbers compared and their limits: PERF.md §2 gives the readings
# of the program and of the control each limit was set from.
LIMITS = {
    "merge_err": 1e-5,
    "exact_err": 8e-6,
    "rank_err": 1e-5,
    "list_order": 0,
    "bad_order_rows": 0,
    "count_diff": 0,
    "cand_miss": 1e-3,
    "rank_perm": 0,
}


def compare(ref: Reference, user_id, hist, tasks, got: Served
            ) -> Dict[str, float]:
    """Numbers that hold ``got`` (program or control) against the
    reference, over M requests:

    - merge_err (cluster rank and Alg. 1's scores): the widest gap, in
      units of the request's range of probed cluster scores, by which a
      served candidate's merge score departs from the reference's
      ``u . e_c + b``, or by which its cluster lies below the
      reference's last probed cluster;
    - exact_err / rank_err (ranking stage): the widest error of a served
      exact score and ranking score, in units of the request's spread of
      that score;
    - list_order (index and Alg. 1, exact): served candidates that are not
      the next item of their cluster's list in the reference (bias
      descending, ties by store slot), or not in the corpus at all:
      Alg. 1 reads each probed list from its head, so a request's k-th
      candidate from a cluster is that list's k-th item;
    - bad_order_rows (ranking, exact): requests whose ranking order is not
      by descending score with the valid candidates first;
    - count_diff (Alg. 1, exact): over all requests, the sum of the
      differences between the number of valid candidates served and the
      number the reference's own serve gives;
    - cand_miss (cluster rank and Alg. 1): the share of the reference's
      candidates, over all requests, that were not served; a swap at a
      near tie of cluster or merge scores moves a few;
    - rank_perm (ranking, exact): requests whose ranked candidates are
      not the same set as the merge's valid candidates.
    """
    cfg = ref.cfg
    nums = dict(merge_err=0.0, exact_err=0.0, rank_err=0.0, list_order=0,
                bad_order_rows=0, count_diff=0, cand_miss=0.0, rank_perm=0)
    widest = lambda k, err: nums.__setitem__(
        k, max(nums[k], float(np.max(err, initial=0.0))))
    own = ref.serve(user_id, hist, tasks)
    missed = 0
    for q in range(len(user_id)):
        v = got.merge[q] > NEG / 2
        want = own.ids[q][own.ids[q] >= 0]
        nums["count_diff"] += abs(int(v.sum()) - want.size)
        missed += np.setdiff1d(want, got.ids[q][v]).size
        ranked = got.ranked_ids[q][got.ranked_valid[q]]
        nums["rank_perm"] += int(not np.array_equal(
            np.sort(ranked), np.sort(got.ids[q][v])))
    nums["cand_miss"] = missed / max(int((own.ids >= 0).sum()), 1)
    for task in np.unique(tasks):
        uf, u = ref.user(user_id, hist, int(task))
        cs = np.asarray(ref.cluster_scores(u), np.float64)
        ex_m, _ = ref.score_items(uf, u, np.maximum(got.ids, 0), int(task))
        _, rk_r = ref.score_items(uf, u, np.maximum(got.ranked_ids, 0),
                                  int(task))
        for q in np.flatnonzero(tasks == task):
            v = got.merge[q] > NEG / 2
            rows = ref.rows_of(got.ids[q][v])
            if (rows < 0).any():
                nums["list_order"] += int((rows < 0).sum())
                nums["merge_err"] = float("inf")
                continue
            top = np.sort(cs[q])[::-1][:cfg.clusters_per_query]
            scale = max(top[0] - top[-1], 1e-30)
            c = ref.cluster[rows]
            seen: Dict[int, int] = {}
            for r, cl in zip(rows, c):
                k = seen.get(int(cl), 0)
                nums["list_order"] += int(ref.list_pos[r] != k)
                seen[int(cl)] = k + 1
            b = ref.bias[rows].astype(np.float64)
            widest("merge_err", np.abs(got.merge[q][v] - (cs[q][c] + b))
                   / scale)
            widest("merge_err", (top[-1] - cs[q][c]) / scale)
            ex_ref = ex_m[q][v]
            spread = max(float(np.std(ex_ref)), 1e-30) if ex_ref.size \
                else 1.0
            widest("exact_err", np.abs(got.exact[q][v] - ex_ref) / spread)
            rv = got.ranked_valid[q]
            n_valid = int(rv.sum())
            scores = got.ranked[q]
            if (not rv[:n_valid].all()
                    or np.any(np.diff(scores[:n_valid]) > 0)):
                nums["bad_order_rows"] += 1
            rk_ref = rk_r[q][rv]
            spread = max(float(np.std(rk_ref)), 1e-30) if rk_ref.size \
                else 1.0
            widest("rank_err", np.abs(scores[rv] - rk_ref) / spread)
    return nums


def verdict(nums: Dict[str, float]) -> bool:
    return all(nums[k] <= lim for k, lim in LIMITS.items())
