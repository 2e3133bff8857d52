"""Bring-up check of the streaming-VQ retriever on a TPU.

    python chip_smoke.py                # one chip: train -> index -> serve
    python chip_smoke.py --use-kernel   # the same through the Pallas kernels
    python chip_smoke.py --chips 4      # only: 4-way sharded serve vs one device

Everything runs in this one process.  The model is ``configs/svq.py:
CONFIG`` at full width (16,384 clusters, d=64, towers 512/256/64,
2,097,152 items, 1,048,576 users), its weights and the synthetic
impression/candidate streams made from ``--seed``.  ``train_svq`` takes
256 steps at batch 8192, one full pass of the candidate stream over the
corpus, so the serving index holds every item the store keeps.  ``RetrievalService.serve_batch`` then serves batches of 512
users, one ``extract_deltas`` -> ``apply_deltas`` publishes a new item,
and the served candidates are checked:

- the Alg. 1 merge against the numpy heap oracle (``core/merge_sort.py``),
  bit for bit, on the chip's own cluster scores and index;
- the candidate sets against the same jitted serve on the host CPU
  backend: f32 matmuls run at default precision on the TPU, so the
  per-row candidate-set overlap is measured and must reach
  ``MIN_OVERLAP``; served again with f32-exact matmuls on the chip it
  must reach ``MIN_OVERLAP_EXACT``.

Timings printed here are smoke figures, not benchmark numbers.  The last
line of stdout is one JSON object naming the device.  The script exits
non-zero when JAX finds no TPU, and when any phase or check fails.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from repro.configs.svq import CONFIG                          # noqa: E402
from repro.core import assignment_store as astore             # noqa: E402
from repro.core import merge_sort, retriever                  # noqa: E402
from repro.core.freq_estimator import hash_ids                # noqa: E402
from repro.data import RecsysStream, StreamConfig             # noqa: E402
from repro.launch.mesh import make_serving_mesh               # noqa: E402
from repro.launch.train import train_svq                      # noqa: E402
from repro.serving import RetrievalService, extract_deltas    # noqa: E402
from repro.serving.telemetry import ServeStats                # noqa: E402
from repro.utils.compile_cache import enable_compile_cache    # noqa: E402

TRAIN_BATCH = 8192
TRAIN_STEPS = 256               # n_items / TRAIN_BATCH: one corpus pass
SERVE_BATCH = 512
SERVE_BATCHES = 4
DELTA_SPARE = 16                # per-cluster headroom for live deltas
# Bounds on the mean per-row candidate-set overlap of two serve paths.
# A wrong index or ranking lands near 0.  At default precision the TPU
# rounds f32 matmul inputs to bf16; at "highest" precision only the
# accumulation order differs.
MIN_OVERLAP = 0.5
MIN_OVERLAP_EXACT = 0.99
GIB = 2 ** 30


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_gib() -> float:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / GIB


def user_batches(stream: RecsysStream, n: int, seed: int):
    rng = np.random.default_rng(seed + 1)
    users = rng.integers(0, stream.cfg.n_users, (n, SERVE_BATCH))
    return [dict(user_id=u.astype(np.int32),
                 hist=stream.user_hist[u].astype(np.int32)) for u in users]


def train(cfg, args):
    stream = RecsysStream(StreamConfig(n_items=cfg.n_items,
                                       n_users=cfg.n_users,
                                       hist_len=cfg.user_hist_len,
                                       seed=args.seed))
    stats = ServeStats()
    t0 = time.perf_counter()
    params, index, res = train_svq(cfg, stream, TRAIN_STEPS,
                                   TRAIN_BATCH, seed=args.seed,
                                   use_kernel=args.use_kernel, stats=stats)
    jax.block_until_ready((params, index))
    total = time.perf_counter() - t0
    first = stats.stage("train_step").max       # step 0 syncs: compile+run
    loss0 = res.metrics[0]["loss"]
    finite = all(bool(jnp.all(jnp.isfinite(x)))
                 for x in jax.tree.leaves(params))
    # the store hashes ids into n_items slots; after a full candidate pass
    # every slot some item hashes to holds an assignment
    assigned = int(jnp.sum(index.store.cluster >= 0))
    slots = int(jnp.unique(hash_ids(jnp.arange(cfg.n_items),
                                    index.store.capacity)).size)
    log(f"train: steps={TRAIN_STEPS} batch={TRAIN_BATCH} "
        f"use_kernel={args.use_kernel} first_step_s={first:.3f} "
        f"warm_s_per_step={(total - first) / (TRAIN_STEPS - 1):.4f}"
        f" total_s={total:.3f} loss_step0={loss0:.5f} params_finite={finite}"
        f" slots_assigned={assigned}/{slots} peak_gib={peak_gib():.3f}")
    if not (np.isfinite(loss0) and finite):
        raise SystemExit("train: loss or parameters not finite")
    if assigned != slots:
        raise SystemExit(f"train: {assigned} of {slots} store slots "
                         f"assigned")
    return stream, params, index


def serve(svc, batches, tag):
    """-> outputs per batch; logs compile (first call) vs warm times."""
    outs, times = [], []
    for b in batches:
        t0 = time.perf_counter()
        outs.append(svc.serve_batch(b))          # returns host arrays
        times.append(time.perf_counter() - t0)
    warm = times[1:] or times
    log(f"serve[{tag}]: batches={len(batches)}x{SERVE_BATCH} "
        f"first_s={times[0]:.3f} warm_s_mean={np.mean(warm):.4f} "
        f"warm_s_max={np.max(warm):.4f} peak_gib={peak_gib():.3f}")
    return outs


def served_sets(out):
    """Per-row sets of the served candidate ids (valid merge lanes)."""
    valid = out["merge_scores"] > merge_sort.NEG / 2
    return [set(ids[v].tolist()) for ids, v in zip(out["index_ids"], valid)]


def compare(tag, a_outs, b_outs, bound):
    """Mean per-row |A & B| / max(|A|, |B|) of candidate sets (two empty
    sets agree); fails below ``bound``."""
    pairs = [(a, b) for oa, ob in zip(a_outs, b_outs)
             for a, b in zip(served_sets(oa), served_sets(ob))]
    fr = [len(a & b) / max(len(a), len(b)) if a or b else 1.0
          for a, b in pairs]
    ov = float(np.mean(fr))
    same = np.mean([np.array_equal(a["index_ids"], b["index_ids"])
                    for a, b in zip(a_outs, b_outs)])
    sizes = np.asarray([(len(a), len(b)) for a, b in pairs])
    bad = sum(int((~np.isfinite(o["exact_scores"])
                   & (o["merge_scores"] > merge_sort.NEG / 2)).sum())
              for o in a_outs + b_outs)
    log(f"parity[{tag}]: candidate_overlap={ov:.6f} min_row={min(fr):.4f}"
        f" index_ids_equal_frac={same} candidates_per_row="
        f"{sizes[:, 0].mean():.2f}/{sizes[:, 1].mean():.2f} empty_rows="
        f"{(sizes[:, 0] == 0).mean():.4f}/{(sizes[:, 1] == 0).mean():.4f}"
        f" nonfinite_exact={bad} bound={bound}")
    if ov < bound:
        raise SystemExit(f"{tag}: overlap {ov} below {bound}")


def heap_oracle_check(cfg, params, state, index, batch, use_kernel, fused):
    """Alg. 1 on the chip vs the numpy heap oracle, bit for bit.

    The chip runs serve stages 1-2 (user tower + cluster ranking, then
    the merge); the oracle re-merges the same cluster scores over slabs
    read on the host from the same index."""
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    s1 = jax.jit(retriever.serve_stage_rank,
                 static_argnames=("cfg", "use_kernel"))(
        params, state, cfg, jb, use_kernel=use_kernel)
    s2 = jax.jit(retriever.serve_stage_merge,
                 static_argnames=("cfg", "use_kernel", "fused"))(
        cfg, index, s1, use_kernel=use_kernel, fused=fused)
    top_s = np.asarray(s1["top_scores"])
    top_c = np.asarray(s1["top_clusters"])
    got_sc = np.asarray(s2["merge_scores"])
    got_ids = np.asarray(s2["cand_ids"])
    got_valid = np.asarray(s2["valid"])
    offs = np.asarray(index.offsets)
    counts = np.asarray(index.counts)
    bias = np.asarray(index.item_bias)
    ids = np.asarray(index.item_ids)
    n, L, S = int(index.n_items), 256, cfg.candidates_out
    starts = offs[top_c]
    lengths = np.minimum(counts[top_c], L)
    slab = np.minimum(starts[..., None] + np.arange(L), n - 1)
    bad = 0
    for q in range(top_s.shape[0]):
        pos, sc = merge_sort.merge_sort_serve_np(
            top_s[q], bias[slab[q]], lengths[q], cfg.chunk_size, S)
        k = len(pos)
        want_ids = ids[slab[q].reshape(-1)[pos]]
        ok = (got_valid[q, :k].all() and not got_valid[q, k:].any()
              and np.array_equal(got_sc[q, :k], sc.astype(np.float32))
              and np.array_equal(got_ids[q, :k], want_ids)
              and (got_sc[q, k:] == merge_sort.NEG).all())
        bad += not ok
    log(f"parity[heap_oracle]: queries={top_s.shape[0]} mismatched={bad} "
        f"use_kernel={use_kernel} fused={fused}")
    if bad:
        raise SystemExit(f"heap oracle: {bad} queries differ")
    return s2


def cpu_reference(cfg, params, state, index, batches):
    """The same jitted serve, lax path, on the host CPU backend."""
    cpu = jax.devices("cpu")[0]
    p, s, i = jax.device_put((params, state, index), cpu)
    fn = jax.jit(retriever.serve, static_argnames=("cfg",))
    outs = []
    t0 = time.perf_counter()
    for b in batches:
        out = fn(p, s, cfg, i, jax.device_put(b, cpu))
        outs.append({k: np.asarray(v) for k, v in out.items()})
    log(f"reference[cpu]: batches={len(batches)} "
        f"total_s={time.perf_counter() - t0:.3f}")
    return outs


def publish_delta(cfg, svc, batch, out):
    """Write a brand-new item into a served cluster; it must be served."""
    donor = int(out["item_ids"][0, 0])
    prev = svc.store_snapshot()
    slot = int(np.asarray(hash_ids(np.asarray([donor], np.int32),
                                   prev.capacity))[0])
    new_id = cfg.n_items - 1
    new_store = astore.write(prev, np.asarray([new_id], np.int32),
                             prev.cluster[np.asarray([slot])],
                             prev.item_emb[np.asarray([slot])],
                             np.asarray([1e6], np.float32))
    t0 = time.perf_counter()
    svc.apply_deltas(extract_deltas(prev, new_store,
                                    np.asarray([new_id], np.int32)))
    t_apply = time.perf_counter() - t0
    after = svc.serve_batch(batch)
    served = bool((after["index_ids"][0] == new_id).any())
    log(f"delta: new_item={new_id} apply_s={t_apply:.3f} "
        f"served_to_row0={served} delta_applies={svc.stats.delta_applies} "
        f"compactions={svc.stats.delta_compactions}")
    if not served:
        raise SystemExit("delta: the published item was not served")


def one_chip(cfg, args):
    stream, params, state = train(cfg, args)
    batches = user_batches(stream, SERVE_BATCHES, args.seed)
    paths = [(False, False)]
    if args.use_kernel:
        paths = [(True, False), (True, True)]
    ref = cpu_reference(cfg, params, state,
                        astore.build_serving_index(state.store,
                                                   cfg.n_clusters),
                        batches)
    lax_outs = None
    for use_kernel, fused in paths:
        tag = f"use_kernel={use_kernel},fused={fused}"
        t0 = time.perf_counter()
        svc = RetrievalService(cfg, params, state, use_kernel=use_kernel,
                               fused=fused, delta_spare=DELTA_SPARE)
        index = svc.index_generation.index
        log(f"index[{tag}]: build_s={time.perf_counter() - t0:.3f} "
            f"layout_items={int(index.n_items)} live_clusters="
            f"{int(jnp.sum(index.counts > 0))}/{cfg.n_clusters} "
            f"max_cluster={int(jnp.max(index.counts))}")
        outs = serve(svc, batches, tag)
        s2 = heap_oracle_check(cfg, params, state,
                               index, batches[0],
                               use_kernel, fused)
        log(f"parity[served_vs_staged]: index_ids_equal="
            f"{np.array_equal(outs[0]['index_ids'], np.asarray(s2['cand_ids']))}")
        compare(f"cpu_reference,{tag}", outs, ref, MIN_OVERLAP)
        # the same serve with f32-exact matmuls on the chip: what is left
        # against the CPU is accumulation order, not bf16 rounding
        with jax.default_matmul_precision("highest"):
            exact = serve(svc, batches, f"{tag},highest")
        compare(f"cpu_reference,{tag},highest", exact, ref,
                MIN_OVERLAP_EXACT)
        compare(f"default_vs_highest,{tag}", outs, exact, MIN_OVERLAP)
        if args.use_kernel:
            if lax_outs is None:
                lax_svc = RetrievalService(cfg, params, state,
                                           delta_spare=DELTA_SPARE)
                lax_outs = serve(lax_svc, batches, "lax on chip")
                del lax_svc
            compare(f"lax_on_chip,{tag}", outs, lax_outs, MIN_OVERLAP)
        publish_delta(cfg, svc, batches[0], outs[0])
        del svc


def four_chips(cfg, args):
    devices = jax.devices()
    if len(devices) != 4:
        raise SystemExit(f"--chips 4 needs 4 devices, found {len(devices)}")
    stream, params, state = train(cfg, args)
    batches = user_batches(stream, SERVE_BATCHES, args.seed)
    one = RetrievalService(cfg, params, state)
    want = serve(one, batches, "one device")
    del one
    svc = RetrievalService(cfg, params, state, n_shards=4,
                           mesh=make_serving_mesh(4))
    sidx = svc.index_generation.index
    for name in ("item_ids", "item_bias", "item_emb", "offsets", "counts"):
        arr = getattr(sidx, name)
        shards = arr.addressable_shards
        on = sorted(s.device.id for s in shards)
        rows = sorted(s.index[0].start for s in shards)
        ok = (on == sorted(d.id for d in devices)
              and all(s.data.shape[0] == arr.shape[0] // 4 for s in shards)
              and rows == [d * arr.shape[0] // 4 for d in range(4)])
        log(f"shards[{name}]: shape={arr.shape} devices={on} "
            f"rows_from={rows} quarter_each={ok}")
        if not ok:
            raise SystemExit(f"{name} is not split over the 4 devices")
    got = serve(svc, batches, "4 shards on 4 chips")
    compare("4_shards_vs_one_device", got, want, MIN_OVERLAP_EXACT)
    log(f"parity[4_shards_vs_one_device]: bitwise_equal_frac " + " ".join(
        f"{k}={np.mean([np.array_equal(g[k], w[k]) for g, w in zip(got, want)])}"
        for k in ("item_ids", "scores", "merge_scores", "exact_scores")))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--use-kernel", action="store_true",
                    help="train and serve through the Pallas kernels")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded serve over 4 chips")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r})",
              file=sys.stderr)
        return 1
    log(f"device: {dev.device_kind} x{len(jax.devices())} "
        f"jax={jax.__version__} compile_cache={enable_compile_cache()}")
    if args.chips == 4:
        four_chips(CONFIG, args)
    else:
        one_chip(CONFIG, args)
    log(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
