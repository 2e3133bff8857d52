"""Readings of the control, and of faults planted in the reference put in
the program's place, held by the same numbers as the program.

    python benchmarks/onchip/control.py --workload <cell> --seeds 1 2 3 \
        [--faults control reverse_heap truncated_merge half_empty]

The control is the reference computed at "high" precision (three
bfloat16 passes per product).  Each fault is the reference at
``highest`` precision serving with one fault planted:

- ``reverse_heap``: Alg. 1 pops the cluster with the lowest head score
  first (each list still read from its head, each score still
  ``u . e_c + b``);
- ``truncated_merge``: the merge stops after its first chunk;
- ``half_empty``: the second half of the requests get no candidates.

For each seed it makes the cell's weights, corpus and requests as a run
does, draws the same sample of requests a run compares, serves them so
and compares that with the float32 reference.  One JSON line per seed;
the limits in ``reference.LIMITS`` sit between these readings and the
program's.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run
import corpus as corpus_lib
import reference as ref_lib
import traffic as traffic_lib


def _reversed_heap(ref, args):
    """The reference's serve with Alg. 1's heap ordered lowest first."""
    merge = ref_lib.heap_merge

    def lowest_first(scores, clusters, lists, bias, cap, chunk, target,
                     add):
        # negated keys pop the lowest head first; negating a float32
        # sum is exact, so the scores come back as u . e_c + b
        rows, merged = merge(-scores, clusters, lists, -bias, cap, chunk,
                             target, add)
        return rows, -merged

    ref_lib.heap_merge = lowest_first
    try:
        return ref.serve(*args)
    finally:
        ref_lib.heap_merge = merge


def _drop(got: ref_lib.Served, keep: np.ndarray) -> ref_lib.Served:
    """``got`` with the merge lanes not in ``keep`` (M, S) made invalid,
    and the ranking order rebuilt over the rest."""
    ids = np.where(keep, got.ids, -1)
    neg = lambda a: np.where(keep, a, ref_lib.NEG)
    ranked_keep = np.stack([np.isin(r, i[i >= 0]) for r, i in
                            zip(got.ranked_ids, ids)]) & got.ranked_valid
    # invalid lanes sort last, as the program's ranking puts them
    order = np.argsort(~ranked_keep, axis=1, kind="stable")
    take = lambda a: np.take_along_axis(a, order, 1)
    return ref_lib.Served(
        ids=ids, merge=neg(got.merge), exact=neg(got.exact),
        ranked_ids=take(got.ranked_ids),
        ranked=take(np.where(ranked_keep, got.ranked, ref_lib.NEG)),
        ranked_valid=take(ranked_keep))


def served_with_fault(ref, args, fault: str, chunk: int) -> ref_lib.Served:
    if fault == "reverse_heap":
        return _reversed_heap(ref, args)
    got = ref.serve(*args)
    m, s = got.ids.shape
    if fault == "truncated_merge":
        return _drop(got, np.broadcast_to(np.arange(s) < chunk, (m, s)))
    if fault == "half_empty":
        return _drop(got, np.broadcast_to(np.arange(m)[:, None] < m // 2,
                                          (m, s)))
    raise ValueError(f"unknown fault {fault!r}")


def readings(cell: str, seed: int, seconds: float, config=None,
             faults=("control",)) -> dict:
    """{fault: the numbers compared} for one seed; "control" is the
    reference at "high"."""
    man = run.manifest()
    entry = run.cell_entry(man, cell)
    cfg = config if config is not None else run.load_config(
        run.ROOT / run.config_entry(man, entry["config"])["file"])
    mix = run.load_traffic(entry["traffic"])
    params, corpus = corpus_lib.make_all(seed, cfg)
    reqs = traffic_lib.make_requests(mix, cfg, seed, seconds)
    rng = np.random.default_rng([seed, 0xc0ec7])
    pick = np.sort(rng.choice(reqs.due.size, min(mix["check_requests"],
                                                 reqs.due.size),
                              replace=False))
    ref = ref_lib.Reference(cfg, params, corpus)
    args = (reqs.user_id[pick], reqs.hist[pick], reqs.task[pick])
    out = {}
    for fault in faults:
        if fault == "control":
            got = ref_lib.Reference(cfg, params, corpus,
                                    precision="high").serve(*args)
        else:
            got = served_with_fault(ref, args, fault, cfg.chunk_size)
        out[fault] = ref_lib.compare(ref, *args, got)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--faults", nargs="+", default=["control"],
                    choices=("control", "reverse_heap", "truncated_merge",
                             "half_empty"))
    args = ap.parse_args(argv)
    # the compile cache, annotations and matmul precision of a run, so
    # the corpus is made as a run makes it
    run.setup_cell(args.workload, "tpu", run.ROOT, run.HERE, None)
    for seed in args.seeds:
        for fault, nums in readings(args.workload, seed, args.seconds,
                                    faults=args.faults).items():
            print(json.dumps({"seed": seed, "fault": fault,
                              "readings": nums}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
