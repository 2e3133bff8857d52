"""Find a cell's knee: the highest offered rate its server sustains.

    python benchmarks/onchip/sweep.py --workload <cell> --seed <n> \
        --seconds 8 --rates 400 800 1200 1600

One process, one set-up: the cell's server is built once and driven open
loop at each rate in turn, each with its own requests.  A rate is
sustained when the requests still queued at the window's close stay
under ``--slack`` seconds of arrivals.  The knee is written into the
cell's traffic file by hand, with the sweep's lines in PERF.md.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import run
import traffic as traffic_lib


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--slack", type=float, default=0.5)
    args = ap.parse_args(argv)
    man, entry, dev, cfg, mix = run.setup_cell(args.workload, "tpu",
                                               run.ROOT, run.HERE, None)
    server = run.Server(cfg, args.seed, dev)
    probe = traffic_lib.make_requests(mix, cfg, args.seed, 1.0)
    server.warm(mix, probe)
    probe_batcher = server.batcher(mix)
    probe_batcher.close()
    for b in probe_batcher.buckets:
        rows = np.arange(b) % probe.due.size
        batch = dict(user_id=probe.user_id[rows], hist=probe.hist[rows])
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            server.serve(batch, int(mix["tasks"][0]))
            times.append(time.perf_counter() - t0)
        print(json.dumps({"bucket": b, "serve_batch_s": times}), flush=True)
    knee = None
    for i, rate in enumerate(args.rates):
        m = dict(mix, rate_per_s=rate)
        reqs = traffic_lib.make_requests(m, cfg, args.seed + 1 + i,
                                         args.seconds)
        res = server.open_loop(m, reqs, args.seconds)
        queued = int((~res.in_window).sum())
        held = queued <= args.slack * rate
        knee = rate if held else knee
        print(json.dumps({
            "rate_per_s": rate, "sustained": held, "queued_at_close": queued,
            "users_per_s": res.served / args.seconds,
            "p50_ms": traffic_lib.percentile(res.latency_s, 50) * 1e3,
            "p95_ms": traffic_lib.percentile(res.latency_s, 95) * 1e3,
            "rows_per_flush": res.batcher.served_rows
            / max(res.batcher.n_flushes, 1),
            "failed": res.failed}), flush=True)
    print(json.dumps({"knee_rate_per_s": knee,
                      "device": dev.device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
