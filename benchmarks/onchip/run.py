"""Run one cell of the on-chip benchmark once.

    python benchmarks/onchip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json`` at the root of the checkout: the configuration in
``configs/<config>.json``, the mix in ``traffic/<traffic>.json``, and each
per-layer metric's reader in ``metrics/<metric>.py``.  Nothing in this file
is specific to a cell.

A run: turns on the compile cache in the checkout and the program's
device annotations (traced or not, so both share one compiled program);
makes the weights and the corpus on the device from ``--seed``; builds
the ``RetrievalService`` through its normal constructor; warms the
cell's own batch shapes; makes the requests; then drives them open loop
through the service's micro-batcher for ``--seconds``.  With
``--trace 1`` the profiler records the window and the per-layer metrics
are reported instead of the end-to-end ones.  Once the window has closed
and the peak memory is read, a sample of the served requests is held
against the plain reference (``reference.py``).

The last line of standard output is one JSON object.  Without a TPU, or
with fewer chips than the cell asks for, the run prints no result and
exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                               # noqa: E402
import functools                                              # noqa: E402
import gc                                                     # noqa: E402
import importlib.util                                         # noqa: E402
import json                                                   # noqa: E402
import shutil                                                 # noqa: E402
import sys                                                    # noqa: E402
import tempfile                                               # noqa: E402
import threading                                              # noqa: E402
from pathlib import Path                                      # noqa: E402
from typing import Callable, Dict, List, NamedTuple, Optional                   # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax                                                    # noqa: E402
import numpy as np                                            # noqa: E402

import corpus as corpus_lib                                   # noqa: E402
import reference as ref_lib                                   # noqa: E402
import trace_reduce                                           # noqa: E402
import traffic as traffic_lib                                 # noqa: E402
import work                                                   # noqa: E402

SERVE_MODULE = "jit(_serve)"     # the service's jitted serve program
HOST_SPANS = ("serve_batch", "submit")
GRACE_S = 60.0                   # wait for answers past the window


class NoChip(RuntimeError):
    """The platform or the number of devices is not what the cell needs."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- finding things by name --------------------------------------------------

def manifest(root: Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_entry(man: Dict, name: str) -> Dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_entry(man: Dict, name: str) -> Dict:
    for c in man["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


# keys of a configuration file that describe it rather than set a field
DESCRIPTIVE_KEYS = ("source", "reduced", "assumed", "deployment",
                    "matmul_precision", "why_precision", "why_reduced")


def load_config(path: Path):
    """The SVQConfig a configuration file states.  Its descriptive keys
    (``DESCRIPTIVE_KEYS``; ``matmul_precision`` is read by
    ``setup_cell``) set no field; any other key that is not a field is an
    error, so a misspelt size cannot be ignored."""
    import dataclasses
    from repro.configs.base import SVQConfig
    raw = json.loads(Path(path).read_text())
    fields = {f.name for f in dataclasses.fields(SVQConfig)}
    unknown = set(raw) - fields - set(DESCRIPTIVE_KEYS)
    if unknown:
        raise KeyError(f"{Path(path).name}: not SVQConfig fields: "
                       f"{sorted(unknown)}")
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in raw.items() if k in fields}
    return SVQConfig(**kw)


def load_traffic(name: str, here: Path = HERE) -> Dict:
    return traffic_lib.check_mix(json.loads(
        (here / "traffic" / f"{name}.json").read_text()))


def metric_reader(name: str, here: Path = HERE) -> Callable:
    """``read(ctx) -> value or None`` from ``metrics/<name>.py``."""
    path = here / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "onchip_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(man: Dict, cell: str, kind: str):
    """The entries of ``end_to_end`` or ``per_layer`` this cell reports.

    An end-to-end metric with no ``workloads`` key is in every cell; a
    per-layer metric names its cells in ``workloads``, always."""
    if kind == "end_to_end":
        return [m for m in man["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]]
    for m in man["per_layer"]:
        if "workloads" not in m:
            raise KeyError(f"per-layer metric {m['name']!r} names no "
                           f"workloads")
    return [m for m in man["per_layer"] if cell in m["workloads"]]


# -- the run -------------------------------------------------------------------

def check_devices(chips: int, platform: str) -> jax.Device:
    devs = jax.devices()
    if devs[0].platform != platform:
        raise NoChip(f"JAX found {devs[0].platform!r}, not {platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[0]


def peak_bytes(dev) -> Optional[int]:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


class CompileCounter:
    """Counts the programs JAX traces for compilation, so a run can show
    that nothing compiled inside its window."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if name == "/jax/core/compile/jaxpr_trace_duration":
            self.n += 1


class OpenLoop(NamedTuple):
    window: traffic_lib.Window
    outs: List                # each request's answer, None if it failed
    failed: int
    latency_s: np.ndarray     # completion minus due time, per request
    in_window: np.ndarray     # completed before the window closed
    batcher: object
    served: float             # users served in the window (Flushes)


class Server:
    """The system under test at one cell's configuration: weights and
    corpus from the seed, the service built through its normal
    constructor, its ``serve_batch`` wrapped so the harness records a host
    span and each request's completion time."""

    def __init__(self, cfg, seed: int, dev):
        from repro.serving import RetrievalService
        self.cfg = cfg
        self.params, self.corpus = corpus_lib.make_all(seed, cfg)
        self.svc = RetrievalService(cfg, self.params,
                                    corpus_lib.index_state(cfg, self.corpus))
        jax.block_until_ready(self.svc.index_generation.index)
        report = corpus_lib.index_report(np.asarray(self.corpus.cluster),
                                         cfg.n_clusters,
                                         self.svc.items_per_cluster)
        log(report.line() + f" peak_bytes_in_use={peak_bytes(dev)}")
        self.done: Optional[traffic_lib.Completions] = None
        self.flush_rows: List[int] = []     # padded rows of each flush
        self.flushes = traffic_lib.Flushes()
        self.compiles = CompileCounter()
        self.serve = serve = self.svc.serve_batch

        @functools.wraps(serve)
        def traced_serve(batch, task=0, **kw):
            req = batch.pop("req")
            n_valid = kw.get("n_valid", len(req))
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("serve_batch"):
                out = serve(batch, task, **kw)
            t1 = time.perf_counter()
            self.done.mark(req[:n_valid], t1)
            self.flushes.add(t0, t1, n_valid)
            self.flush_rows.append(len(req))
            return out

        self.svc.serve_batch = traced_serve

    def batcher(self, mix: Dict):
        """The service's micro-batcher with the mix's settings; without
        ``buckets`` in the mix, the program's own."""
        return self.svc.make_batcher(max_batch=mix["max_batch"],
                                     max_delay_s=mix["max_delay_s"],
                                     buckets=mix.get("buckets"))

    def warm(self, mix: Dict, reqs: traffic_lib.Requests) -> None:
        """Compile every (task, bucket) shape the mix's batcher can
        flush."""
        probe = self.batcher(mix)
        buckets = probe.buckets
        probe.close()
        for task in mix["tasks"]:
            for b in buckets:
                rows = np.arange(b) % reqs.due.size
                jax.block_until_ready(self.serve(
                    dict(user_id=reqs.user_id[rows], hist=reqs.hist[rows]),
                    task))

    def open_loop(self, mix: Dict, reqs: traffic_lib.Requests,
                  seconds: float, before_window=None,
                  after_window=None) -> OpenLoop:
        """Drive ``reqs`` through a fresh micro-batcher, then wait for
        every answer (at most GRACE_S past the close)."""
        n = reqs.due.size
        self.done = traffic_lib.Completions(n)
        self.flush_rows = []
        self.flushes = traffic_lib.Flushes()
        batcher = self.batcher(mix)
        payloads = [dict(user_id=reqs.user_id[i:i + 1],
                         hist=reqs.hist[i:i + 1], req=np.array([i]))
                    for i in range(n)]

        def submit(i):
            with jax.profiler.TraceAnnotation("submit"):
                return batcher.submit(payloads[i], int(reqs.task[i]))

        gc.collect()
        gc.freeze()
        if before_window is not None:
            before_window()
        compiles0 = self.compiles.n
        window = traffic_lib.drive(submit, reqs, seconds)
        compiles = self.compiles.n - compiles0
        t_close = window.t0 + seconds
        if after_window is not None:
            after_window()
        waiter = threading.Thread(target=batcher.close, daemon=True)
        waiter.start()
        waiter.join(GRACE_S)
        outs, failed = [], 0
        for fut in window.futures:
            try:
                outs.append(fut.result(
                    timeout=max(t_close + GRACE_S - time.perf_counter(),
                                0.0)))
            except Exception:               # an error or no answer at all
                outs.append(None)
                failed += 1
        gc.unfreeze()
        lat = traffic_lib.latencies(reqs, window, self.done)
        lat = np.where(np.isfinite(lat), lat, GRACE_S + seconds)
        in_window = self.done.t_done <= t_close
        late = window.lateness_s
        log(f"window: rate_per_s={mix['rate_per_s']} requests={n} "
            f"completed_in_window={int(in_window.sum())} failed={failed} "
            f"queued_at_close={int((~in_window).sum())} "
            f"p50_ms={traffic_lib.percentile(lat, 50) * 1e3} "
            f"p95_ms={traffic_lib.percentile(lat, 95) * 1e3} "
            f"p99_ms={traffic_lib.percentile(lat, 99) * 1e3} "
            f"users_per_s={self.flushes.served_by(t_close) / seconds} "
            f"whole_flushes_users_per_s={in_window.sum() / seconds} "
            f"generator_late_p99_ms={traffic_lib.percentile(late, 99) * 1e3}"
            f" generator_late_max_ms={late.max() * 1e3} "
            f"flushes={batcher.n_flushes} served_rows={batcher.served_rows} "
            f"padded_rows={batcher.padded_rows} "
            f"compiles_in_window={compiles}")
        log(f"flush shapes in order (rows x flushes): "
            f"{run_lengths(self.flush_rows)}")
        return OpenLoop(window=window, outs=outs, failed=failed,
                        latency_s=lat, in_window=in_window, batcher=batcher,
                        served=self.flushes.served_by(t_close))


def run_lengths(values: List[int]) -> str:
    """'4x1 8x2 256x40': each value with how many times it came in a
    row."""
    out: List[List[int]] = []
    for v in values:
        if out and out[-1][0] == v:
            out[-1][1] += 1
        else:
            out.append([v, 1])
    return " ".join(f"{v}x{k}" for v, k in out)


def setup_cell(cell: str, platform: str, root: Path, here: Path, config):
    """-> (manifest, cell entry, device, config, mix), the compile cache
    and the program's device annotations turned on."""
    from repro.obs import trace as obs_trace
    from repro.utils.compile_cache import enable_compile_cache
    man = manifest(root)
    entry = cell_entry(man, cell)
    dev = check_devices(entry["chips"], platform)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    obs_trace.enable_device_annotations(True)
    cfg_file = root / config_entry(man, entry["config"])["file"]
    # the precision the configuration states for float32 matmuls, set
    # before anything compiles
    jax.config.update("jax_default_matmul_precision", json.loads(
        cfg_file.read_text())["matmul_precision"])
    cfg = config if config is not None else load_config(cfg_file)
    mix = load_traffic(entry["traffic"], here)
    driver(mix, entry["traffic"])
    log(f"cell: {cell} config={cfg.name} traffic={entry['traffic']} "
        f"device={dev.device_kind} x{len(jax.devices())}")
    return man, entry, dev, cfg, mix


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, here: Path = HERE, t_start: float = T_START,
             platform: str = "tpu", config=None) -> Dict:
    """One run of one cell -> the result object.  ``config`` replaces
    the cell's configuration (tests run a small one on the CPU)."""
    man, entry, dev, cfg, mix = setup_cell(cell, platform, root, here,
                                           config)
    return driver(mix, entry["traffic"])(man, cell, dev, cfg, mix, seed,
                                         seconds, trace, here, t_start)


def serve_open_loop(man: Dict, cell: str, dev, cfg, mix: Dict, seed: int,
                    seconds: float, trace: bool, here: Path,
                    t_start: float) -> Dict:
    """A serve cell: requests driven open loop through the service's
    micro-batcher, a sample of the answers held against the reference."""
    server = Server(cfg, seed, dev)
    reqs = traffic_lib.make_requests(mix, cfg, seed, seconds)
    server.warm(mix, reqs)
    trace_dir = tempfile.mkdtemp(prefix="onchip_trace_") if trace else None
    clock = {}

    def start():
        clock["setup_s"] = time.perf_counter() - t_start
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # keep the host's cost small
            jax.profiler.start_trace(trace_dir, profiler_options=opts)

    def stop():
        if trace:
            jax.profiler.stop_trace()

    log(f"setup: seed={seed} requests={reqs.due.size} "
        f"peak_bytes_in_use={peak_bytes(dev)}")
    run = server.open_loop(mix, reqs, seconds, before_window=start,
                           after_window=stop)
    setup_s = clock["setup_s"]
    log(f"setup: setup_s={setup_s}")
    n, failed, outs = reqs.due.size, run.failed, run.outs
    e2e = {
        "serve_p95_ms": traffic_lib.percentile(run.latency_s, 95) * 1e3,
        "serve_users_per_s": run.served / seconds,
        "setup_s": setup_s,
    }
    mem = peak_bytes(dev)
    svc, batcher = server.svc, run.batcher
    params, corpus = server.params, server.corpus

    result = {"correct": False, "attempted": n, "failed": failed}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    if trace:
        tr = trace_reduce.load(trace_reduce.find_trace(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        # the window in the trace's clock: from the first submit on
        lo = trace_reduce.first_span_ns(tr, "submit")
        busy = trace_reduce.busy_s(tr, lo, lo + seconds * 1e9) \
            if lo is not None else 0.0
        device["busy_s"] = busy
        device["window_s"] = seconds
        ctx = dict(trace=tr, busy_s=busy, window_s=seconds, batcher=batcher,
                   stats=svc.stats, cfg=cfg, e2e=e2e, module=SERVE_MODULE,
                   peaks=work.peaks(dev.device_kind))
        metrics = {}
        for m in metrics_of(man, cell, "per_layer"):
            v = metric_reader(m["name"], here)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(tr),
            "idle_gaps": trace_reduce.idle_gaps(tr, HOST_SPANS)}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in metrics_of(man, cell, "end_to_end")}
    result["metrics"] = metrics
    result["device"] = device

    # -- correct: a seeded sample of the answers against the reference -----
    del svc, batcher, server
    answered = np.flatnonzero([o is not None for o in outs])
    rng = np.random.default_rng([seed, 0xc0ec7])
    pick = np.sort(rng.choice(answered, min(mix["check_requests"],
                                            answered.size), replace=False))
    nums = {}
    if pick.size:
        got = ref_lib.served_from_outputs([outs[i] for i in pick])
        ref = ref_lib.Reference(cfg, params, corpus)
        nums.update(ref_lib.compare(ref, reqs.user_id[pick],
                                    reqs.hist[pick], reqs.task[pick], got))
    checks = {k: {"value": nums.get(k), "limit": lim}
              for k, lim in ref_lib.LIMITS.items()}
    result["correct"] = bool(failed == 0 and pick.size
                             and ref_lib.verdict(nums))
    for k, c in checks.items():
        log(f"check {k}: {c['value']} limit {c['limit']}")
    result["checks"] = checks
    return result


# the driver of each traffic ``kind``; a mix of any other kind is refused
KINDS = {"serve_open_loop": serve_open_loop}


def driver(mix: Dict, name: str) -> Callable:
    if mix["kind"] not in KINDS:
        raise ValueError(f"traffic {name!r}: no driver for kind "
                         f"{mix['kind']!r} (known: {sorted(KINDS)})")
    return KINDS[mix["kind"]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        log(f"run: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
