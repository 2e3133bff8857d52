"""Reduce a JAX profiler trace to device times.

The profiler writes, beside its ``.xplane.pb``, the same trace as
Chrome trace-event JSON (``*.trace.json.gz``).  ``load`` reads that file,
because it carries each device operation's ``tf_op``: the jitted
module and the ``jax.named_scope`` path the program gave it, which
``jax.profiler.ProfileData`` does not expose.  It yields three plain
lists: the device's operations, its module runs, and the host's spans.
Everything else is arithmetic on those lists:

- ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices;
- ``scope_s``: device time of the operations inside a named scope;
- ``module_s`` / ``module_runs``: device time and run count by jitted
  module;
- ``top_ops``: the operations that took most device time, by name;
- ``idle_gaps``: the longest gaps with no operation on the device, each
  labelled by the harness span that overlaps it most.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Op(NamedTuple):
    device: str
    name: str
    start_ns: float
    dur_ns: float
    module: str         # jitted module, e.g. "jit(_serve)"
    scope: str          # the op's tf_op: module / scopes / primitive
    self_ns: float = 0.0    # duration less that of the ops nested in it


class Span(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float


class Trace(NamedTuple):
    ops: List[Op]
    modules: List[Op]
    host: List[Span]
    n_devices: int


def find_trace(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.trace.json.gz"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .trace.json.gz under {log_dir}")
    return max(files, key=os.path.getmtime)


def _is_device(process: str) -> bool:
    return process.startswith("/device:TPU:")


def load(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    process, thread = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            process[e["pid"]] = e["args"]["name"]
        elif e.get("name") == "thread_name":
            thread[(e["pid"], e["tid"])] = e["args"]["name"]
    ops: List[Op] = []
    modules: List[Op] = []
    host: List[Span] = []
    devices = set()
    for e in events:
        if e.get("ph") != "X":
            continue
        proc = process.get(e["pid"], "")
        start, dur = float(e["ts"]) * 1e3, float(e.get("dur", 0.0)) * 1e3
        if _is_device(proc):
            devices.add(proc)
            line = thread.get((e["pid"], e["tid"]), "")
            if line not in (OPS_LINE, MODULES_LINE):
                continue
            tf_op = e.get("args", {}).get("tf_op", "")
            head = tf_op.split("/")[0]
            op = Op(device=proc, name=e["name"], start_ns=start, dur_ns=dur,
                    module=head if head.startswith("jit(") else "",
                    scope=tf_op, self_ns=dur)
            (ops if line == OPS_LINE else modules).append(op)
        elif proc.startswith("/host:"):
            host.append(Span(e["name"], start, dur))
    ops = _self_times(_attach_modules(ops, modules))
    return Trace(ops=ops, modules=modules, host=host,
                 n_devices=max(len(devices), 1))


def _module_name(run_name: str) -> str:
    """'jit__serve(1735...)' (a module run) -> 'jit(_serve)' (tf_op)."""
    base = run_name.split("(")[0]
    return f"jit({base[4:]})" if base.startswith("jit_") else base


def _attach_modules(ops: List[Op], modules: List[Op]) -> List[Op]:
    """Give each op without a tf_op the module run enclosing it."""
    by_dev: Dict[str, List[Op]] = {}
    for m in modules:
        by_dev.setdefault(m.device, []).append(m)
    for runs in by_dev.values():
        runs.sort(key=lambda m: m.start_ns)
    out = []
    for op in ops:
        if op.module:
            out.append(op)
            continue
        name = ""
        for m in by_dev.get(op.device, ()):
            if m.start_ns <= op.start_ns <= m.start_ns + m.dur_ns:
                name = _module_name(m.name)
                break
            if m.start_ns > op.start_ns:
                break
        out.append(op._replace(module=name))
    return out


def _self_times(ops: List[Op]) -> List[Op]:
    """Subtract from each op the time of the ops directly nested in it
    (a while loop encloses its body's ops on the same line)."""
    ops = sorted(ops, key=lambda o: (o.device, o.start_ns, -o.dur_ns))
    self_ns = [o.dur_ns for o in ops]
    stack: List[int] = []
    for i, op in enumerate(ops):
        while stack and (ops[stack[-1]].device != op.device
                         or ops[stack[-1]].start_ns + ops[stack[-1]].dur_ns
                         <= op.start_ns):
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= op.dur_ns
        stack.append(i)
    return [o._replace(self_ns=max(t, 0.0)) for o, t in zip(ops, self_ns)]


def union_ns(intervals: Iterable[Sequence[float]]) -> float:
    """Length of the union of [start, start + dur) intervals."""
    total, end = 0.0, None
    for s, d in sorted((float(s), float(d)) for s, d in intervals):
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def busy_s(tr: Trace, lo_ns: float = -float("inf"),
           hi_ns: float = float("inf")) -> float:
    """Seconds in which some operation ran, averaged over devices, within
    [lo_ns, hi_ns) of the trace's clock."""
    per_dev: Dict[str, list] = {}
    for op in tr.ops:
        s, e = max(op.start_ns, lo_ns), min(op.start_ns + op.dur_ns, hi_ns)
        if e > s:
            per_dev.setdefault(op.device, []).append((s, e - s))
    if not per_dev:
        return 0.0
    return sum(union_ns(v) for v in per_dev.values()) / 1e9 / tr.n_devices


def in_scope(op: Op, scope: str) -> bool:
    """Whether the op lies in the named scope: one element of its
    '/'-separated tf_op path."""
    return scope in op.scope.split("/")


def scope_primitives(tr: Trace, scope: str,
                     module: Optional[str] = None) -> set:
    """The primitives (last element of the tf_op path, as ``top_k``) of
    the ops inside ``scope``."""
    return {op.scope.rsplit("/", 1)[-1].rstrip(":") for op in tr.ops
            if _matches(op.module, module) and in_scope(op, scope)}


def _matches(module: str, pattern: Optional[str]) -> bool:
    return pattern is None or pattern in module


def scope_s(tr: Trace, scopes: Sequence[str],
            module: Optional[str] = None) -> float:
    """Device seconds of ops inside any of ``scopes`` (per device)."""
    return sum(op.self_ns for op in tr.ops
               if _matches(op.module, module)
               and any(in_scope(op, s) for s in scopes)) / 1e9 \
        / tr.n_devices


def module_s(tr: Trace, module: str) -> float:
    """Device seconds of the ops of modules whose name holds ``module``
    (as in tf_op: ``jit(_serve)``)."""
    return sum(op.self_ns for op in tr.ops if module in op.module) / 1e9 \
        / tr.n_devices


def module_runs(tr: Trace, module: str) -> int:
    """Runs of modules whose name holds ``module``, per device."""
    runs = [m for m in tr.modules if module in _module_name(m.name)]
    return round(len(runs) / tr.n_devices)


def top_ops(tr: Trace, n: int = 10) -> List[list]:
    """[name, device seconds] of the n ops that took most time (self
    time: a loop's body ops count for themselves), named by their tf_op
    (module / scopes / primitive) where they have one."""
    acc: Dict[str, float] = {}
    for op in tr.ops:
        key = op.scope or op.name
        acc[key] = acc.get(key, 0.0) + op.self_ns
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9 / tr.n_devices] for k, v in best]


def first_span_ns(tr: Trace, name: str) -> Optional[float]:
    """Start of the earliest host span called ``name``."""
    starts = [sp.start_ns for sp in tr.host if sp.name == name]
    return min(starts) if starts else None


def idle_gaps(tr: Trace, labels: Sequence[str], n: int = 10) -> List[list]:
    """[label, seconds] of the n longest gaps between device ops (first
    device), the label naming the host span among ``labels`` that
    overlaps the gap most ("no harness span" where none does)."""
    if not tr.ops:
        return []
    dev = tr.ops[0].device
    iv = sorted((op.start_ns, op.start_ns + op.dur_ns)
                for op in tr.ops if op.device == dev)
    gaps = []
    end = iv[0][1]
    for s, e in iv[1:]:
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = [sp for sp in tr.host if sp.name in labels]
    out = []
    for a, b in gaps[:n]:
        best, best_ov = "no harness span", 0.0
        for sp in spans:
            ov = min(b, sp.start_ns + sp.dur_ns) - max(a, sp.start_ns)
            if ov > best_ov:
                best, best_ov = sp.name, ov
        out.append([best, (b - a) / 1e9])
    return out


def ms_per_run(tr: Trace, module: str, scopes: Sequence[str] = (),
               outside: bool = False) -> Optional[float]:
    """Device milliseconds per run of ``module``: of its ops in any of
    ``scopes``, or with ``outside`` of its ops in none of them.  None
    when the trace holds no run of the module or no such op."""
    runs = module_runs(tr, module)
    if runs == 0:
        return None
    inside = scope_s(tr, scopes, module) if scopes else 0.0
    t = module_s(tr, module) - inside if outside else inside
    return t * 1e3 / runs if t > 0 else None
