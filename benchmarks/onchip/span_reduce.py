"""The program's own names against the device trace.

Built on ``trace_reduce``'s lists.  Two kinds of name, both written by
the program itself:

- named scopes, which the serve step's ops carry in their ``tf_op``:
  ``SERVE_SCOPES`` tile ``jit(_serve)``, so their device times and the
  time outside all of them (the residue) add up to the module's;
- host spans, ``jax.profiler.TraceAnnotation`` events on the device
  ops' clock: ``batcher.*`` from the micro-batcher's worker and
  ``serve.*`` from ``RetrievalService.serve_batch``.  They run one after
  another on the worker's thread, so each idle nanosecond of the device
  lies under at most one of them.

The harness's own spans (``serve_batch``, ``submit``) are not the
program's: ``submit`` only marks the window's start, as for every
reader.  A trace without program spans (a program that does not write
them) reads None, not 0.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from trace_reduce import Trace, first_span_ns, module_runs

# the serve step's scopes, in program order; fused_gather_rank takes
# merge_serve's place on the fused path
SERVE_SCOPES = ("user_tower", "cluster_rank", "slab_gather", "merge_serve",
                "fused_gather_rank", "cand_gather", "rank_features",
                "rank_score")

# host spans by what the device waits on; a name ending in "." is a prefix
LAUNCH = ("serve.put", "serve.dispatch")
FETCH = ("serve.fetch",)
BATCHER = ("batcher.",)
PROGRAM = ("batcher.", "serve.")

Interval = Tuple[float, float]


def _named(name: str, names: Sequence[str]) -> bool:
    return any(name.startswith(n) if n.endswith(".") else name == n
               for n in names)


def spans(tr: Trace, names: Sequence[str]) -> List[Interval]:
    """[start, end) of the host spans matching ``names``, as the union
    of their intervals, sorted."""
    iv = sorted((sp.start_ns, sp.start_ns + sp.dur_ns) for sp in tr.host
                if _named(sp.name, names))
    out: List[Interval] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def idle_intervals(tr: Trace, lo: float, hi: float) -> List[Interval]:
    """The first device's gaps between ops, clipped to [lo, hi), sorted.
    Time before the first op or after the last is no gap: the trace did
    not record what ran there (the profiler starts after the first
    flush's wait began and stops while the last flush is in flight)."""
    dev = min((op.device for op in tr.ops), default=None)
    busy = sorted((op.start_ns, op.start_ns + op.dur_ns) for op in tr.ops
                  if op.device == dev)
    out: List[Interval] = []
    end = None
    for s, e in busy:
        if end is not None and s > end:
            a, b = max(end, lo), min(s, hi)
            if b > a:
                out.append((a, b))
        end = e if end is None else max(end, e)
    return out


def overlap_ns(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def window_idle(ctx) -> Optional[List[Interval]]:
    """The idle intervals of the window that starts at the first submit,
    or None where the trace has no submit or no program span."""
    tr = ctx["trace"]
    lo = first_span_ns(tr, "submit")
    if lo is None or not spans(tr, PROGRAM):
        return None
    return idle_intervals(tr, lo, lo + ctx["window_s"] * 1e9)


def idle_ms_per_run(ctx, names: Sequence[str]) -> Optional[float]:
    """Device-idle ms per run of the serve module under the host spans
    ``names``."""
    idle = window_idle(ctx)
    runs = module_runs(ctx["trace"], ctx["module"])
    if idle is None or runs == 0:
        return None
    return overlap_ns(idle, spans(ctx["trace"], names)) / 1e6 / runs


def idle_unattributed_pct(ctx) -> Optional[float]:
    """Share of the window's device-idle time, %, under no program
    span."""
    idle = window_idle(ctx)
    if idle is None:
        return None
    total = sum(e - s for s, e in idle)
    if total <= 0:
        return None
    named = overlap_ns(idle, spans(ctx["trace"], PROGRAM))
    return 100.0 * (total - named) / total
