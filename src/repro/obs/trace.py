"""Lightweight request tracing for the serving pipeline.

One *trace* is the life of one serve request: a unique trace ID plus
the named *spans* it passed through — its queue wait, then the host
phases of the micro-batcher flush that served it (``batcher.*``) and of
``serve_batch`` (``serve.put``, ``serve.dispatch``, ``serve.fetch``),
every one carrying the flush's sequence number, so a request joins the
same flush's spans in a device profile.  A traced request runs the same
jitted program as every other request.

Traces are cheap host objects: a span is a (name, start, end, thread)
record on ``time.monotonic()``; recording one is two clock reads and a
list append, so the serve path stays benchmarkably flat when tracing is
on (see ``benchmarks/bench_observability.py``).

Completed traces land in a LOCK-EXACT bounded ring buffer: with
capacity R, after finishing N traces the buffer holds exactly the last
``min(N, R)`` and ``n_dropped == max(N - R, 0)`` — no tolerance, which
the concurrency suite asserts from N threads.

``export_chrome_trace()`` emits Chrome trace-event JSON (the
"traceEvents" array form) loadable in Perfetto / chrome://tracing;
every event carries its trace ID in ``args`` so one request's spans
can be filtered across threads.  Those timestamps are on the monotonic
clock, not on a device profile's.

Two primitives put the program's own names into a ``jax.profiler``
trace, both off unless ``enable_device_annotations()`` was called:

- ``span(name, sink, **args)`` is the host primitive.  Around eager host
  work it opens a ``jax.profiler.TraceAnnotation``, which the profiler
  writes into its own trace on the device ops' clock, with ``args`` as
  the event's arguments; given a ``sink`` list it also appends a
  monotonic-clock ``Span`` for the ``Tracer``.
- ``annotate(name)`` is the device scope: a ``jax.named_scope`` inside
  a jitted function, which names the function's ops in their HLO
  metadata (a device op's ``tf_op`` in the trace).  It changes metadata
  only, never an op.  Around code that is not traced it names nothing:
  use ``span`` there.

Both make no jax call when annotations are off and no sink is given.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import json
import threading
import time
from collections import deque
from typing import Deque, Dict, Iterator, List, Optional

from repro.obs.sampling import CounterSampler

# -- optional device-profile bridging ---------------------------------------

_DEVICE_ANNOTATIONS = False


def enable_device_annotations(on: bool = True) -> None:
    """Write ``span`` and ``annotate`` names into jax profiles (opt-in;
    set it before the annotated functions are traced and compiled, or
    their ops carry no scope).

    It also puts op metadata into JAX's persistent compile-cache key.
    The key leaves metadata out by default, so a program compiled with
    other scopes, or none, would be loaded from the cache and its ops
    would carry those names in the profile.  The metadata then holds op
    names only, no Python traceback, so the key does not change with
    the checkout's path or the calling script.  Turning annotations off
    restores JAX's defaults for both settings."""
    global _DEVICE_ANNOTATIONS
    _DEVICE_ANNOTATIONS = bool(on)
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      _DEVICE_ANNOTATIONS)
    jax.config.update("jax_traceback_in_locations_limit",
                      0 if _DEVICE_ANNOTATIONS else 10)


def device_annotations_enabled() -> bool:
    return _DEVICE_ANNOTATIONS


@contextlib.contextmanager
def annotate(name: str):
    """Device scope: ``jax.named_scope(name)`` around code a jit traces,
    so its ops carry ``name`` in their metadata.  No-op unless
    ``enable_device_annotations()`` was called before the tracing."""
    if not _DEVICE_ANNOTATIONS:
        yield
        return
    import jax
    with jax.named_scope(name):
        yield


@contextlib.contextmanager
def span(name: str, sink: Optional[List["Span"]] = None,
         **args) -> Iterator[Dict[str, object]]:
    """Host span around eager work: a ``jax.profiler.TraceAnnotation``
    with ``args`` as event arguments when device annotations are on,
    and a monotonic-clock ``Span`` appended to ``sink`` when it is a
    list.  Yields ``args``: keys the body adds (values known only at the
    end) reach both copies.  With annotations off and no sink it is one
    branch."""
    if not _DEVICE_ANNOTATIONS and sink is None:
        yield args
        return
    ann = None
    if _DEVICE_ANNOTATIONS:
        import jax
        ann = jax.profiler.TraceAnnotation(name, **args)
        ann.__enter__()
    first = set(args)
    t0 = time.monotonic()
    try:
        yield args
    finally:
        if ann is not None:
            late = {k: v for k, v in args.items() if k not in first}
            if late:
                ann.set_metadata(**late)
            ann.__exit__(None, None, None)
        if sink is not None:
            sink.append(make_span(name, t0, **args))


# -- spans + traces ---------------------------------------------------------

@dataclasses.dataclass
class Span:
    """One named interval on the ``time.monotonic()`` clock."""
    name: str
    t_start: float
    t_end: float
    thread_id: int = 0
    attrs: Optional[Dict[str, object]] = None

    @property
    def duration_s(self) -> float:
        return self.t_end - self.t_start


def make_span(name: str, t_start: float, t_end: Optional[float] = None,
              **attrs) -> Span:
    return Span(name=name, t_start=t_start,
                t_end=time.monotonic() if t_end is None else t_end,
                thread_id=threading.get_ident(),
                attrs=attrs or None)


class Trace:
    """One request's spans under one trace ID (single-writer: the
    thread driving the request appends; the ring buffer owns it only
    after ``Tracer.finish``)."""

    __slots__ = ("trace_id", "name", "t_start", "t_end", "spans", "attrs")

    def __init__(self, trace_id: int, name: str,
                 attrs: Optional[Dict[str, object]] = None):
        self.trace_id = trace_id
        self.name = name
        self.t_start = time.monotonic()
        self.t_end: Optional[float] = None
        self.spans: List[Span] = []
        self.attrs: Dict[str, object] = dict(attrs or {})

    def add_span(self, span: Span) -> Span:
        self.spans.append(span)
        return span

    @contextlib.contextmanager
    def span(self, name: str, **attrs) -> Iterator[Span]:
        t0 = time.monotonic()
        s = make_span(name, t0, t0, **attrs)
        try:
            yield s
        finally:
            s.t_end = time.monotonic()
            self.spans.append(s)

    @property
    def duration_s(self) -> float:
        end = self.t_end if self.t_end is not None else time.monotonic()
        return end - self.t_start


class Tracer:
    """Trace factory + bounded completed-trace ring buffer.

    ``sample_every=k`` keeps tracing affordable under heavy traffic:
    every k-th started request is traced (a deterministic
    ``obs/sampling.py`` counter, not a PRNG, so tests and benchmarks are
    reproducible); ``k=1`` traces all.  ``enabled=False`` short-circuits
    every entry point to one branch.  Pass ``sampler=`` to SHARE one
    sampling decision stream with another consumer (e.g. a
    ``QualityProber``), so sampled traces and probes are the same
    requests.
    """

    def __init__(self, capacity: int = 1024, enabled: bool = True,
                 sample_every: int = 1,
                 sampler: Optional[CounterSampler] = None):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self.enabled = enabled
        self._sampler = sampler if sampler is not None \
            else CounterSampler(every=sample_every)
        self.sample_every = self._sampler.every
        self._ids = itertools.count(1)
        self._lock = threading.Lock()
        self._ring: Deque[Trace] = deque()
        self.n_started = 0
        self.n_finished = 0
        self.n_dropped = 0

    # -- lifecycle ---------------------------------------------------------
    def should_sample(self) -> bool:
        """One deterministic sampling decision (call once per request)."""
        if not self.enabled:
            return False
        return self._sampler.should_sample()

    def start_trace(self, name: str, **attrs) -> Trace:
        with self._lock:
            self.n_started += 1
        return Trace(next(self._ids), name, attrs)

    def finish(self, trace: Trace) -> None:
        """Complete a trace into the ring (drop-oldest, lock-exact)."""
        if trace.t_end is None:
            trace.t_end = time.monotonic()
        with self._lock:
            if len(self._ring) >= self.capacity:
                self._ring.popleft()
                self.n_dropped += 1
            self._ring.append(trace)
            self.n_finished += 1

    # -- reading -----------------------------------------------------------
    def traces(self) -> List[Trace]:
        """Snapshot of completed traces, oldest first."""
        with self._lock:
            return list(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def find(self, trace_id: int) -> Optional[Trace]:
        with self._lock:
            for t in self._ring:
                if t.trace_id == trace_id:
                    return t
        return None

    # -- export ------------------------------------------------------------
    def export_chrome_trace(self) -> Dict[str, object]:
        """Chrome trace-event JSON (Perfetto / chrome://tracing).

        Complete events (``ph: "X"``) with microsecond timestamps on the
        shared monotonic clock; each event's ``args.trace_id`` names the
        owning request so one request filters cleanly across threads.
        """
        events: List[Dict[str, object]] = []
        for t in self.traces():
            end = t.t_end if t.t_end is not None else t.t_start
            events.append(dict(
                ph="X", cat="request", name=t.name, pid=1,
                tid=t.spans[0].thread_id if t.spans
                else threading.get_ident(),
                ts=t.t_start * 1e6, dur=max(end - t.t_start, 0.0) * 1e6,
                args=dict(trace_id=t.trace_id, **t.attrs)))
            for s in t.spans:
                args: Dict[str, object] = dict(trace_id=t.trace_id)
                if s.attrs:
                    args.update(s.attrs)
                events.append(dict(
                    ph="X", cat="span", name=s.name, pid=1,
                    tid=s.thread_id, ts=s.t_start * 1e6,
                    dur=max(s.duration_s, 0.0) * 1e6, args=args))
        return dict(traceEvents=events, displayTimeUnit="ms")

    def export_chrome_trace_json(self, path: Optional[str] = None) -> str:
        """Serialize; optionally write to ``path`` (Perfetto-loadable)."""
        text = json.dumps(self.export_chrome_trace())
        if path is not None:
            with open(path, "w") as f:
                f.write(text)
        return text
