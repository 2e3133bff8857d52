"""Async micro-batching request router (the "heavy traffic" front door).

Production retrieval traffic is millions of small per-user requests, but
the TPU path wants large fixed-shape batches: one jitted serve call per
micro-batch, padded to a BUCKETED shape so XLA compiles once per bucket
instead of once per request size.  ``MicroBatcher`` multiplexes
concurrent producers into such calls:

  submit() -> request joins the queue, producer blocks on a future
  flush triggers:  (a) queued rows reach ``max_batch``  (size trigger)
                   (b) the oldest request ages past ``max_delay_s``
                       (deadline trigger -> bounded added latency)

A flush drains the oldest request's task group (requests for different
user-tower tasks never share a jit call — ``task`` is a static argument
of the serve function), concatenates the rows, pads them up to the next
bucket, runs ``serve_fn`` ONCE, and scatters row slices back to each
waiting future.  Queue-wait and flush latencies are recorded into the
shared ``ServeStats`` stage histograms, so the p99 seen by a *request*
(wait + serve) is observable, not just the p99 of the jit call.

The worker's phases run in host spans (``obs.trace.span``), one after
another on its thread: ``batcher.wait`` (waiting for a trigger, the
trigger scan included), ``batcher.take`` (popping the group, with
``queued``, the queue's length before it), ``batcher.assemble``
(queue-wait records, concatenation and padding), the serve function's
own spans, then
``batcher.scatter`` (traces, stats and futures).  Each carries
``flush``, the flush's sequence number; ``take``, ``assemble`` and
``scatter`` also ``task``, ``rows`` (real rows) and ``bucket`` (padded
rows), and ``wait`` the ``task`` its trigger chose.
"""
from __future__ import annotations

import inspect
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.obs import trace as trace_lib
from repro.serving.telemetry import ServeStats


def default_buckets(max_batch: int) -> Tuple[int, ...]:
    """Powers of two up to (and including) max_batch."""
    out = []
    b = 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return tuple(out)


class ServeFuture:
    """Single-assignment result slot a producer blocks on.

    Deliberately NOT concurrent.futures.Future: used as a bare promise
    (no executor), it raises the BUILTIN TimeoutError (the stdlib class
    is a distinct type before 3.11) and exposes no cancellation surface
    the batcher would then have to honor."""

    __slots__ = ("_event", "_value", "_error")

    def __init__(self):
        self._event = threading.Event()
        self._value = None
        self._error: Optional[BaseException] = None

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout):
            raise TimeoutError("serve request timed out")
        if self._error is not None:
            raise self._error
        return self._value

    def _set(self, value) -> None:
        self._value = value
        self._event.set()

    def _set_error(self, err: BaseException) -> None:
        self._error = err
        self._event.set()


class _Pending:
    __slots__ = ("batch", "rows", "task", "future", "t_enqueue", "trace")

    def __init__(self, batch: Dict[str, np.ndarray], rows: int, task: int,
                 future: ServeFuture,
                 trace: Optional[trace_lib.Trace] = None):
        self.batch = batch
        self.rows = rows
        self.task = task
        self.future = future
        self.t_enqueue = time.monotonic()
        self.trace = trace


class MicroBatcher:
    """Deadline/size-triggered micro-batching in front of a serve fn.

    ``serve_fn(batch: Dict[str, np.ndarray], task: int) -> Dict`` must
    return arrays with a leading batch axis (RetrievalService.serve_batch
    qualifies).  Close with ``close()`` (drains the queue first).
    """

    def __init__(self, serve_fn: Callable[[Dict[str, np.ndarray], int],
                                          Dict[str, np.ndarray]],
                 max_batch: int = 64, max_delay_s: float = 0.002,
                 buckets: Optional[Sequence[int]] = None,
                 stats: Optional[ServeStats] = None,
                 tracer: Optional[trace_lib.Tracer] = None):
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self._serve_fn = serve_fn
        # serve fns that accept ``n_valid`` get the REAL row count, so
        # their request counters exclude the bucket-padding rows; fns
        # that accept ``span_sink`` get per-flush stage spans back, which
        # are fanned out to every traced request in the flush group; fns
        # that accept ``flush`` get the flush's sequence number for
        # their own spans
        try:
            sig_params = inspect.signature(serve_fn).parameters
            self._pass_n_valid = "n_valid" in sig_params
            self._pass_span_sink = "span_sink" in sig_params
            self._pass_flush = "flush" in sig_params
        except (TypeError, ValueError):            # pragma: no cover
            self._pass_n_valid = False
            self._pass_span_sink = False
            self._pass_flush = False
        self.tracer = tracer
        self.max_batch = max_batch
        self.max_delay_s = max_delay_s
        self.buckets = tuple(sorted(set(buckets or
                                        default_buckets(max_batch))))
        if self.buckets[-1] < max_batch:
            raise ValueError("largest bucket must cover max_batch")
        self.stats = stats if stats is not None else ServeStats()
        # exact flush accounting (mutated only by the worker thread)
        self.n_flushes = 0
        self.n_size_flushes = 0
        self.n_deadline_flushes = 0
        self.padded_rows = 0
        self.served_rows = 0
        self.shapes_seen: set = set()
        self._next_flush = 0        # sequence number of the next flush

        self._pending: List[_Pending] = []
        self._cond = threading.Condition()
        self._closed = False
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name="micro-batcher")
        self._worker.start()

    # -- producer side -----------------------------------------------------
    def submit(self, batch: Dict[str, np.ndarray],
               task: int = 0) -> ServeFuture:
        """Enqueue a small request; returns a future for its row slice."""
        batch = {k: np.asarray(v) for k, v in batch.items()}
        rows = len(batch["user_id"])
        if rows == 0 or rows > self.max_batch:
            raise ValueError(f"request rows must be in [1, {self.max_batch}]"
                             f", got {rows}")
        fut = ServeFuture()
        # the sampling decision happens at SUBMIT, so a trace's clock
        # starts before the queue and queue_wait is part of the trace
        trace = None
        if self.tracer is not None and self.tracer.should_sample():
            trace = self.tracer.start_trace("request", rows=rows,
                                            task=task)
        with self._cond:
            if self._closed:
                raise RuntimeError("batcher is closed")
            self._pending.append(_Pending(batch, rows, task, fut, trace))
            self._cond.notify()
        return fut

    def close(self) -> None:
        """Drain remaining requests, then stop the worker."""
        with self._cond:
            self._closed = True
            self._cond.notify()
        self._worker.join()

    # -- worker side -------------------------------------------------------
    def _run(self) -> None:
        while True:
            seq = self._next_flush
            # the flush's host spans, kept for the traced requests in it
            sink = [] if self.tracer is not None else None
            with self._cond:
                with trace_lib.span("batcher.wait", sink, flush=seq) as wait:
                    flush_task, deadline_flush = self._wait_trigger()
                    if flush_task is None:
                        return
                    wait["task"] = flush_task
                with trace_lib.span("batcher.take", sink, flush=seq,
                                    task=flush_task,
                                    queued=len(self._pending)) as take:
                    group = self._take_group(flush_task)
                    rows = sum(p.rows for p in group)
                    take.update(rows=rows, bucket=next(
                        b for b in self.buckets if b >= rows))
            self._next_flush += 1
            self._flush(group, flush_task, deadline_flush, sink,
                        dict(flush=seq, task=flush_task, rows=rows,
                             bucket=take["bucket"]))

    def _wait_trigger(self) -> Tuple[Optional[int], bool]:
        """Wait (cond held) until a flush is due -> (task, deadline
        flush), or (None, False) once closed with nothing queued."""
        while True:
            if self._pending:
                oldest = self._pending[0]
                # the size trigger scans EVERY task group (a full group
                # must not be head-of-line blocked behind another task's
                # lone aging request); one O(P) pass, the queue can be
                # long
                rows_by_task: Dict[int, int] = {}
                size_task = None
                for p in self._pending:
                    r = rows_by_task.get(p.task, 0) + p.rows
                    rows_by_task[p.task] = r
                    if r >= self.max_batch:
                        size_task = p.task
                        break
                if size_task is not None:
                    return size_task, False
                wait_left = (oldest.t_enqueue + self.max_delay_s
                             - time.monotonic())
                if wait_left <= 0 or self._closed:
                    return oldest.task, True
                self._cond.wait(timeout=wait_left)
            elif self._closed:
                return None, False
            else:
                self._cond.wait()

    def _take_group(self, task: int) -> List[_Pending]:
        """Pop FIFO requests of ``task`` until max_batch rows (cond held)."""
        group, rows, rest = [], 0, []
        for p in self._pending:
            if p.task == task and rows + p.rows <= self.max_batch:
                group.append(p)
                rows += p.rows
            else:
                rest.append(p)
        self._pending = rest
        return group

    def _flush(self, group: List[_Pending], task: int,
               deadline_flush: bool, sink: Optional[List[trace_lib.Span]],
               args: Dict[str, int]) -> None:
        t_flush = time.monotonic()
        rows, bucket = args["rows"], args["bucket"]
        # the flush's spans are shared verbatim by every traced request
        # in the group (each trace re-stamps them with its own trace ID
        # at export time); the serve fn adds its own only when one is
        # traced
        traced = [p for p in group if p.trace is not None]
        try:
            # batch assembly stays inside the error path: a malformed
            # request (mismatched keys/shapes across the group) must
            # fail ITS futures, not kill the worker thread
            with trace_lib.span("batcher.assemble", sink, **args):
                for p in group:
                    self.stats.stage("queue_wait").record(
                        t_flush - p.t_enqueue)
                    if p.trace is not None:
                        p.trace.add_span(trace_lib.make_span(
                            "queue_wait", p.t_enqueue, t_flush, **args))
                batch = {}
                for k in group[0].batch.keys():
                    cat = np.concatenate([p.batch[k] for p in group],
                                         axis=0)
                    if bucket > rows:
                        # pad by repeating row 0: valid ids, constant
                        # shape
                        pad = np.repeat(cat[:1], bucket - rows, axis=0)
                        cat = np.concatenate([cat, pad], axis=0)
                    batch[k] = cat
            kwargs = {}
            if self._pass_n_valid:
                kwargs["n_valid"] = rows
            if self._pass_flush:
                kwargs["flush"] = args["flush"]
            if traced and self._pass_span_sink:
                kwargs["span_sink"] = sink
            out = self._serve_fn(batch, task, **kwargs)
        except BaseException as e:
            for p in group:
                p.future._set_error(e)
                if p.trace is not None:
                    p.trace.attrs["error"] = repr(e)
                    self.tracer.finish(p.trace)
            return
        with trace_lib.span("batcher.scatter", **args):
            for p in traced:
                p.trace.spans.extend(sink)
                p.trace.attrs["flush"] = args["flush"]
                p.trace.attrs["flush_rows"] = rows
                self.tracer.finish(p.trace)
            self.stats.stage("batcher_flush").record(
                time.monotonic() - t_flush)
            self.n_flushes += 1
            if deadline_flush:
                self.n_deadline_flushes += 1
            else:
                self.n_size_flushes += 1
            self.padded_rows += bucket - rows
            self.served_rows += rows
            self.shapes_seen.add(bucket)
            lo = 0
            for p in group:
                sl = {k: v[lo:lo + p.rows] for k, v in out.items()}
                lo += p.rows
                p.future._set(sl)
