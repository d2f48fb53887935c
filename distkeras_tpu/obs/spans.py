"""Nested timed scopes — the trace half of the telemetry layer.

A ``SpanTracer`` keeps a thread-local span stack and emits one record per
closed span into the SAME JSONL sink the metrics use (``MetricsLogger`` —
traces and metrics share one stream, so ``scripts/obsview.py`` reads both
from a single file).  Each record carries the span name, its full
``parent/child`` path, nesting depth and wall seconds::

    tracer = SpanTracer(metrics_logger)
    with tracer.span("train"):
        with tracer.span("jit_compile"):
            ...   # -> {"event": "span", "name": "jit_compile",
                  #     "path": "train/jit_compile", "depth": 1,
                  #     "seconds": 1.83}

Every span additionally carries identity (ISSUE 5): a thread-local
``trace_id`` (settable — async workers pin theirs to ``w<worker_id>`` so
one trace follows one worker) and a per-span ``span_id``; nested spans
record the enclosing span as ``parent_span``.  The ids are what lets a
span CROSS a process boundary: the PS client ships its open commit span's
``(trace_id, span_id)`` over the wire and the server's apply span adopts
them as its ``trace_id``/``parent_span`` — ``scripts/obsview.py`` then
links server applies back to the worker windows that caused them.

One clock with the device (ISSUE 26): for its life every span also holds
a ``jax.profiler.TraceAnnotation`` of its own name, so whenever a
profiler trace is running (``Trainer(profile=<dir>)``,
``obs.profile.device_trace``, ``benchmark/run.py --trace 1``) the
program's spans lie in the trace's host plane beside PjRt's own events,
on the profiler's clock — an idle gap on the device can then be named by
the span the host was in (``train.readback``, ``train.dispatch``, ...).
With no trace running the annotation is a flag check; a process that
never imported JAX (a PS shard server) is not made to.

Optionally a ``Registry`` accumulates per-name duration histograms
(``span.<name>.seconds``) so cumulative span time shows up in ``STATS``
snapshots too.  A process-wide default tracer (``obs.span``) serves ad-hoc
call sites; components that own a metrics sink build their own.
"""

from __future__ import annotations

import contextlib
import itertools
import sys
import threading
import time
import uuid
from typing import Optional, Tuple

from .logging import get_logger
from .registry import Registry, TIME_BUCKETS

_LOG = "obs.spans"

#: span ids are ``<trace_id>.<salt><seq>``: a process-wide monotone
#: counter plus a per-process random salt.  The salt is what keeps ids
#: unique when several PROCESSES (or sequential runs) append to one JSONL
#: sink under the same pinned trace tag (``w0`` restarts with the worker)
#: — without it, run 2's ``w0.5`` would collide with run 1's and obsview
#: would link spans across runs.
_SPAN_SEQ = itertools.count(1)
#: 8 hex chars = 32 bits: birthday collision across runs sharing a sink
#: stays negligible into the tens of thousands of appended runs (4 chars
#: would collide ~50% by ~256 runs, and colliding runs collide id-for-id
#: because the sequence restarts at 1)
_SPAN_SALT = uuid.uuid4().hex[:8]


def _annotate(name: str):
    """An entered ``jax.profiler.TraceAnnotation(name)``, or None: where
    JAX was never imported by this process (the tracer must not be what
    imports it), or where the profiler refuses — a span's record and
    timing never depend on the profiler."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        annotation = jax.profiler.TraceAnnotation(name)
        annotation.__enter__()
    except Exception as e:  # noqa: BLE001 — the profiler is best effort
        get_logger(_LOG).debug("span %s: no trace annotation: %r", name, e)
        return None
    return annotation


def _end_annotation(annotation) -> None:
    if annotation is None:
        return
    try:
        annotation.__exit__(None, None, None)
    except Exception as e:  # noqa: BLE001 — see _annotate
        get_logger(_LOG).debug("trace annotation did not close: %r", e)


class SpanTracer:
    """Thread-local nested span stack bound to an optional JSONL sink
    (anything with ``.log(event, **fields)``) and an optional registry."""

    def __init__(self, sink=None, registry: Optional[Registry] = None):
        self.sink = sink
        self.registry = registry
        self._local = threading.local()

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @property
    def depth(self) -> int:
        return len(self._stack())

    def current_path(self) -> str:
        return "/".join(name for name, _ in self._stack())

    # -- trace identity ------------------------------------------------------
    def set_trace_id(self, trace_id: str) -> None:
        """Pin THIS thread's trace id (e.g. ``w3`` for async worker 3) —
        every span the thread opens afterwards belongs to that trace."""
        self._local.trace_id = str(trace_id)

    def trace_id(self) -> str:
        """This thread's trace id (lazily minted when never pinned)."""
        tid = getattr(self._local, "trace_id", None)
        if tid is None:
            tid = self._local.trace_id = f"t{uuid.uuid4().hex[:8]}"
        return tid

    def current_span_id(self) -> Optional[str]:
        stack = self._stack()
        return stack[-1][1] if stack else None

    def context(self) -> Tuple[str, Optional[str]]:
        """``(trace_id, current_span_id)`` — the wire header the PS client
        attaches to commit/pull RPCs so remote spans can link back here."""
        return self.trace_id(), self.current_span_id()

    @contextlib.contextmanager
    def span(self, name: str, **fields):
        """Time a scope; emits on exit (exceptions included — a crashed
        span still records its duration, flagged ``error=True``).
        ``trace_id``/``parent_span`` keyword fields override the automatic
        thread-local ones — the server-side hook for adopting a REMOTE
        caller's trace context.  The ``with`` target is the record's
        own fields: what a scope learns only once it has run goes there
        (the trainers put a cold call's compile split on its
        ``jit_compile`` span); the structural keys stay authoritative
        (``_emit``)."""
        stack = self._stack()
        # a span adopting a REMOTE trace (explicit trace_id field — the
        # server-side hook) mints its id under THAT trace, so span-id
        # prefixes never name a trace absent from the stream
        tid = fields.get("trace_id") or self.trace_id()
        span_id = f"{tid}.{_SPAN_SALT}{next(_SPAN_SEQ)}"
        parent = stack[-1][1] if stack else None
        stack.append((name, span_id))
        path = "/".join(n for n, _ in stack)
        depth = len(stack) - 1
        annotation = _annotate(name)
        t0 = time.perf_counter()
        try:
            yield fields
        except BaseException:
            seconds = time.perf_counter() - t0
            _end_annotation(annotation)
            self._emit(name, path, depth, seconds, span_id, parent,
                       dict(fields, error=True))
            raise
        else:
            seconds = time.perf_counter() - t0
            _end_annotation(annotation)
            self._emit(name, path, depth, seconds, span_id, parent, fields)
        finally:
            stack.pop()

    def _emit(self, name: str, path: str, depth: int, seconds: float,
              span_id: str, parent: Optional[str], fields: dict) -> None:
        if self.sink is not None:
            rec = dict(fields)
            # only the trace-adoption keys are caller-overridable; the
            # structural keys below are authoritative (a field named
            # "seconds" must not silently replace the measured duration)
            rec.setdefault("trace_id", self.trace_id())
            if parent is not None:
                rec.setdefault("parent_span", parent)
            rec.update(name=name, path=path, depth=depth, seconds=seconds,
                       span_id=span_id)
            self.sink.log("span", **rec)
        if self.registry is not None:
            self.registry.histogram(f"span.{name}.seconds",
                                    TIME_BUCKETS).observe(seconds)


_DEFAULT = SpanTracer()


def default_tracer() -> SpanTracer:
    return _DEFAULT


def span(name: str, **fields):
    """Ad-hoc span on the process-wide tracer (silent until a sink is
    attached via ``set_default_sink``; nesting/paths always tracked)."""
    return _DEFAULT.span(name, **fields)


def set_default_sink(sink, registry: Optional[Registry] = None) -> None:
    """Point the process-wide tracer at a JSONL sink (and optionally a
    registry) — e.g. one line in a script turns on ad-hoc tracing."""
    _DEFAULT.sink = sink
    if registry is not None:
        _DEFAULT.registry = registry
