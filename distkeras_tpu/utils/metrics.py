"""Metrics / logging / profiling — SURVEY.md §5.1 + §5.5.

The reference's observability is wall-clock + per-worker loss history plus
Spark's web UI.  Ours: a structured JSONL metrics sink (stdout or file),
trainer-emitted per-epoch records (loss, samples/sec, epoch seconds), and
a ``jax.profiler`` trace context for TensorBoard/Perfetto captures.
"""

from __future__ import annotations

import collections
import contextlib
import json
import math
import sys
import threading
import time
from typing import IO, Optional, Union

import numpy as np

from ..obs.logging import get_logger

#: arrays at or below this many elements serialize as nested lists; larger
#: ones as a shape/dtype/stats summary (a logged metric should never drag
#: megabytes of weights into the JSONL stream)
_ARRAY_INLINE_MAX = 64


def json_safe(x):
    """Coerce a logged value into strictly-valid JSON data.

    ``json.dumps(default=float)`` raised on ``np.ndarray`` and emitted bare
    ``NaN``/``Infinity`` tokens (invalid JSON — downstream parsers choke).
    Rules: ndarrays become nested lists (small) or a summary dict (large);
    numpy scalars become Python scalars; non-finite floats become the
    strings ``"NaN"`` / ``"Infinity"`` / ``"-Infinity"``; anything exotic
    falls back through ``np.asarray`` and finally ``str``.
    """
    if x is None or isinstance(x, (bool, int, str)):
        return x
    if isinstance(x, float):
        if math.isfinite(x):
            return x
        if math.isnan(x):
            return "NaN"
        return "Infinity" if x > 0 else "-Infinity"
    if isinstance(x, dict):
        return {str(k): json_safe(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [json_safe(v) for v in x]
    if isinstance(x, np.integer):
        return int(x)
    if isinstance(x, np.floating):
        return json_safe(float(x))
    if isinstance(x, np.bool_):
        return bool(x)
    if isinstance(x, np.ndarray):
        if x.dtype == object:
            return str(x)
        if x.size <= _ARRAY_INLINE_MAX:
            return json_safe(x.tolist())
        out = {"shape": list(x.shape), "dtype": str(x.dtype)}
        if x.size and np.issubdtype(x.dtype, np.number):
            xf = np.asarray(x, dtype=np.float64)
            out.update(mean=json_safe(float(xf.mean())),
                       min=json_safe(float(xf.min())),
                       max=json_safe(float(xf.max())))
        return out
    try:  # jax.Array and friends expose __array__
        return json_safe(np.asarray(x))
    except (TypeError, ValueError, RuntimeError) as e:
        # the swallowed catch-all here turned serialization bugs into
        # silent "<object repr>" strings in the metrics stream (dklint
        # swallow-guard); narrow types + a warning keep the fallback
        # without hiding the cause
        get_logger("utils.metrics").warning(
            "json_safe: %s is not array-coercible (%s); logging str()",
            type(x).__name__, e)
        return str(x)


class MetricsLogger:
    """Append-only JSONL metrics sink.

    ``MetricsLogger("train.jsonl")`` or ``MetricsLogger(sys.stdout)``;
    ``log(event, **fields)`` writes one line with a wall-clock timestamp.
    The most recent ``keep_records`` records are also kept in ``.records``
    so callers (benchmarks, notebooks) can read trainer-emitted metrics
    back without parsing the sink; the cap keeps memory bounded even if a
    long-lived service logs per-step events (the sink, if any, still gets
    every record).
    """

    def __init__(self, sink: Union[str, IO, None] = None,
                 keep_records: int = 100_000):
        self._own = False
        self.records: collections.deque = collections.deque(
            maxlen=keep_records)
        #: async workers heartbeat from their own threads; one lock keeps
        #: JSONL lines whole (interleaved writes would corrupt the stream)
        self._lock = threading.Lock()
        if sink is None:
            self._fh = None
        elif isinstance(sink, str):
            self._fh = open(sink, "a", buffering=1)
            self._own = True
        else:
            self._fh = sink

    def log(self, event: str, **fields) -> dict:
        rec = {"ts": time.time(), "event": event, **fields}
        # raw values stay in .records (benchmarks read them back without a
        # parse round-trip); only the serialized line is coerced
        line = None
        if self._fh is not None:
            line = json.dumps(json_safe(rec), allow_nan=False) + "\n"
        with self._lock:
            self.records.append(rec)
            # re-check under the lock: a concurrent close() may have
            # retired the sink after the serialization check above
            if line is not None and self._fh is not None:
                self._fh.write(line)
        return rec

    def close(self) -> None:
        # under the write lock: a concurrent log() must never observe a
        # half-closed sink (close raced unsynchronized before — dklint)
        with self._lock:
            if self._own and self._fh is not None:
                self._fh.close()
                self._fh = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


@contextlib.contextmanager
def profile_trace(log_dir: str):
    """Capture a ``jax.profiler`` trace (open with TensorBoard/Perfetto).

    TPU equivalent of the reference leaning on the Spark UI for task
    timing: wrap any training region::

        with profile_trace("/tmp/trace"):
            trainer.train(ds)

    Thin alias for ``obs.profile.device_trace`` (ISSUE 6) — the one
    sanctioned start/stop seam: the output dir is announced once via
    ``obs.logging`` and the trace session can no longer leak open on
    exception paths (this helper used to own a bare start/stop pair that
    did exactly that when ``stop_trace`` failed during unwind)."""
    from ..obs.profile import device_trace
    with device_trace(log_dir):
        yield


class StepTimer:
    """Honest step timing: ``mark()`` between steps; ``rate(samples)``
    reports samples/sec.  Callers are responsible for a hard sync (e.g. a
    scalar readback) before ``mark``: JAX returns before the device is
    done, so a lap without one times the enqueue."""

    def __init__(self):
        self.t0 = time.perf_counter()
        self.laps: list = []

    def mark(self) -> float:
        t = time.perf_counter()
        lap = t - self.t0
        self.t0 = t
        self.laps.append(lap)
        return lap

    def rate(self, samples_per_lap: int) -> float:
        if not self.laps:
            return 0.0
        return samples_per_lap * len(self.laps) / sum(self.laps)
