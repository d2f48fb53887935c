"""Telemetry subsystem — counters/gauges/histograms, spans, exposition.

The observability layer the reference out-sourced to Spark's web UI
(SURVEY/PAPER §5) and this reproduction lacked entirely: process-local
instruments with mergeable plain-data snapshots (``registry``), nested
timed scopes sharing the JSONL metrics stream (``spans``), Prometheus text
rendering (``exposition``) and the library logging/console seam
(``logging``).  Threaded through the hot layers: the parameter-server
stack exposes a live ``STATS`` RPC returning a registry snapshot, the
networking layer counts bytes/round-trips, streaming counts
batches/stalls, trainers split compile time from steady-state and async
workers heartbeat — all readable by ``scripts/obsview.py``.

On top of the raw telemetry sits the regression-tracking layer (ISSUE 5):
``drift`` diffs persisted registry snapshots across runs (counter ratio
deltas, bucket-wise PSI + quantile shift, thresholds from the committed
``OBS_BASELINE.json``) and ``stragglers`` turns per-window worker
heartbeat gaps into a live ``ps.stragglers`` gauge.

The telemetry plane (ISSUE 20): instruments take an optional
``labels={...}`` dimension that flattens into the legacy dotted names
(``registry.flat_name``), ``timeseries`` aggregates push-shipped
``snapshot_delta`` increments into one bounded live fleet series, and
``alerts`` evaluates threshold + SLO burn-rate rules over it with
hysteresis — the live half of the drift gate's contract.

The profiling layer (ISSUE 6): ``profile`` adds the recompilation
sentinel (``jit.compiles``/``jit.retraces``, drift-gated), memory
watermarks (``mem.*`` gauges sampled at the heartbeat points), the
compile ledger (what a ``jit_compile`` span spent on tracing, lowering
and the backend, and whether the persistent cache was hit), the memory
account of the program a cold call compiled (``program_*`` bytes on the
same record, as the compiler counts them), and the one
sanctioned ``jax.profiler`` capture seam, in whose host plane every span
appears (``spans`` holds a ``TraceAnnotation``); ``export`` renders the
span/heartbeat JSONL as a Chrome/Perfetto trace
(``obsview --export-trace``) with the PR 5 cross-process links drawn as
flow arrows.
"""

from .registry import (  # noqa: F401
    COUNT_BUCKETS,
    TIME_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    Registry,
    default_registry,
    flat_name,
    flatten_snapshot,
    snapshot_quantile,
)
from .spans import SpanTracer, default_tracer, set_default_sink, span  # noqa: F401
from .exposition import to_prometheus_text  # noqa: F401
from .logging import emit, enable_stderr_logging, get_logger  # noqa: F401
from .stragglers import (  # noqa: F401
    LinkQuality,
    StragglerDetector,
    detect_from_heartbeats,
)
from .profile import (  # noqa: F401
    ProfileConfig,
    RetraceSentinel,
    device_trace,
    memory_snapshot,
    observe_memory,
    tree_signature,
)
from .export import records_to_chrome_trace, write_chrome_trace  # noqa: F401
from .drift import (  # noqa: F401
    BASELINE_SCHEMA,
    DEFAULT_THRESHOLDS,
    WINDOW_KINDS,
    DriftReport,
    WindowVerdict,
    classify_window,
    diff_docs,
    diff_files,
    find_baseline,
    load_baseline,
    snapshot_delta,
)
from .timeseries import TelemetryShipper, TimeSeriesStore  # noqa: F401
from .alerts import (  # noqa: F401
    KNOWN_LABEL_KEYS,
    AlertEngine,
    AlertRule,
    parse_rules,
)
