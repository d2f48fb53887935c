"""Profiling layer over the registries/spans (ISSUE 6 tentpole).

PR 2 gave the stack metrics and spans, PR 5 a drift gate — but none of it
answers the three questions a perf regression actually raises: *did
something recompile*, *where did the HBM go*, and *is the step host-bound
or device-bound*.  Five instruments, feeding the existing registries (so
``obs.drift`` gates them like any other metric) and the spans' records:

* **Recompilation sentinel** (``RetraceSentinel``) — tracks the arg
  signature (pytree structure + per-leaf shape/dtype) of every jit entry
  point.  The first signature is the cold compile (``jit.compiles``);
  any NEW signature later is a retrace (``jit.retraces``), the silent
  throughput killer SURVEY.md §7 names — logged once per signature with
  the offending shape/dtype hash, and drift-gated by the committed
  ``OBS_BASELINE.json`` (any increase fails ``obsview --diff``).
* **Compile ledger** (``compile_totals`` / ``compile_spent``, ISSUE 26)
  — what a compile was spent on, from inside JAX: ``jax.monitoring``
  listeners, registered once per process, add up per thread the seconds
  of Python tracing (``trace_s``), of lowering to MLIR (``lower_s``) and
  of the backend (``backend_s``: XLA's compile, or at a persistent-cache
  hit the entry's read and the executable's load), and count the cache's
  hits and misses.  The trainers take the difference around a cold call
  and put it on the ``jit_compile`` span's record, so a slow set-up says
  which of the three it was and what the cache did: ``cache_misses`` 1
  is a compile that was written to the cache, ``cache_hits`` 1 a load
  from it, both 0 a compile the cache never saw (no cache directory, or
  a program under ``jax_persistent_cache_min_compile_time_secs``).
* **Program memory** (``program_memory``, ISSUE 38) — the memory account
  of a compiled program, taken where the executable is born: the
  compiler's own bytes of arguments, outputs, aliased (donated) outputs,
  temporaries and code, their sum ``program_bytes``, and beside them the
  device's limit and the fullest device's ``bytes_in_use``.  The
  trainers compile a cold call's program first and put the account on
  its ``jit_compile`` (``SpmdTrainer``: ``aot_compile``) record; nothing
  runs on a warm call.
* **Memory watermarks** (``memory_snapshot`` / ``observe_memory``) —
  live device-array bytes (``jax.live_arrays()``), array count, a
  max-tracked ``mem.peak_live_bytes`` gauge, and the backend allocator's
  ``peak_bytes_in_use`` where the platform reports it (TPU/GPU; CPU
  returns none).  Sampled at the existing heartbeat points: trainer
  epoch records and async-worker window heartbeats.
* **Device trace seam** (``device_trace``) — the one sanctioned
  ``jax.profiler`` start/stop wrapper: announces the output dir once via
  ``obs.logging``, and never leaks an open trace session on exception
  paths (a failing ``stop_trace`` is logged, not allowed to mask the
  body's error).  ``utils.metrics.profile_trace`` delegates here, and
  ``ProfileConfig.trace_dir`` requests per-epoch captures from trainer
  config.  Every ``obs.spans`` span holds a ``TraceAnnotation``, so the
  capture's host plane carries the program's own spans (``train``,
  ``train.stage``, ``train.init``, ``train.dispatch``, ``jit_compile``,
  ``train.readback``, ``train.to_host``) beside PjRt's events and on the
  clock of the device's planes: the host/device split of a step is read
  off the trace, with no fence added to the run.

``ProfileConfig`` is the trainer-facing knob bundle
(``Trainer(..., profile=...)`` accepts a ``ProfileConfig``, a dict of
its fields, or a bare path string meaning ``trace_dir``: the operator's
way to such a trace, ``Trainer(profile="<dir>")``, then TensorBoard or
ui.perfetto.dev on ``<dir>/epoch0``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import threading
from typing import Any, Callable, Optional, Sequence, Tuple, Union

from .logging import get_logger
from .registry import Registry, default_registry

#: live-byte buckets for the optional watermark histogramming — gauges are
#: the primary surface (levels), these exist for callers that want a
#: distribution over a long run
_LOG = "obs.profile"


# ---------------------------------------------------------------------------
# recompilation sentinel
# ---------------------------------------------------------------------------

def tree_signature(args: Any) -> Tuple:
    """Hashable retrace signature of a call's arguments: the pytree
    structure plus each array leaf's ``(shape, dtype)``.  Non-array leaves
    contribute their type only (jit specializes on structure and
    shape/dtype, not on array values; hashing Python scalar VALUES would
    report a retrace for every new step count).  Matches what actually
    triggers an XLA re-trace for the static-shape programs this repo
    compiles."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            sig.append((tuple(shape), str(dtype)))
        else:
            sig.append(type(leaf).__name__)
    return treedef, tuple(sig)


def signature_digest(sig: Tuple) -> str:
    """Short stable hash of a ``tree_signature`` — what the one-time
    retrace log (and the JSONL ``retrace`` record) names, so two runs can
    be compared by signature without dumping whole shape trees."""
    return hashlib.sha1(repr(sig).encode()).hexdigest()[:12]


class RetraceSentinel:
    """Counts cold compiles and retraces of ONE jit entry point.

    ``observe(args)`` returns ``"cold"`` (first signature ever),
    ``"warm"`` (seen before — the steady state) or ``"retrace"`` (a NEW
    signature after the first: XLA recompiles synchronously inside this
    call).  Counters land in ``registry`` — an ``obs.Registry``, a
    zero-arg callable returning one (resolved per event, so a registry
    attached after construction still receives the counts), or None for
    the process-wide default.  Retraces log once per signature (warning —
    they are the regression this sentinel exists to catch) and, with a
    ``sink``, emit a ``retrace`` record into the JSONL stream.

    ``observe_key`` (ISSUE 7) is the variant for entry points that manage
    their own compiled-program cache keyed by MORE than arg shapes (the
    decode runners in ``models.generation``: temperature, top-k, beam
    width ... all bake into the program): the caller's hashable cache key
    IS the signature, so value-level program changes the shape signature
    cannot see still count.  ``warn=False`` keeps the counters but
    silences the once-per-signature log — for entry points where many
    signatures are a legitimate workload (offline eval/bench sweeps),
    not a regression."""

    def __init__(self, name: str, registry=None, sink=None,
                 warn: bool = True):
        self.name = name
        self._registry = registry
        self.sink = sink
        self.warn = bool(warn)
        self._sigs: dict = {}   # signature -> digest
        self._lock = threading.Lock()

    def _reg(self) -> Registry:
        reg = self._registry() if callable(self._registry) else self._registry
        return reg if reg is not None else default_registry()

    @property
    def compiles(self) -> int:
        return len(self._sigs)

    def observe(self, args: Any) -> str:
        return self._observe_sig(tree_signature(args))

    def observe_key(self, key: Any) -> str:
        """Count a call by the caller's own hashable program-cache key
        (same cold/warm/retrace semantics as ``observe``) — for entry
        points whose compiled program depends on more than arg shapes."""
        return self._observe_sig(("key", key))

    def _observe_sig(self, sig: Any) -> str:
        with self._lock:
            if sig in self._sigs:
                return "warm"
            first = not self._sigs
            digest = signature_digest(sig)
            self._sigs[sig] = digest
            n_retrace = len(self._sigs) - 1
        reg = self._reg()
        reg.counter("jit.compiles").inc()
        if first:
            return "cold"
        reg.counter("jit.retraces").inc()
        # once per signature by construction: a signature enters _sigs
        # exactly once, and only that insertion reaches this path
        if self.warn:
            get_logger(_LOG).warning(
                "%s: retrace #%d — new arg signature %s (shapes/dtypes "
                "changed since the cold compile; steady-state steps should "
                "never re-trace)", self.name, n_retrace, digest)
        if self.sink is not None:
            self.sink.log("retrace", entry=self.name, signature=digest,
                          retraces=n_retrace)
        return "retrace"

    def wrap(self, fn: Callable) -> Callable:
        """``fn`` with every call observed (counting only — the cold/warm
        split callers like the trainers' ``jit_compile`` span need is
        theirs to build from ``observe``)."""
        def wrapped(*args):
            self.observe(args)
            return fn(*args)
        return wrapped


# ---------------------------------------------------------------------------
# compile ledger (jax.monitoring)
# ---------------------------------------------------------------------------

#: JAX's own timers around the three stages of a jit cold call -> the
#: field each feeds.  ``backend_s`` spans ``compile_or_get_cached``: XLA's
#: compile at a miss, the cache entry's read and the executable's load at
#: a hit.
_STAGE_FIELD = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
#: persistent-cache events -> counts (a miss is recorded where the fresh
#: executable is written to the cache)
_CACHE_FIELD = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


class _CompileLedger(threading.local):
    """One thread's running totals (a jit cold call traces, lowers and
    compiles on the thread that made it)."""

    def __init__(self):
        self.totals = dict.fromkeys(_STAGE_FIELD.values(), 0.0)
        self.totals.update(dict.fromkeys(_CACHE_FIELD.values(), 0))
        #: the stage timers counted since the last reading, as
        #: ``(start, field, seconds)`` in the order they closed.  JAX's
        #: timers nest (tracing a program traces the jitted functions it
        #: calls, each under a timer of its own) and a timer reports as
        #: it closes, inner before outer: one that closes later and
        #: started no later holds those counted before it, which are
        #: taken out again, so the three fields never count a second
        #: twice
        self.counted = []


_LEDGER = _CompileLedger()
_LISTENING = False
_LISTEN_LOCK = threading.Lock()


def _stage_closed(event: str, start: float, end: float, **kw) -> None:
    field = _STAGE_FIELD.get(event)
    if field is None:
        return
    totals, counted = _LEDGER.totals, _LEDGER.counted
    while counted and counted[-1][0] >= start:
        _, inner, seconds = counted.pop()
        totals[inner] -= seconds
    counted.append((start, field, end - start))
    totals[field] += end - start


def _cache_event(event: str, **kw) -> None:
    field = _CACHE_FIELD.get(event)
    if field is not None:
        _LEDGER.totals[field] += 1


def compile_totals() -> dict:
    """The calling thread's running totals since the listeners were
    registered (they are, once per process, by the first call here):
    ``trace_s`` / ``lower_s`` / ``backend_s`` seconds and ``cache_hits``
    / ``cache_misses`` counts.  Take one before a cold call and hand it
    to :func:`compile_spent` after: a reading is taken between compiles,
    never inside one."""
    global _LISTENING
    with _LISTEN_LOCK:  # once per cold call: no need to dodge the lock
        if not _LISTENING:
            import jax.monitoring as monitoring
            monitoring.register_event_time_span_listener(_stage_closed)
            monitoring.register_event_listener(_cache_event)
            _LISTENING = True
    _LEDGER.counted.clear()  # no timer is open: nothing can hold these
    return dict(_LEDGER.totals)


def compile_spent(before: dict) -> dict:
    """What this thread spent compiling since ``before``
    (a :func:`compile_totals` reading): the five fields of a
    ``jit_compile`` record."""
    return {k: v - before[k] for k, v in compile_totals().items()}


# ---------------------------------------------------------------------------
# the memory account of a compiled program
# ---------------------------------------------------------------------------

#: ``compiled.memory_analysis()``'s counts -> the field each becomes
_PROGRAM_FIELD = {
    "argument_size_in_bytes": "program_argument_bytes",
    "output_size_in_bytes": "program_output_bytes",
    "alias_size_in_bytes": "program_alias_bytes",
    "temp_size_in_bytes": "program_temp_bytes",
    "generated_code_size_in_bytes": "program_code_bytes",
}


def program_memory(compiled) -> dict:
    """The memory account of one executable (``jit(f).lower(...)
    .compile()``), as a cold call's ``jit_compile`` / ``aot_compile``
    record carries it.  From the compiler's own ``memory_analysis()``:
    ``program_argument_bytes``, ``program_output_bytes``,
    ``program_alias_bytes`` (outputs written over donated arguments),
    ``program_temp_bytes`` (what the program takes while it runs, beside
    its arguments and outputs), ``program_code_bytes``, and the one sum
    that says whether it fits: ``program_bytes`` = arguments + outputs −
    aliased + temporaries.  Of a program over a mesh the analysis is ONE
    device's share (a sharded argument counts at its shard's size), which
    is what a device's limit is held against.  Beside them
    ``device_bytes_limit`` (``models.remat.device_limit``) and
    ``device_bytes_in_use``, the allocator's ``bytes_in_use`` on the
    fullest local device (a max over devices, never a sum) at the moment
    of this call: both None where the backend reports none (the CPU).  An
    executable that reports no analysis gives an empty dict."""
    import jax
    from ..models.remat import device_limit
    try:
        analysis = compiled.memory_analysis()
    except (RuntimeError, NotImplementedError, AttributeError):
        analysis = None
    if analysis is None:
        return {}
    account = {field: int(getattr(analysis, name))
               for name, field in _PROGRAM_FIELD.items()}
    account["program_bytes"] = (
        account["program_argument_bytes"] + account["program_output_bytes"]
        - account["program_alias_bytes"] + account["program_temp_bytes"])
    in_use = [stats["bytes_in_use"] for stats in
              (d.memory_stats() for d in jax.local_devices())
              if stats and stats.get("bytes_in_use") is not None]
    account["device_bytes_limit"] = device_limit()
    account["device_bytes_in_use"] = max(map(int, in_use), default=None)
    return account


# ---------------------------------------------------------------------------
# memory watermarks
# ---------------------------------------------------------------------------

#: guards the read-modify-write on the max-tracked peak gauges (Gauge ops
#: are individually locked, but max() needs the pair to be atomic across
#: concurrently-heartbeating workers)
_PEAK_LOCK = threading.Lock()


def memory_snapshot() -> dict:
    """Point-in-time device-memory accounting: ``live_bytes`` /
    ``live_arrays`` from ``jax.live_arrays()`` (every live ``jax.Array``
    this process holds), plus ``device_peak_bytes`` — the backend
    allocator's ``peak_bytes_in_use`` summed over devices — where the
    platform reports it (TPU/GPU; CPU's ``memory_stats()`` is None)."""
    import jax
    live_bytes = 0
    count = 0
    for a in jax.live_arrays():
        try:
            live_bytes += int(a.nbytes)
            count += 1
        except RuntimeError:
            continue  # deleted/donated between enumeration and read
    snap = {"live_bytes": live_bytes, "live_arrays": count,
            "device_peak_bytes": None}
    peak = 0
    seen = False
    for d in jax.devices():
        try:
            stats = d.memory_stats()
        except (RuntimeError, NotImplementedError, AttributeError):
            stats = None
        if stats and stats.get("peak_bytes_in_use") is not None:
            peak += int(stats["peak_bytes_in_use"])
            seen = True
    if seen:
        snap["device_peak_bytes"] = peak
    return snap


def observe_memory(registry: Optional[Registry] = None) -> dict:
    """Sample ``memory_snapshot`` into watermark gauges:
    ``mem.live_bytes`` / ``mem.live_arrays`` (levels),
    ``mem.peak_live_bytes`` (max over every sample this registry saw —
    the HBM high-water mark the OOM postmortem wants), and
    ``mem.device_peak_bytes`` when the backend reports it.  Returns the
    snapshot so call sites (epoch records, worker heartbeats) can stamp
    the bytes into their JSONL record too."""
    snap = memory_snapshot()
    reg = registry if registry is not None else default_registry()
    reg.gauge("mem.live_bytes").set(snap["live_bytes"])
    reg.gauge("mem.live_arrays").set(snap["live_arrays"])
    with _PEAK_LOCK:
        peak = reg.gauge("mem.peak_live_bytes")
        if snap["live_bytes"] > peak.value:
            peak.set(snap["live_bytes"])
    if snap["device_peak_bytes"] is not None:
        reg.gauge("mem.device_peak_bytes").set(snap["device_peak_bytes"])
    return snap


# ---------------------------------------------------------------------------
# the timing fence
# ---------------------------------------------------------------------------

def fence(tree):
    """THE timing fence: wait until every array in ``tree`` has been
    computed, and return it.  ``jax.block_until_ready`` is honest on the
    machine this repo runs on — ``chip_smoke.py``'s fence phase times one
    matmul chain three ways, and on the v5e (JAX 0.9.0, PR 21) it waited
    189.5 ms against 189.8 ms for a device->host readback of the result,
    with 0.3 ms to enqueue — so a timed region that ends here spans the
    device work, and no readback is needed just to stop a clock."""
    import jax
    return jax.block_until_ready(tree)


# ---------------------------------------------------------------------------
# device trace seam (jax.profiler)
# ---------------------------------------------------------------------------

#: dirs already announced — the capture log is once per destination, not
#: once per epoch
_ANNOUNCED: set = set()
_ANNOUNCE_LOCK = threading.Lock()


@contextlib.contextmanager
def device_trace(log_dir: str):
    """Capture a ``jax.profiler`` trace of the wrapped region (open the
    result in TensorBoard or Perfetto).  The one sanctioned start/stop
    pair: announces the output dir once via ``obs.logging``, and on an
    exception inside the region the trace session is still closed — a
    ``stop_trace`` failure there is logged instead of masking the body's
    error (the old ``utils.metrics.profile_trace`` leaked the open
    session exactly that way)."""
    import jax
    log = get_logger(_LOG)
    with _ANNOUNCE_LOCK:
        if log_dir not in _ANNOUNCED:
            _ANNOUNCED.add(log_dir)
            log.info("device trace capture -> %s (open with TensorBoard or "
                     "ui.perfetto.dev)", log_dir)
    jax.profiler.start_trace(log_dir)
    try:
        yield log_dir
    except BaseException:
        try:
            jax.profiler.stop_trace()
        except RuntimeError as e:
            # the body's exception is the story; a stop failure on the
            # unwind path must not replace it (but must not hide either)
            log.warning("device trace %s: stop_trace failed during "
                        "exception unwind: %s", log_dir, e)
        raise
    else:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# trainer-facing config
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ProfileConfig:
    """Profiling knobs a trainer accepts as ``profile=``.

    * ``trace_dir`` — request per-epoch ``jax.profiler`` captures into
      ``<trace_dir>/epoch<k>`` for every epoch in ``trace_epochs``
      (None = no device capture).
    * ``trace_epochs`` — which epochs to capture (default: epoch 0, the
      compile-heavy one); None means every epoch.
    * ``memory`` — sample memory watermarks at the existing heartbeat
      points (per-epoch records, per-window worker heartbeats)."""

    trace_dir: Optional[str] = None
    trace_epochs: Optional[Sequence[int]] = (0,)
    memory: bool = True

    def trace_epoch(self, epoch: int) -> bool:
        """Should ``epoch`` run under a device capture?"""
        if not self.trace_dir:
            return False
        return self.trace_epochs is None or epoch in tuple(self.trace_epochs)

    @staticmethod
    def resolve(spec: Union[None, str, dict, "ProfileConfig"]
                ) -> "ProfileConfig":
        """``None`` (defaults) | a path string (= ``trace_dir``) | a dict
        of fields | a ready ProfileConfig."""
        if spec is None:
            return ProfileConfig()
        if isinstance(spec, ProfileConfig):
            return spec
        if isinstance(spec, str):
            return ProfileConfig(trace_dir=spec)
        if isinstance(spec, dict):
            return ProfileConfig(**spec)
        raise TypeError(f"profile= expects None, a trace dir path, a dict "
                        f"of ProfileConfig fields, or a ProfileConfig "
                        f"(got {type(spec).__name__})")
