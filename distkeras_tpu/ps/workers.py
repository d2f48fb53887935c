"""Async worker loops — parity with reference ``distkeras/workers.py``.

Each worker owns a device, runs the jit-compiled window scan
(``parallel.sync.make_window_fn``) on its partition, and talks to the
parameter server at window boundaries:

* ``PullCommitWorker``  — DOWNPOUR / ADAG (reference ``DOWNPOURWorker`` /
  ``ADAGWorker``): pull center, train a window from it, commit the delta.
* ``StalenessWorker``   — DynSGD (reference ``DynSGDWorker``): same, but the
  commit carries the update counter seen at pull time so the server can
  compute staleness.
* ``ElasticWorker``     — AEASGD / EAMSGD (reference ``AEASGDWorker`` /
  ``EAMSGDWorker``): the local model persists across windows; the elastic
  force E = α(local − center) moves local toward center and is committed.

Workers run as threads in this process (the reference's ran as Spark
executor tasks): JAX compute releases the GIL, so windows genuinely overlap
and commits interleave nondeterministically — real asynchrony, real
staleness.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Optional

import jax
import numpy as np

from ..obs import profile as obs_profile
from ..obs.logging import get_logger
from ..obs.spans import SpanTracer
from ..parallel.sync import _inexact, adopt_float_leaves, tmap as _tmap
from .client import PSClient, WorkerEvicted

Tree = Any


def _host(tree):
    return _tmap(np.asarray, tree)


def _merge_pull(local, center):
    """Adopt the pulled center's floating leaves; keep worker-local
    integer/bool state (RNG counters stay decorrelated across workers —
    same rule as the sync engine's window edge)."""
    return adopt_float_leaves(center, local)


class AsyncWorker(threading.Thread):
    """Base: epochs × windows loop over this worker's partition slice."""

    def __init__(self, worker_id: int, window_fn: Callable,
                 variables: Tree, opt_state: Tree, rng,
                 host: str, port: int, num_epoch: int,
                 device=None, start_window: int = 0, metrics=None,
                 comm_codec: str = "none", profile_memory: bool = True,
                 generation: int = 0, comm_down: str = "none",
                 shm: bool = False, pull_overlap: bool = False,
                 telemetry_s: Optional[float] = None):
        super().__init__(name=f"worker-{worker_id}", daemon=True)
        self.worker_id = worker_id
        #: commit generation this incarnation runs under (ISSUE 9): the
        #: supervisor bumps it on eviction, so a zombie predecessor's
        #: late commits tombstone instead of double-applying
        self.generation = int(generation)
        #: True when the PS evicted this incarnation (a replacement owns
        #: the id): a CLEAN exit, distinct from ``error``
        self.evicted = False
        self.window_fn = window_fn
        self.variables = variables
        self.opt_state = opt_state
        self.rng = rng
        self.ps_host = host
        self.ps_port = port
        self.num_epoch = num_epoch
        self.device = device
        #: delta-compression codec spec (``ps.codecs``): the client built
        #: in ``run()`` owns the stateful error-feedback instance
        self.comm_codec = comm_codec
        #: DOWN pull-compression spec and same-host shm-transport opt-in
        #: (ISSUE 12) — like the codec, the client owns the per-link
        #: state (reference epoch, adaptive policy, rings); a respawned
        #: incarnation's fresh client starts reference-less, so its
        #: first pull is a full resync by construction
        self.comm_down = comm_down
        self.shm = bool(shm)
        #: dispatch-ahead pulls (ISSUE 15): issue window k+1's pull right
        #: after window k's device step is DISPATCHED, so the center
        #: transfer rides the wire while the device computes — the pull
        #: all but leaves the window critical path (recorded per pull as
        #: ``ps.pull.hidden_seconds`` / ``ps.pull.overlap_fraction``).
        #: The worker then trains window k+1 from a center pulled before
        #: its own commit k landed: one extra window of self-staleness,
        #: exactly the regime the async update rules already absorb
        #: (DynSGD's staleness math sees it as staleness 1).  Pull-first
        #: workers only; the elastic family computes before it pulls, so
        #: there is nothing to hide the transfer behind.
        self.pull_overlap = bool(pull_overlap)
        #: (center, seen_updates) collected by the previous window's
        #: overlapped pull — the next window dispatches from it the
        #: moment the final chunk lands
        self._next_center = None
        #: set per window by ``_train`` so the LAST window skips issuing
        #: a dispatch-ahead pull nothing will consume
        self._is_last_window = False
        #: optional shared JSONL sink (``MetricsLogger`` — thread-safe):
        #: one ``heartbeat`` record per committed window, so a stalled or
        #: straggling worker is visible IN-RUN, not post-mortem (ISSUE 2)
        self.metrics = metrics
        #: exact resume: global window index to continue from (= this
        #: worker's commit count in the restored PS snapshot; one commit
        #: per window).  0 on a fresh run.
        self.start_window = int(start_window)
        self.losses: list = []          # one (n_windows, w) array per epoch
        self.epoch_losses: dict = {}    # absolute epoch -> (n_windows, w)
        #: flat (global_window_index, (w,) losses) pairs — the exact record
        self.window_losses: list = []
        self.error: Optional[BaseException] = None
        self.xs = self.ys = None        # (n_windows, w, batch, ...) numpy
        #: per-worker span tracer (built on the worker's own thread in
        #: ``run()``): trace id ``w<worker_id>``, sink shared with the
        #: heartbeats — commit/pull spans and the server's linked apply
        #: spans interleave in one stream (ISSUE 5)
        self.tracer: Optional[SpanTracer] = None
        #: monotonic clock of the previous commit — the heartbeat-gap
        #: source (``gap_s``); wall-clock diffs would absorb NTP steps
        self._last_commit_mono: Optional[float] = None
        self._gap_s: Optional[float] = None
        #: memory-watermark sampling at the heartbeat points (ISSUE 6):
        #: ``mem.*`` gauges in the process-wide registry + ``live_bytes``
        #: on every heartbeat record (the per-window HBM trail)
        self.profile_memory = bool(profile_memory)
        #: push-telemetry cadence (ISSUE 20): when set, the worker ships
        #: ``snapshot_delta`` frames of its process-wide registry to the
        #: PS every ``telemetry_s`` seconds.  Meant for PROCESS placement
        #: (one registry per worker process); thread-placement fleets
        #: share one registry, so the supervisor ingests it in-process
        #: instead of N workers each shipping the same deltas.
        self.telemetry_s = telemetry_s
        self._shipper = None
        self._platform_stated = False

    def set_data(self, xs, ys):
        self.xs, self.ys = xs, ys

    def set_stream(self, factory: Callable, n_windows: int):
        """Disk-streaming data source: ``factory(epoch) -> iterator`` of
        ``(wx, wy)`` window tuples, each ``(window, batch, ...)``.  The
        worker streams its OWN shard partition instead of holding the
        epoch in RAM (SURVEY.md §7 hard part 6)."""
        self._stream_factory = factory
        self._stream_windows = int(n_windows)

    def _put(self, tree):
        if self.device is not None:
            return _tmap(lambda x: jax.device_put(x, self.device), tree)
        return tree

    def _make_client(self):
        """One PS connection — or, when ``port`` is a LIST of shard
        ports (ISSUE 10), a ``ShardedPSClient`` fanning this worker's
        traffic across the fleet with consistent-cut pulls.  Either way
        the worker loop drives the same pull/commit surface."""
        if isinstance(self.ps_port, (list, tuple)):
            from .shard import ShardedPSClient
            return ShardedPSClient(
                [(self.ps_host, p) for p in self.ps_port],
                template=_host(self.variables), worker_id=self.worker_id,
                codec=self.comm_codec, tracer=self.tracer,
                generation=self.generation, down=self.comm_down,
                shm=self.shm or None)
        return PSClient(self.ps_host, self.ps_port, self.worker_id,
                        codec=self.comm_codec, tracer=self.tracer,
                        generation=self.generation, down=self.comm_down,
                        shm=self.shm or None)

    def run(self):
        try:
            # built HERE so the thread-local trace id binds to the worker's
            # own thread (__init__ runs on the spawning thread)
            self.tracer = SpanTracer(self.metrics)
            self.tracer.set_trace_id(f"w{self.worker_id}")
            self._last_commit_mono = time.monotonic()
            client = self._make_client()
            if self.telemetry_s:
                from ..obs.registry import default_registry
                from ..obs.timeseries import TelemetryShipper
                # frames ride the existing PS connection; retry-less, so
                # a frame the server may have folded never replays
                self._shipper = TelemetryShipper(
                    default_registry(),
                    lambda p: client.ship_telemetry(
                        p["delta"], source=p["source"]),
                    source=f"worker{self.worker_id}",
                    period_s=float(self.telemetry_s))
            try:
                self._train(client)
            finally:
                if self._shipper is not None:
                    # flush the tail increments before the socket closes
                    # (ship() itself swallows and counts SEND failures;
                    # this guard keeps teardown alive on anything else)
                    try:
                        self._shipper.ship()
                    except Exception as e:
                        get_logger("ps.worker").warning(
                            "final telemetry flush failed: %s", e)
                client.close()
        except WorkerEvicted:
            # eviction notice, not a failure: the supervisor's replacement
            # owns this worker id — wind down without burning the slice
            self.evicted = True
        except BaseException as e:  # surfaced by the runner after join()
            self.error = e

    def _commit_gap(self) -> float:
        """Monotonic seconds since this worker's previous commit — the
        per-window heartbeat gap shipped on the commit RPC (and echoed on
        the heartbeat record) so the straggler detector and obsview never
        reconstruct gaps from wall-clock diffs (ISSUE 5).  The first
        window measures from loop start: a worker that stalls before its
        first commit still shows a stretched gap."""
        now = time.monotonic()
        self._gap_s = now - self._last_commit_mono
        self._last_commit_mono = now
        return self._gap_s

    @staticmethod
    def _link_ewma(client) -> Optional[float]:
        """The client's link RTT EWMA (ISSUE 15) — representative across
        a sharded client's connections (the slowest link gates the
        fan-out, so take the max)."""
        link = getattr(client, "link", None)
        if link is not None:
            return link.ewma
        subs = getattr(client, "clients", None)
        if subs:
            ewmas = [c.link.ewma for c in subs if c.link.ewma is not None]
            return max(ewmas) if ewmas else None
        return None

    def _train(self, client: PSClient):
        self._client = client
        stream = getattr(self, "_stream_factory", None)
        n_windows = self._stream_windows if stream is not None \
            else int(self.xs.shape[0])
        total = self.num_epoch * n_windows
        try:
            if stream is not None:
                self._stream_epochs(client, stream, n_windows, total)
            else:
                for gw in range(self.start_window, total):
                    wi = gw % n_windows  # window within the epoch
                    self._is_last_window = gw == total - 1
                    wx = self._put(self.xs[wi])
                    wy = self._put(self.ys[wi])
                    losses = self._window(client, wx, wy)
                    self.window_losses.append((gw, np.asarray(losses)))
                    self._heartbeat(gw, n_windows)
        finally:
            # per-epoch view for the COMPLETE epochs this run covered —
            # built even on a crash so a retried worker's merge keeps the
            # epochs this attempt finished (a resumed worker may start
            # mid-epoch; that partial epoch is only in window_losses)
            by_epoch: dict = {}
            for gw, l in self.window_losses:
                by_epoch.setdefault(gw // n_windows, []).append(l)
            self.epoch_losses = {e: np.stack(ls)
                                 for e, ls in by_epoch.items()
                                 if len(ls) == n_windows}
            self.losses = [self.epoch_losses[e]
                           for e in sorted(self.epoch_losses)]

    def _stream_epochs(self, client: PSClient, factory: Callable,
                       n_windows: int, total: int):
        """Epoch loop over streamed windows; a resumed worker fast-forwards
        its first epoch's iterator to the window its commits reached (the
        skipped windows are read and dropped — disk IO, no compute)."""
        gw = self.start_window
        while gw < total:
            epoch = gw // n_windows
            it = factory(epoch)
            try:
                skip = gw % n_windows
                for _ in range(skip):
                    next(it)
                for _ in range(skip, n_windows):
                    wx, wy = next(it)
                    self._is_last_window = gw == total - 1
                    losses = self._window(client, self._put(wx),
                                          self._put(wy))
                    self.window_losses.append((gw, np.asarray(losses)))
                    self._heartbeat(gw, n_windows)
                    gw += 1
            finally:
                if hasattr(it, "close"):
                    it.close()

    def _state_platform(self) -> None:
        """Say ONCE where this worker trains, read off the carry itself
        after its first window: process workers default to the host CPU
        because the parent holds the chip, and a run that believed it was
        on the accelerator must be able to see that it was not.  A carry
        with no device array in it (a host-only window function) sits on
        no device and says nothing."""
        self._platform_stated = True
        on_device = [leaf for leaf in jax.tree_util.tree_leaves(
            (self.rng, self.opt_state, self.variables))
            if isinstance(leaf, jax.Array)]
        if not on_device:
            return
        dev = next(iter(on_device[0].devices()))
        get_logger("ps.worker").info(
            "worker %d trains on %s (%s)", self.worker_id, dev,
            dev.device_kind)
        if self.metrics is not None:
            self.metrics.log("worker_platform", worker_id=self.worker_id,
                             platform=dev.platform,
                             device_kind=dev.device_kind, device=str(dev))

    def _heartbeat(self, gw: int, n_windows: int) -> None:
        """One liveness record per committed window into the shared sink.
        The latest window's mean loss rides along so a live tail of the
        JSONL shows progress AND health per worker; ``worker_id`` +
        monotonic ``gap_s`` make each record self-contained for the
        straggler detector and obsview (ISSUE 5 — no wall-clock-diff
        reconstruction downstream; readers fall back to the pre-PR-5
        ``worker`` key on old streams)."""
        if not self._platform_stated:
            self._state_platform()
        if self._shipper is not None:
            # window-boundary hook, BEFORE the metrics-sink guard: push
            # telemetry is independent of the JSONL heartbeat stream
            self._shipper.maybe_ship()
        if self.metrics is None:
            return
        _, losses = self.window_losses[-1]
        extra = {}
        if self.profile_memory:
            extra["live_bytes"] = obs_profile.observe_memory()["live_bytes"]
        link = self._link_ewma(getattr(self, "_client", None))
        if link is not None:
            # the link half of the health record (ISSUE 15): obsview's
            # offline replay renders gap and link side by side
            extra["link_rtt_s"] = float(link)
        self.metrics.log("heartbeat", worker_id=self.worker_id, window=gw,
                         epoch=gw // n_windows, gap_s=self._gap_s,
                         mean_loss=float(np.mean(losses)), **extra)

    def _run_window(self, wx, wy):
        # slow-motion throttle for the chaos harness / contention benches
        # (ISSUE 9): toy windows finish in ms, far too fast to inject a
        # mid-run fault deterministically — a per-window sleep stretches
        # the run without changing any numerics.  Off (0) in production.
        delay = float(os.environ.get("DKTPU_WINDOW_DELAY_S", 0) or 0)
        if delay > 0:
            time.sleep(delay)
        self.variables, self.opt_state, self.rng, losses = self.window_fn(
            self.variables, self.opt_state, self.rng, wx, wy)
        return losses

    def _window(self, client: PSClient, wx, wy):
        raise NotImplementedError


class _PullFirstWorker(AsyncWorker):
    """Shared loop shape of the pull-first family (DOWNPOUR / ADAG /
    DynSGD): pull center -> train a window from it -> commit the delta.

    With ``pull_overlap`` (ISSUE 15) the loop becomes dispatch-ahead:

    1. dispatch window k's device step (JAX async dispatch — returns
       before the device finishes);
    2. ``pull_begin()`` — window k+1's center transfer starts NOW;
    3. block on window k's outputs (the device time is what hides the
       transfer) and build the delta;
    4. ``pull_join()`` — by now the final chunk has usually landed, so
       window k+1 can dispatch the moment this returns;
    5. commit window k.

    The wire order per connection stays the strict split-phase contract
    (pull request, pull reply, commit request, commit reply), so there
    is no head-of-line deadlock and no reply mismatch; the cost is one
    window of self-staleness — window k+1's center predates commit k —
    which is exactly the regime the async update rules absorb."""

    def _commit_kw(self, seen_updates) -> dict:
        """Extra commit kwargs derived from the pull (DynSGD's
        ``last_update``)."""
        return {}

    def _window(self, client, wx, wy):
        if self._next_center is not None:
            center, seen = self._next_center
            self._next_center = None
        else:
            pulled = client.pull()
            center, seen = pulled[0], pulled[1]
        self.variables = self._put(_merge_pull(_host(self.variables), center))
        losses = self._run_window(wx, wy)
        overlap = self.pull_overlap and not self._is_last_window
        if overlap:
            # window k+1's pull rides the wire while the device runs
            client.pull_begin()
        after = _host(self.variables)
        delta = _tmap(lambda a, c: a - np.asarray(c), after, center)
        if overlap:
            nxt = client.pull_join()
            self._next_center = (nxt[0], nxt[1])
        client.commit(delta, **self._commit_kw(seen),
                      gap_s=self._commit_gap())
        return losses


class PullCommitWorker(_PullFirstWorker):
    """DOWNPOUR / ADAG: local model is replaced by the pulled center each
    window; the commit is the accumulated local update Δ = θ_after −
    θ_pulled (the server's rule decides scaling)."""


class StalenessWorker(_PullFirstWorker):
    """DynSGD: like PullCommitWorker but the commit reports the server
    update counter observed at pull time (staleness bookkeeping)."""

    def _commit_kw(self, seen_updates):
        return {"last_update": seen_updates}


class ElasticWorker(AsyncWorker):
    """AEASGD / EAMSGD: local model persists (exploration); every window the
    elastic force E = α(local − center) is applied locally and committed."""

    def __init__(self, *args, alpha: float = 0.05, **kw):
        super().__init__(*args, **kw)
        self.alpha = float(alpha)

    def _window(self, client, wx, wy):
        losses = self._run_window(wx, wy)
        center, _ = client.pull()
        local = _host(self.variables)
        # elastic force on floating leaves only; integer/bool state (RNG
        # counters) commits a zero delta (the server skips it anyway) and
        # stays worker-local, dtype intact
        elastic = _tmap(
            lambda l, c: self.alpha * (l - np.asarray(c)) if _inexact(l)
            else np.zeros_like(l), local, center)
        self.variables = self._put(
            _tmap(lambda l, e: l - e, local, elastic))
        client.commit(elastic, gap_s=self._commit_gap())
        return losses
