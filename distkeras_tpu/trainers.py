"""Training orchestration — the dist-keras trainer API, TPU-native.

Parity surface with reference ``distkeras/trainers.py``: the same class
names (``SingleTrainer``, ``AveragingTrainer``, ``EnsembleTrainer``,
``DOWNPOUR``, ``AEASGD``, ``EAMSGD``, ``DynSGD``, ``ADAG``), the same
hyperparameters (``num_workers``, ``batch_size``, ``communication_window``,
``rho``, ``momentum``, ``num_epoch``, ``features_col``, ``label_col``) and
the same contract: ``trainer.train(dataset) -> trained model``, plus
``get_training_time()`` / ``get_history()`` / ``serialize()``.

Under the hood nothing resembles the reference's Spark + socket-PS stack:

* ``mode="sync"`` (default): the algorithm's synchronous limit as one
  jit-compiled SPMD program over a ``jax.sharding.Mesh`` — local window
  scans + psum/pmean at window edges (``distkeras_tpu.parallel.sync``).
  This is the idiomatic, fast path: collectives ride ICI, chips never wait
  on a host.
* ``mode="async"``: faithful asynchronous semantics (true staleness, shared
  center variable, per-commit update rules) via the host-side parameter
  server (``distkeras_tpu.ps``) — the reference's behavioral twin.
"""

from __future__ import annotations

import contextlib
import os
import time
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .data.dataset import Dataset
from .models.layers import Activation, Dense, Sequential, state_leaves
from .models.model import Model
from .obs import SpanTracer, get_logger
from .obs import profile as obs_profile
from .obs.registry import default_registry
from .ops.losses import get_loss, probs_loss_variant
from .ops.moe import routing_stats
from .ops.optimizers import get_optimizer
from .parallel import mesh as mesh_lib
from .parallel.sync import (AdagSync, DownpourSync, DynSgdSync, EasgdSync,
                            NoCommSync, SyncEngine, make_window_fn, tmap)
from .utils import serde
from .utils.checkpoint import CheckpointManager
from .utils.metrics import MetricsLogger


class _EpochPipeline:
    """Deferred per-epoch loss readback.

    The reference's workers accumulate loss history on the host as they go;
    a naive translation (``np.asarray(losses)`` after every epoch) inserts a
    device→host sync per epoch and drains the TPU dispatch queue — measured
    ~27% of headline throughput (VERDICT round 2).  Instead, epoch k's
    (on-device) losses are fetched only AFTER epoch k+1 has been
    dispatched, so the readback overlaps device compute and the queue never
    empties.  ``flush()`` performs the final hard sync before the trainer
    returns — timing stays honest: each epoch's wall time is marked at the
    completion of its loss readback, so ``sum(epoch_seconds)`` spans loop
    start → last epoch's compute actually finished.
    """

    def __init__(self, trainer: "Trainer", samples: int, reshape=None):
        self.trainer = trainer
        self.samples = samples
        self.reshape = reshape
        self.pending = None
        self.t_mark = time.time()

    def push(self, epoch: int, dev_losses) -> None:
        """Hand over epoch's device losses; drains the previous epoch."""
        prev, self.pending = self.pending, (epoch, dev_losses)
        self._drain(prev)

    def flush(self) -> None:
        self._drain(self.pending)
        self.pending = None

    def _drain(self, item) -> None:
        if item is None:
            return
        epoch, dev_losses = item
        # the host waiting for that epoch's compute
        with self.trainer.tracer.span("train.readback", epoch=epoch):
            losses = _to_host(dev_losses)
        if self.reshape is not None:
            losses = losses.reshape(self.reshape)
        now = time.time()
        dt, self.t_mark = now - self.t_mark, now
        self.trainer.history.append(losses)
        self.trainer._epoch_metrics(epoch, losses, dt, self.samples)


def _to_host(x):
    """Device leaf → host numpy; on a multi-HOST mesh (jax.distributed)
    allgather the shards this process cannot address so every process
    returns the same complete trained model (the async cluster's
    broadcast contract, for the GSPMD/pipeline trainers)."""
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    return np.asarray(x)


def _resolve_dtype(dtype):
    """None | str | dtype -> numpy dtype (or None).  Accepts the common
    shorthands so ``compute_dtype="bf16"`` works."""
    if dtype is None:
        return None
    if isinstance(dtype, str):
        dtype = {"bf16": "bfloat16", "fp16": "float16",
                 "f32": "float32", "fp32": "float32"}.get(dtype, dtype)
    return jnp.dtype(dtype)


def _ends_in_prob_activation(model) -> bool:
    """Reference models end in a softmax (or sigmoid, for binary heads)
    layer and train with crossentropy on probabilities (Keras semantics).
    Detect that so the loss can use the numerically-stable on-probs
    variant.  Works for native models and ingested Keras-3 models."""
    kmodel = getattr(model, "keras_model", None)
    if kmodel is not None:
        try:
            last = kmodel.layers[-1]
            if type(last).__name__ in ("Softmax", "Sigmoid"):
                return True
            act = getattr(last, "activation", None)
            return getattr(act, "__name__", None) in ("softmax", "sigmoid")
        except (IndexError, AttributeError):
            return False
    layer = model.layer
    while isinstance(layer, Sequential) and layer.layers:
        layer = layer.layers[-1]
    if isinstance(layer, (Activation, Dense)) and \
            layer.activation in ("softmax", "sigmoid"):
        return True
    return False


class Trainer:
    """Base trainer (reference ``distkeras/trainers.py:Trainer``): owns the
    model + optimizer + loss, records wall-clock training time and the
    per-iteration loss history."""

    def __init__(self, keras_model: Model, worker_optimizer="sgd",
                 loss="categorical_crossentropy", features_col: str = "features",
                 label_col: str = "label", num_epoch: int = 1,
                 batch_size: int = 32, learning_rate: float = 0.01,
                 seed: int = 0, checkpoint_dir: Optional[str] = None,
                 checkpoint_keep: int = 3, metrics=None,
                 compute_dtype=None, remat: bool = False,
                 aux_weight: float = 0.0, profile=None):
        self.model = keras_model
        self.worker_optimizer = worker_optimizer
        self.loss = loss
        self.features_col = features_col
        self.label_col = label_col
        self.num_epoch = int(num_epoch)
        self.batch_size = int(batch_size)
        self.learning_rate = float(learning_rate)
        self.seed = int(seed)
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_keep = int(checkpoint_keep)
        #: mixed precision: cast activations to this dtype in the train
        #: step (params/optimizer state stay f32 — layers cast weights to
        #: the activation dtype at use, so matmuls/convs hit the MXU in
        #: e.g. bfloat16 while the master copy keeps full precision).
        self.compute_dtype = _resolve_dtype(compute_dtype)
        #: rematerialization: the step MAY recompute activations in its
        #: backward pass to fit the device — for deep models whose
        #: activations, not weights, are what OOMs (SURVEY.md §7 /
        #: scaling-book memory recipe).  How much is not the user's to
        #: say: ``models.remat.Plan`` decides it where the step is traced,
        #: from the shapes and the device's memory limit (the attention
        #: kernels' outputs are kept, the last child is not wrapped, whole
        #: children are kept from the end backward while they fit), and
        #: ``_fit_remat`` holds the compiled program against that limit
        self.remat = bool(remat)
        #: opt-in MoE router load-balance weight: folds
        #: ``aux_weight * Σ state['aux_loss']`` into the objective
        #: (ADVICE r3 — mitigates router/expert collapse; 0.0 keeps the
        #: reference-parity task-loss-only behavior)
        self.aux_weight = float(aux_weight)
        if metrics is None or isinstance(metrics, MetricsLogger):
            self.metrics = metrics or MetricsLogger(None)
        else:
            self.metrics = MetricsLogger(metrics)
        #: span tracer bound to the SAME sink as the metrics — traces and
        #: per-epoch records interleave in one JSONL stream (ISSUE 2),
        #: readable by ``scripts/obsview.py``
        self.tracer = SpanTracer(self.metrics)
        #: profiling knobs (ISSUE 6): per-epoch ``jax.profiler`` captures
        #: (the spans below appear in their host plane), memory
        #: watermarks — ``obs.ProfileConfig`` | dict of its fields |
        #: trace-dir string
        self.profile = obs_profile.ProfileConfig.resolve(profile)
        #: per-(kind, config) retrace sentinels behind ``_instrumented``:
        #: the cold/warm split for the ``jit_compile`` span AND the
        #: ``jit.compiles``/``jit.retraces`` counters (ISSUE 6)
        self._sentinels: dict = {}

        self.history: list = []
        self.training_time: float = 0.0
        self.trained_variables: Optional[dict] = None

    # -- parity helpers -----------------------------------------------------
    def get_training_time(self) -> float:
        """Parity: reference ``Trainer.get_training_time``."""
        return self.training_time

    def get_history(self) -> list:
        """Per-epoch arrays of per-iteration training loss (reference
        workers accumulate these and trainers expose them)."""
        return self.history

    def get_averaged_history(self) -> np.ndarray:
        """Mean loss per epoch (reference history-averaging helpers in
        ``distkeras/utils.py``)."""
        return np.array([float(np.mean(h)) for h in self.history])

    def serialize(self) -> bytes:
        """Parity: reference ``Trainer.serialize`` (pickled model blob) —
        ours is the msgpack model+variables blob."""
        return serde.serialize_model(self.model, self.trained_variables)

    # -- shared plumbing ----------------------------------------------------
    def _resolve(self):
        loss_fn = get_loss(self.loss)
        if isinstance(self.loss, str) and _ends_in_prob_activation(self.model):
            loss_fn = probs_loss_variant(self.loss) or loss_fn
        optimizer = get_optimizer(self.worker_optimizer, self.learning_rate)
        return loss_fn, optimizer

    def _config_key(self) -> tuple:
        """Hashable fingerprint of everything the compiled programs capture;
        the caches below rebuild when it changes, so mutating a trainer
        hyperparameter between ``train()`` calls takes effect."""
        o, l = self.worker_optimizer, self.loss
        return (o if isinstance(o, str) else id(o),
                l if isinstance(l, str) else id(l),
                self.learning_rate, str(self.compute_dtype), self.remat,
                self.aux_weight)

    def _obs_registry(self):
        """Where this trainer's profiled metrics land: the tracer's
        registry when one is attached (bench.py scopes a private one),
        else the process-wide default."""
        return self.tracer.registry if self.tracer.registry is not None \
            else default_registry()

    def _instrumented(self, run, kind: str = "window"):
        """Split first-call compile time from steady-state dispatch: the
        first invocation of a freshly-built jit program (trace + XLA
        compile happen synchronously inside that call) is recorded as a
        ``jit_compile`` span in the metrics stream; warm calls dispatch in
        microseconds and go unobserved.  Without the split, compile time
        silently pollutes the first epoch's throughput number — exactly
        the bias BASELINE round 5 tripped over.

        ISSUE 6: every call additionally feeds the recompilation sentinel
        — a NEW arg signature (shape/dtype tree) after the cold compile
        is a retrace, counted into ``jit.retraces`` (drift-gated) and
        recorded as a ``jit_compile`` span flagged ``retrace=True``.

        ISSUE 26: the ``jit_compile`` record says what the call spent —
        ``trace_s`` (Python tracing), ``lower_s`` (to MLIR), ``backend_s``
        (XLA's compile, or the persistent cache's read and load at a
        hit), ``cache_hits`` / ``cache_misses`` — from JAX's own timers
        (``obs.profile.compile_totals``); what is left of ``seconds`` is
        the dispatch.

        ISSUE 38: and what the program takes.  A ``run`` that can be
        lowered (a ``jax.jit``) is compiled before it is called
        (``_fit_remat``: the call then finds trace, lowering and
        executable cached), and once the call is dispatched the
        executable's memory account (``obs.profile.program_memory``: the
        ``program_*`` bytes, the device's limit and its ``bytes_in_use``)
        goes on the record.  A plain wrapper records none and runs as it
        is; a warm call does nothing of this."""
        key = (kind, self._config_key())
        sentinel = self._sentinels.get(key)
        if sentinel is None:
            sentinel = self._sentinels[key] = obs_profile.RetraceSentinel(
                f"{type(self).__name__}.{kind}",
                registry=self._obs_registry, sink=self.metrics)

        def wrapped(*args):
            state = sentinel.observe(args)
            if state == "warm":
                return run(*args)
            with self.tracer.span("jit_compile", kind=kind,
                                  trainer=type(self).__name__,
                                  **({"retrace": True}
                                     if state == "retrace" else {})
                                  ) as record:
                before = obs_profile.compile_totals()
                compiled = None
                try:
                    if hasattr(run, "lower"):
                        compiled = self._fit_remat(run, args, record)
                    return run(*args)
                finally:
                    if compiled is not None:
                        record.update(obs_profile.program_memory(compiled))
                    record.update(obs_profile.compile_spent(before))
        return wrapped

    def _fit_remat(self, run, args, record: dict):
        """Compiles the cold call's program before the call runs it (the
        call then finds trace, lowering and executable cached: no second
        of any) and returns the executable, whose memory account the
        record carries.  Of a program that may recompute
        (``run.remat_plan``, ``models.remat``) it is the judge besides:
        it puts the program's own size (``program_memory``'s
        ``program_bytes``) beside the plan's estimate on the
        ``jit_compile`` record, and where that passes ``remat.REFUSE`` of
        the device's limit, or XLA refuses the program for memory, has
        the plan recompute one child more and compiles again."""
        plan = getattr(run, "remat_plan", None)
        if plan is None:
            return run.lower(*args).compile()
        plan.tracer = self.tracer
        while True:
            try:
                compiled = run.lower(*args).compile()
            except jax.errors.JaxRuntimeError as e:
                if "RESOURCE_EXHAUSTED" not in str(e) \
                        or not plan.step_back():
                    raise
                why = f"XLA refused the program: {str(e)[:200]}"
            else:
                size = obs_profile.program_memory(compiled).get(
                    "program_bytes", 0)
                if not plan.judge(size):
                    record.update(plan.record())
                    return compiled
                why = f"the program is {size} B of the device's {plan.limit}"
            get_logger("trainers").warning(
                "remat: %s; compiling again with %d of %d children "
                "recomputed", why, plan.first_kept, plan.children)
            # every trace, the scanned step's too, is made again
            jax.clear_caches()

    def _profiled_run(self, run, epoch: int, *args):
        """One epoch-program call, optionally under a per-epoch
        ``jax.profiler`` capture (``profile.trace_dir`` /
        ``trace_epochs`` — ISSUE 6).  The capture blocks on the outputs
        before stopping so the trace holds THIS epoch's device work; the
        pipelined (uncaptured) epochs keep their no-sync dispatch.  The
        call itself is the ``train.dispatch`` span (a cold call's
        ``jit_compile`` nests in it)."""
        capture = self.profile.trace_epoch(epoch)
        with obs_profile.device_trace(
                os.path.join(self.profile.trace_dir, f"epoch{epoch}")) \
                if capture else contextlib.nullcontext():
            with self.tracer.span("train.dispatch", epoch=epoch):
                out = run(*args)
            if capture:
                jax.block_until_ready(out)
        return out

    def _window_run(self):
        """Cached jit window program — repeated ``train()`` calls on an
        unchanged trainer reuse the compiled executable instead of
        re-tracing (same shapes → no recompile)."""
        key = self._config_key()
        cached = getattr(self, "_run_cache", None)
        if cached is None or cached[0] != key:
            loss_fn, optimizer = self._resolve()
            run = make_window_fn(self.model, loss_fn, optimizer,
                                 compute_dtype=self.compute_dtype,
                                 remat=self.remat,
                                 aux_weight=self.aux_weight)
            self._run_cache = (key, run, optimizer)
        _, run, optimizer = self._run_cache
        return self._instrumented(run), optimizer

    def _finish(self, variables) -> Model:
        with self.tracer.span("train.to_host"):  # the variables' way back
            self.trained_variables = jax.tree_util.tree_map(_to_host,
                                                            variables)
        self.model.variables = self.trained_variables
        # the routed layers' last step, now that their state is on the host
        stats = routing_stats(self.trained_variables["state"])
        if stats is not None:
            registry = default_registry()
            registry.counter("moe.rows_needed").inc(stats["rows_needed"])
            registry.counter("moe.rows_run").inc(stats["rows_run"])
            # rounds ÷ routed layers = 1.0: every layer ran one round
            registry.counter("moe.rounds").inc(stats["rounds"])
            registry.gauge("moe.round_rows").set(stats["round_rows"])
            registry.gauge("moe.expert_load_max_over_mean").set(
                stats["load_max_over_mean"])
        # and an exit gate's: the mean chance of answering at each pass
        for shares in state_leaves(self.trained_variables["state"],
                                   "exit_share"):
            shares = np.asarray(shares)  # (steps,), or one row a worker
            for t, share in enumerate(
                    shares.reshape(-1, shares.shape[-1]).mean(axis=0)):
                default_registry().gauge(f"loop.exit_share.{t}").set(
                    float(share))
        return self.model

    def train(self, dataset: Dataset, shuffle: bool = False,
              resume: bool = False) -> Model:
        """Parity: reference ``Trainer.train(dataframe, shuffle)``.

        ``resume=True`` restarts from the latest checkpoint in
        ``checkpoint_dir`` (our addition — the reference has no mid-training
        persistence, SURVEY.md §5.4).
        """
        t0 = time.time()
        self._resume = bool(resume)
        try:
            with self.tracer.span("train", trainer=type(self).__name__,
                                  epochs=self.num_epoch):
                return self._train(dataset, shuffle)
        finally:
            self.training_time = time.time() - t0

    def _train(self, dataset: Dataset, shuffle: bool) -> Model:
        raise NotImplementedError

    # -- checkpoint plumbing -------------------------------------------------
    def _ckpt_manager(self) -> Optional[CheckpointManager]:
        if not self.checkpoint_dir:
            return None
        return CheckpointManager(self.checkpoint_dir, keep=self.checkpoint_keep)

    def _maybe_restore(self, ckpt, state):
        """Returns ``(state, start_epoch)``; restores iff resume requested."""
        if ckpt is None or not getattr(self, "_resume", False):
            return state, 0
        if ckpt.latest_step() is None:
            return state, 0
        state, meta = ckpt.restore(state)
        return state, int(meta.get("epoch", -1)) + 1

    def _epoch_metrics(self, epoch: int, losses: np.ndarray, dt: float,
                       samples: int) -> None:
        extra = {}
        if self.profile.memory:
            # memory watermark sample at the per-epoch heartbeat point
            # (ISSUE 6): mem.* gauges in the obs registry, live bytes on
            # the epoch record for obsview / --export-trace
            snap = obs_profile.observe_memory(self._obs_registry())
            extra["live_bytes"] = snap["live_bytes"]
        self.metrics.log("epoch", trainer=type(self).__name__, epoch=epoch,
                         mean_loss=float(np.mean(losses)),
                         epoch_seconds=dt,
                         samples_per_sec=samples / dt if dt > 0 else 0.0,
                         **extra)


class SingleTrainer(Trainer):
    """Single-worker baseline (reference ``SingleTrainer`` +
    ``SingleTrainerWorker``): the whole dataset on one chip, a jit-compiled
    ``lax.scan`` over minibatches per epoch.  The conformance anchor all
    distributed trainers are compared against.

    Also accepts a disk-backed ``data.streaming.ShardedFileDataset``:
    epochs then stream window-by-window from disk (``stream_window``
    batches per jit call) with bounded host memory — the ImageNet-scale
    input story (SURVEY.md §7 hard part 6)."""

    #: batches per jit window call on the streaming path (static shape;
    #: larger = fewer dispatches, more host RAM in flight)
    stream_window = 8

    def _train(self, dataset: Dataset, shuffle: bool) -> Model:
        from .data.streaming import ShardedFileDataset
        if isinstance(dataset, ShardedFileDataset):
            return self._train_stream(dataset, shuffle)
        if shuffle:
            dataset = dataset.shuffle(self.seed)
        run, optimizer = self._window_run()

        with self.tracer.span("train.stage"):
            ds = dataset.coalesce(1)
            stacked, steps = ds.stacked(
                [self.features_col, self.label_col], self.batch_size)
            xs = jnp.asarray(stacked[self.features_col][0])
            ys = jnp.asarray(stacked[self.label_col][0])

        with self.tracer.span("train.init"):
            variables = self.model.init(self.seed)
            opt_state = optimizer.init(variables["params"])
            rng = jax.random.PRNGKey(self.seed + 1)

        ckpt = self._ckpt_manager()
        (variables, opt_state, rng), start_epoch = self._maybe_restore(
            ckpt, (variables, opt_state, rng))
        samples = int(xs.shape[0]) * self.batch_size
        pipe = _EpochPipeline(self, samples)
        for epoch in range(start_epoch, self.num_epoch):
            variables, opt_state, rng, losses = self._profiled_run(
                run, epoch, variables, opt_state, rng, xs, ys)
            pipe.push(epoch, losses)
            if ckpt is not None:  # note: saving implies a per-epoch sync
                ckpt.save(epoch, (variables, opt_state, rng),
                          {"epoch": epoch})
        pipe.flush()
        return self._finish(variables)

    def _train_stream(self, source, shuffle: bool) -> Model:
        """Stream epochs from disk: the host assembles window w+1 (the
        prefetch thread / tf.data does the IO) while the device trains
        window w; loss readback is deferred to epoch edges as usual."""
        run, optimizer = self._window_run()
        bs = self.batch_size
        steps = source.steps_per_epoch(bs)
        if steps == 0:
            raise ValueError(f"batch_size {bs} exceeds dataset rows "
                             f"{source.num_rows}")
        w = max(1, min(int(self.stream_window), steps))
        n_windows = steps // w

        variables = self.model.init(self.seed)
        opt_state = optimizer.init(variables["params"])
        rng = jax.random.PRNGKey(self.seed + 1)
        ckpt = self._ckpt_manager()
        (variables, opt_state, rng), start_epoch = self._maybe_restore(
            ckpt, (variables, opt_state, rng))

        cols = [self.features_col, self.label_col]
        samples = n_windows * w * bs
        pipe = _EpochPipeline(self, samples)
        for epoch in range(start_epoch, self.num_epoch):
            seed = (self.seed + 1000 + epoch) if shuffle else None
            it = source.batches(cols, bs, seed=seed)
            epoch_losses = []
            try:
                for _ in range(n_windows):
                    window = [next(it) for _ in range(w)]
                    wx = np.stack([b[0] for b in window])
                    wy = np.stack([b[1] for b in window])
                    variables, opt_state, rng, losses = run(
                        variables, opt_state, rng, jnp.asarray(wx),
                        jnp.asarray(wy))
                    epoch_losses.append(losses)
            finally:
                # the epoch takes exactly n_windows*w batches; close the
                # stream so the prefetch thread releases its shard now
                if hasattr(it, "close"):
                    it.close()
            pipe.push(epoch, jnp.concatenate(epoch_losses))
            if ckpt is not None:
                ckpt.save(epoch, (variables, opt_state, rng),
                          {"epoch": epoch})
        pipe.flush()
        return self._finish(variables)


class DistributedTrainer(Trainer):
    """Base for multi-worker trainers (reference ``DistributedTrainer``):
    owns ``num_workers``, partitions the dataset one-partition-per-worker,
    and drives the epoch program.  Subclasses pick the communication rule
    (sync mode) / parameter-server flavor (async mode)."""

    #: default window when the algorithm has no explicit one
    _default_window = 1

    def __init__(self, keras_model: Model, worker_optimizer="sgd",
                 loss="categorical_crossentropy", num_workers: int = 2,
                 features_col: str = "features", label_col: str = "label",
                 num_epoch: int = 1, batch_size: int = 32,
                 communication_window: Optional[int] = None,
                 learning_rate: float = 0.01, seed: int = 0,
                 mode: str = "sync", mesh=None,
                 async_workers: str = "threads",
                 comm_codec: str = "none",
                 comm_down: str = "none",
                 ps_shm: bool = False,
                 pull_overlap: bool = False,
                 ps_shards: int = 1,
                 heartbeat_hard_s: float = 30.0,
                 startup_grace_s: float = 300.0, **kw):
        super().__init__(keras_model, worker_optimizer, loss, features_col,
                         label_col, num_epoch, batch_size, learning_rate, seed,
                         **kw)
        self.num_workers = int(num_workers)
        #: fleet self-healing knobs (ISSUE 9, async mode): a worker whose
        #: commits/pulls stop reaching the PS for ``heartbeat_hard_s`` is
        #: evicted and respawned by the live supervisor;
        #: ``startup_grace_s`` applies instead until an incarnation's
        #: first commit (interpreter start + jit compile must not read as
        #: a stall)
        self.heartbeat_hard_s = float(heartbeat_hard_s)
        self.startup_grace_s = float(startup_grace_s)
        #: live fleet supervisor, set only while an async run is in
        #: flight — the ``add_worker`` elastic-join seam
        self._supervisor = None
        self.communication_window = int(
            communication_window if communication_window is not None
            else self._default_window)
        if mode not in ("sync", "async"):
            raise ValueError(f"mode must be 'sync' or 'async', got {mode!r}")
        if async_workers not in ("threads", "processes"):
            raise ValueError(f"async_workers must be 'threads' or "
                             f"'processes', got {async_workers!r}")
        self.mode = mode
        self.mesh = mesh
        #: async-mode worker placement: in-process threads (fast, hermetic)
        #: or one OS process per worker — the reference's deployment shape
        #: (Spark executor tasks); see ``ps.runner`` / ``ps.worker_main``.
        self.async_workers = async_workers
        #: async-mode center sharding (ISSUE 10): 1 (default) hosts the
        #: center on one SocketParameterServer — bit-identical to the
        #: pre-shard behavior; N > 1 partitions the center pytree across
        #: N shard servers (``ps.shard``), each with its own lock/accept
        #: loop/pull cache, and workers fan commits/pulls out in parallel
        #: with consistent-cut assembly.
        self.ps_shards = int(ps_shards)
        if self.ps_shards < 1:
            raise ValueError(f"ps_shards must be >= 1, got {ps_shards}")
        #: async-mode commit compression (``ps.codecs``): "none" (default,
        #: bit-identical numerics), "int8", "bf16", or "topk<frac>" —
        #: quantized deltas with worker-side error feedback (ISSUE 4).
        #: Sync mode communicates on-device (ICI collectives); no codec.
        from .ps.codecs import Codec, get_codec, validate_down_spec
        if isinstance(comm_codec, Codec):
            # a Codec INSTANCE carries per-worker mutable error-feedback
            # state and cannot be shared by N workers (racing residuals);
            # keep only its spec — every worker builds its own instance
            comm_codec = comm_codec.name
        get_codec(comm_codec)  # validate the spec at construction time
        self.comm_codec = comm_codec
        #: async-mode DOWN pull compression (ISSUE 12): "none" (default —
        #: raw pulls, bit-identical wire), "int8"/"bf16"/"topk<frac>"
        #: (quantized residuals against the server's shared reference
        #: center), or "adaptive" (per-link codec chosen from measured
        #: pull RTTs, with hysteresis and a recorded switch trail)
        self.comm_down = validate_down_spec(comm_down)
        #: async-mode same-host shared-memory transport (ISSUE 12): offer
        #: shm rings in the hello on every PS connection — co-located
        #: peers (thread-placed fleets; the cluster runner's process-0
        #: host) skip the kernel socket path, cross-host peers are
        #: refused at the capability probe and stay on TCP untouched
        self.ps_shm = bool(ps_shm)
        #: async-mode dispatch-ahead pulls (ISSUE 15): each pull-first
        #: worker issues window k+1's pull right after window k's device
        #: step is dispatched, hiding the center transfer behind compute
        #: (``ps.pull.hidden_seconds`` / ``ps.pull.overlap_fraction``)
        #: at the cost of one window of self-staleness — the regime the
        #: async update rules already absorb.  Streamed pull replies
        #: themselves (the ``DKW4`` chunk wire) are negotiated per
        #: connection and on by default; ``DKTPU_STREAM=0`` opts out.
        self.pull_overlap = bool(pull_overlap)

    # -- fleet elasticity (ISSUE 9) -----------------------------------------
    def add_worker(self, worker_id=None) -> int:
        """Elastic join: add a worker to the LIVE async run (``train()``
        currently blocking on another thread).  The new worker pulls the
        current center and starts committing, fully accounted by the PS
        (``ps.joins``).  With no id, the next unused one is picked.
        Returns the worker id."""
        sup = self._supervisor
        if sup is None:
            raise RuntimeError(
                "no live async run to join — add_worker() is valid only "
                "while train(mode='async') is in flight")
        return sup.add_worker(worker_id)

    # -- algorithm hooks ----------------------------------------------------
    def _sync_algorithm(self):
        raise NotImplementedError

    def _ps_factory(self):
        """Async-mode parameter-server factory; see ``distkeras_tpu.ps``."""
        raise NotImplementedError(
            f"{type(self).__name__} has no async parameter-server mode")

    # -- data staging -------------------------------------------------------
    def _stage_data(self, dataset: Dataset, window: int):
        """(P, n_windows, window, batch, ...) device arrays, sharded on the
        workers axis — Spark's repartition+ship collapsed to one transfer."""
        ds = dataset.repartition(self.num_workers)
        stacked, steps = ds.stacked([self.features_col, self.label_col],
                                    self.batch_size)
        n_windows = steps // window
        if n_windows == 0:
            raise ValueError(
                f"communication_window {window} exceeds the {steps} "
                f"steps available per worker (decrease window/batch_size "
                f"or add data)")
        dropped = steps - n_windows * window
        if dropped:
            warnings.warn(
                f"{dropped} of {steps} per-worker batches don't fill a "
                f"communication_window of {window} and are dropped each "
                f"epoch (static shapes require whole windows); pick a "
                f"window dividing {steps} to use all data", stacklevel=3)

        def shape_windows(a):
            a = a[:, : n_windows * window]
            return a.reshape(a.shape[0], n_windows, window, *a.shape[2:])

        xs = shape_windows(stacked[self.features_col])
        ys = shape_windows(stacked[self.label_col])
        return xs, ys, n_windows

    # -- training -----------------------------------------------------------
    def _train(self, dataset: Dataset, shuffle: bool) -> Model:
        from .data.streaming import ShardedFileDataset
        if isinstance(dataset, ShardedFileDataset):
            # disk-streaming path: every worker streams ITS shard partition
            # (partition == worker, SURVEY.md §3.1 boundary #1); the whole
            # epoch is never resident in host RAM or HBM
            if self.mode == "async":
                return self._train_async(dataset, stream_shuffle=shuffle)
            return self._train_sync_stream(dataset, shuffle)
        if shuffle:
            dataset = dataset.shuffle(self.seed)
        if self.mode == "async":
            return self._train_async(dataset)
        return self._train_sync(dataset)

    def _config_key(self) -> tuple:
        return super()._config_key() + (
            self.num_workers, self.communication_window,
            id(self.mesh) if self.mesh is not None else None,
            getattr(self, "rho", None), getattr(self, "momentum", None))

    def _engine_parts(self):
        """Cached (engine, mesh, optimizer, programs) for the current
        hyperparameters; ``programs`` caches the compiled epoch/window
        executables so repeated ``train()`` calls skip re-tracing."""
        key = self._config_key()
        cached = getattr(self, "_engine_cache", None)
        if cached is None or cached[0] != key:
            loss_fn, optimizer = self._resolve()
            mesh = self.mesh if self.mesh is not None else mesh_lib.make_mesh(
                self.num_workers)
            engine = SyncEngine(self.model, loss_fn, optimizer,
                                self._sync_algorithm(), self.num_workers,
                                self.communication_window, mesh=mesh,
                                compute_dtype=self.compute_dtype,
                                remat=self.remat,
                                aux_weight=self.aux_weight)
            self._engine_cache = (key, engine, mesh, optimizer, {})
        return self._engine_cache[1:]

    def _engine_run(self):
        """Cached jit epoch program + mesh + optimizer (see
        ``Trainer._window_run`` — same reuse-across-train()-calls story)."""
        engine, mesh, optimizer, programs = self._engine_parts()
        if "epoch" not in programs:
            programs["epoch"] = engine.epoch_fn()
        return self._instrumented(programs["epoch"], "epoch"), mesh, optimizer

    def _engine_window(self):
        """Cached jit single-window program (streaming path)."""
        engine, mesh, optimizer, programs = self._engine_parts()
        if "window" not in programs:
            programs["window"] = engine.window_fn()
        return (self._instrumented(programs["window"], "window"), mesh,
                optimizer)

    def _train_sync(self, dataset: Dataset) -> Model:
        run, mesh, optimizer = self._engine_run()
        P = self.num_workers

        with self.tracer.span("train.stage"):
            xs, ys, _ = self._stage_data(dataset, self.communication_window)
            xs = mesh_lib.host_to_mesh(mesh, xs)
            ys = mesh_lib.host_to_mesh(mesh, ys)

        with self.tracer.span("train.init"):
            center = self.model.init(self.seed)
            center = mesh_lib.broadcast_to_mesh(mesh, center)
            local = tmap(lambda x: np.broadcast_to(np.asarray(x)[None],
                                                   (P, *np.shape(x))),
                         center)
            local = mesh_lib.host_to_mesh(mesh, local)
            opt_state = jax.vmap(optimizer.init)(local["params"])
            rngs = jax.random.split(jax.random.PRNGKey(self.seed + 1), P)
            rngs = mesh_lib.host_to_mesh(mesh, rngs)

        ckpt = self._ckpt_manager()
        (center, local, opt_state, rngs), start_epoch = self._maybe_restore(
            ckpt, (center, local, opt_state, rngs))
        if start_epoch:  # restored host arrays need re-placing on the mesh
            center = mesh_lib.broadcast_to_mesh(mesh, center)
            local = mesh_lib.host_to_mesh(mesh, local)
            opt_state = mesh_lib.host_to_mesh(mesh, opt_state)
            rngs = mesh_lib.host_to_mesh(mesh, rngs)
        samples = int(xs.shape[1]) * int(xs.shape[2]) * self.batch_size * P
        pipe = _EpochPipeline(self, samples, reshape=(P, -1))
        for epoch in range(start_epoch, self.num_epoch):
            center, local, opt_state, rngs, losses = self._profiled_run(
                run, epoch, center, local, opt_state, rngs, xs, ys)
            pipe.push(epoch, losses)  # history rows: (workers, steps)
            if ckpt is not None:  # note: saving implies a per-epoch sync
                ckpt.save(epoch, (center, local, opt_state, rngs),
                          {"epoch": epoch})
        pipe.flush()
        return self._collect(center, local)

    def _collect(self, center, local) -> Model:
        """Final model = the center variable (reference: trainers return
        ``PS.get_model()``)."""
        return self._finish(center)

    # -- disk-streaming sync path (SURVEY.md §7 hard part 6) ----------------
    def _stream_locals(self, P: int):
        """(center, local) initial host pytrees for the streaming path;
        local's leading axis is workers.  Default: all workers start from
        the center init (EnsembleTrainer decorrelates seeds instead)."""
        center = self.model.init(self.seed)
        local = tmap(lambda x: np.broadcast_to(np.asarray(x)[None],
                                               (P, *np.shape(x))), center)
        return center, local

    def _train_sync_stream(self, source, shuffle: bool) -> Model:
        """Synchronous epochs streamed from disk: each worker's shard
        partition feeds its mesh slot window-by-window; the host (with
        per-worker prefetch threads) assembles window w+1 while the devices
        train window w.  Peak host memory is O(P × window × batch), never
        the epoch."""
        from .data.streaming import (worker_window_factory,
                                     worker_windows_per_epoch)
        run, mesh, optimizer = self._engine_window()
        P = self.num_workers
        w = self.communication_window
        bs = self.batch_size
        n_windows = worker_windows_per_epoch(source, bs, P, w)

        center, local = self._stream_locals(P)
        center = mesh_lib.broadcast_to_mesh(mesh, center)
        local = mesh_lib.host_to_mesh(mesh, local)
        opt_state = jax.vmap(optimizer.init)(local["params"])
        rngs = jax.random.split(jax.random.PRNGKey(self.seed + 1), P)
        rngs = mesh_lib.host_to_mesh(mesh, rngs)

        ckpt = self._ckpt_manager()
        (center, local, opt_state, rngs), start_epoch = self._maybe_restore(
            ckpt, (center, local, opt_state, rngs))
        if start_epoch:  # restored host arrays need re-placing on the mesh
            center = mesh_lib.broadcast_to_mesh(mesh, center)
            local = mesh_lib.host_to_mesh(mesh, local)
            opt_state = mesh_lib.host_to_mesh(mesh, opt_state)
            rngs = mesh_lib.host_to_mesh(mesh, rngs)

        cols = [self.features_col, self.label_col]
        factories = [worker_window_factory(source, cols, bs, k, P, w,
                                           self.seed, shuffle)
                     for k in range(P)]
        samples = n_windows * w * bs * P
        pipe = _EpochPipeline(self, samples, reshape=(P, -1))
        for epoch in range(start_epoch, self.num_epoch):
            its = [f(epoch) for f in factories]
            losses = []
            try:
                for _ in range(n_windows):
                    grp = [next(it) for it in its]
                    wx = np.stack([g[0] for g in grp])  # (P, w, B, ...)
                    wy = np.stack([g[1] for g in grp])
                    center, local, opt_state, rngs, l = run(
                        center, local, opt_state, rngs,
                        mesh_lib.host_to_mesh(mesh, wx),
                        mesh_lib.host_to_mesh(mesh, wy))
                    losses.append(l)  # (P, w) device array, not synced
            finally:
                for it in its:
                    it.close()
            pipe.push(epoch, jnp.concatenate(losses, axis=1))
            if ckpt is not None:  # note: saving implies a per-epoch sync
                ckpt.save(epoch, (center, local, opt_state, rngs),
                          {"epoch": epoch})
        pipe.flush()
        return self._collect(center, local)

    def _train_async(self, dataset, stream_shuffle: Optional[bool] = None):
        try:
            from .ps.runner import run_async_training
        except ImportError as e:
            raise NotImplementedError(
                "async parameter-server mode requires the distkeras_tpu.ps "
                "package") from e
        return run_async_training(self, dataset,
                                  stream_shuffle=stream_shuffle)


class AveragingTrainer(DistributedTrainer):
    """Model averaging (reference ``AveragingTrainer``): workers train
    completely independently on their partition; the final model is the
    plain average of all worker models."""

    def __init__(self, keras_model, worker_optimizer="sgd",
                 loss="categorical_crossentropy", num_workers: int = 2,
                 **kw):
        super().__init__(keras_model, worker_optimizer, loss, num_workers, **kw)

    def _sync_algorithm(self):
        return NoCommSync()

    def _collect(self, center, local) -> Model:
        averaged = tmap(lambda l: jnp.mean(l, axis=0), local)
        return self._finish(averaged)


class EnsembleTrainer(DistributedTrainer):
    """Ensemble training (reference ``EnsembleTrainer``): N independent
    models (different partitions AND different init seeds), all returned.
    ``train`` returns a list of Models."""

    def __init__(self, keras_model, worker_optimizer="sgd",
                 loss="categorical_crossentropy", num_ensembles: int = 2,
                 **kw):
        super().__init__(keras_model, worker_optimizer, loss,
                         num_workers=num_ensembles, **kw)
        self.num_ensembles = int(num_ensembles)

    def _sync_algorithm(self):
        return NoCommSync()

    def _stream_locals(self, P: int):
        # independent decorrelated inits per ensemble member (same rule as
        # the in-RAM path below)
        fresh = getattr(self.model, "reinit", self.model.init)
        inits = [fresh(self.seed + i) for i in range(P)]
        local = tmap(lambda *xs_: np.stack([np.asarray(x) for x in xs_]),
                     *inits)
        return inits[0], local

    def _collect(self, center, local):
        # N independent models, all returned (in-RAM and streaming paths;
        # on a multi-process mesh the worker-sharded stack allgathers)
        local = jax.tree_util.tree_map(_to_host, local)
        models = []
        for i in range(self.num_workers):
            # type(...) so ingested Keras models (KerasAdapter) work too
            m = type(self.model).from_config(self.model.config())
            m.variables = tmap(lambda l: l[i], local)
            models.append(m)
        self.trained_variables = models[0].variables
        return models

    def _train_sync(self, dataset: Dataset):
        run, mesh, optimizer = self._engine_run()
        P = self.num_workers

        xs, ys, _ = self._stage_data(dataset, self.communication_window)
        xs = mesh_lib.host_to_mesh(mesh, xs)
        ys = mesh_lib.host_to_mesh(mesh, ys)

        # independent inits per ensemble member (reinit = deliberate fresh
        # decorrelated init; Keras adapters keep init() as the pretrained
        # snapshot and expose reinit separately)
        fresh = getattr(self.model, "reinit", self.model.init)
        inits = [fresh(self.seed + i) for i in range(P)]
        local = tmap(lambda *xs_: np.stack([np.asarray(x) for x in xs_]),
                     *inits)
        local = mesh_lib.host_to_mesh(mesh, local)
        center = mesh_lib.broadcast_to_mesh(mesh, inits[0])
        opt_state = jax.vmap(optimizer.init)(local["params"])
        rngs = jax.random.split(jax.random.PRNGKey(self.seed + 1), P)
        rngs = mesh_lib.host_to_mesh(mesh, rngs)

        ckpt = self._ckpt_manager()
        (center, local, opt_state, rngs), start_epoch = self._maybe_restore(
            ckpt, (center, local, opt_state, rngs))
        if start_epoch:  # restored host arrays need re-placing on the mesh
            center = mesh_lib.broadcast_to_mesh(mesh, center)
            local = mesh_lib.host_to_mesh(mesh, local)
            opt_state = mesh_lib.host_to_mesh(mesh, opt_state)
            rngs = mesh_lib.host_to_mesh(mesh, rngs)
        samples = int(xs.shape[1]) * int(xs.shape[2]) * self.batch_size * P
        pipe = _EpochPipeline(self, samples, reshape=(P, -1))
        for epoch in range(start_epoch, self.num_epoch):
            center, local, opt_state, rngs, losses = self._profiled_run(
                run, epoch, center, local, opt_state, rngs, xs, ys)
            pipe.push(epoch, losses)
            if ckpt is not None:
                ckpt.save(epoch, (center, local, opt_state, rngs),
                          {"epoch": epoch})
        pipe.flush()
        return self._collect(center, local)


class SpmdTrainer(Trainer):
    """Multi-axis GSPMD trainer — the TPU-native strategy beyond the
    reference's data parallelism: one jit-compiled train step over a
    dp × mp mesh; XLA inserts the gradient all-reduce (dp) and partitions
    large matmuls (mp) from sharding annotations alone
    (``parallel.spmd``).  No reference equivalent; this is where models
    too large to replicate train.

    ``mesh_shape``: e.g. ``{"dp": 2, "mp": 4}`` (defaults to all devices
    on dp).  Also accepts a disk-backed ``ShardedFileDataset``: epochs
    then stream window-by-window with dp-sharded batches and mp-sharded
    params (``_train_stream``).
    """

    def __init__(self, keras_model: Model, worker_optimizer="sgd",
                 loss="categorical_crossentropy",
                 mesh_shape: Optional[dict] = None, **kw):
        super().__init__(keras_model, worker_optimizer, loss, **kw)
        self.mesh_shape = mesh_shape
        #: filled per ``train()``: per-leaf PartitionSpec + global vs
        #: per-device bytes (``spmd.sharding_report``) — the audit that mp
        #: actually sharded parameters (VERDICT r3 weak #3)
        self.sharding_report: Optional[dict] = None
        #: the AOT-compiled window executable; ``.as_text()`` is the HLO
        #: tests grep for the expected collectives
        self.compiled_step = None

    def _config_key(self) -> tuple:
        # the mesh (and thus the compiled program + AOT executable) is
        # cached under this key — mesh_shape edits must invalidate it
        return super()._config_key() + (
            tuple(sorted(self.mesh_shape.items())) if self.mesh_shape
            else None,)

    def _window_run(self):
        """Like ``Trainer._window_run`` but the forward is wrapped in
        activation sharding anchors (``spmd.constrained_model``) so the
        intended dp/mp sharding is part of the traced program, not just a
        placement hint."""
        from .parallel import spmd
        key = self._config_key()
        cached = getattr(self, "_run_cache", None)
        if cached is None or cached[0] != key:
            loss_fn, optimizer = self._resolve()
            if self.mesh_shape:
                axes, sizes = zip(*self.mesh_shape.items())
            else:
                axes, sizes = ("dp",), (len(jax.devices()),)
            mesh = mesh_lib.make_mesh(axis_names=axes, shape=sizes)
            dp = "dp" if "dp" in axes else axes[0]
            proxy = spmd.constrained_model(self.model, mesh, dp)
            run = make_window_fn(proxy, loss_fn, optimizer,
                                 compute_dtype=self.compute_dtype,
                                 remat=self.remat,
                                 aux_weight=self.aux_weight)
            self._run_cache = (key, run, optimizer, mesh, dp)
        return self._run_cache[1:]

    def _train_stream(self, source, shuffle: bool) -> Model:
        """Disk-streaming GSPMD epochs: windows assemble on the host while
        the mesh trains the previous one; batches land batch-sharded over
        dp, params stay mp-sharded — ImageNet-scale inputs for models too
        large to replicate (SURVEY.md §7 hard part 6 × GSPMD)."""
        from .data.streaming import window_batches
        from .parallel import spmd
        run, optimizer, mesh, dp = self._window_run()
        run = self._instrumented(run)
        bs = self.batch_size
        steps = source.steps_per_epoch(bs)
        if steps == 0:
            raise ValueError(f"batch_size {bs} exceeds dataset rows "
                             f"{source.num_rows}")
        w = max(1, min(int(SingleTrainer.stream_window), steps))
        n_windows = steps // w

        variables = self.model.init(self.seed)
        specs = spmd.infer_param_specs(variables["params"], mesh)
        variables = {"params": spmd.place(variables["params"], mesh, specs),
                     "state": spmd.replicate(variables["state"], mesh)}
        self.sharding_report = spmd.sharding_report(variables["params"])
        opt_state = optimizer.init(variables["params"])
        rng = spmd.put(jax.random.PRNGKey(self.seed + 1),
                       jax.sharding.NamedSharding(
                           mesh, jax.sharding.PartitionSpec()))
        ckpt = self._ckpt_manager()
        opt_shardings = jax.tree_util.tree_map(lambda x: x.sharding,
                                               opt_state)
        (variables, opt_state, rng), start_epoch = self._maybe_restore(
            ckpt, (variables, opt_state, rng))
        if start_epoch:  # restored host arrays: re-apply GSPMD placement
            variables = {
                "params": spmd.place(variables["params"], mesh, specs),
                "state": spmd.replicate(variables["state"], mesh)}
            opt_state = jax.tree_util.tree_map(
                spmd.put, opt_state, opt_shardings)
            rng = spmd.put(rng, jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()))

        bsh = spmd.batch_sharding(mesh, dp, batch_dim=1)  # (w, batch, ...)
        cols = [self.features_col, self.label_col]
        samples = n_windows * w * bs
        pipe = _EpochPipeline(self, samples)
        for epoch in range(start_epoch, self.num_epoch):
            seed = (self.seed + 1000 + epoch) if shuffle else None
            it = window_batches(source.batches(cols, bs, seed=seed), w)
            losses = []
            try:
                for _ in range(n_windows):
                    wx, wy = next(it)
                    variables, opt_state, rng, l = run(
                        variables, opt_state, rng,
                        spmd.put(wx, bsh), spmd.put(wy, bsh))
                    losses.append(l)
            finally:
                it.close()
            pipe.push(epoch, jnp.concatenate(losses))
            if ckpt is not None:
                ckpt.save(epoch, (variables, opt_state, rng),
                          {"epoch": epoch})
        pipe.flush()
        return self._finish(variables)

    def _train(self, dataset: Dataset, shuffle: bool) -> Model:
        from .data.streaming import ShardedFileDataset
        from .parallel import spmd
        if isinstance(dataset, ShardedFileDataset):
            return self._train_stream(dataset, shuffle)
        if shuffle:
            dataset = dataset.shuffle(self.seed)
        run, optimizer, mesh, dp = self._window_run()

        ds = dataset.coalesce(1)
        stacked, steps = ds.stacked([self.features_col, self.label_col],
                                    self.batch_size)
        bsh = spmd.batch_sharding(mesh, dp, batch_dim=1)  # (steps, batch,...)
        xs = spmd.put(stacked[self.features_col][0], bsh)
        ys = spmd.put(stacked[self.label_col][0], bsh)

        variables = self.model.init(self.seed)
        specs = spmd.infer_param_specs(variables["params"], mesh)
        variables = {"params": spmd.place(variables["params"], mesh, specs),
                     "state": spmd.replicate(variables["state"], mesh)}
        self.sharding_report = spmd.sharding_report(variables["params"])
        opt_state = optimizer.init(variables["params"])
        rng = spmd.put(jax.random.PRNGKey(self.seed + 1),
                       jax.sharding.NamedSharding(
                           mesh, jax.sharding.PartitionSpec()))

        ckpt = self._ckpt_manager()
        # shardings of the freshly-initialized state, to re-apply on resume
        opt_shardings = jax.tree_util.tree_map(lambda x: x.sharding, opt_state)
        (variables, opt_state, rng), start_epoch = self._maybe_restore(
            ckpt, (variables, opt_state, rng))
        if start_epoch:  # restored host arrays: re-apply GSPMD placement
            variables = {
                "params": spmd.place(variables["params"], mesh, specs),
                "state": spmd.replicate(variables["state"], mesh)}
            opt_state = jax.tree_util.tree_map(
                spmd.put, opt_state, opt_shardings)
            rng = spmd.put(rng, jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()))
        # AOT-compile the window program (replaces the implicit jit-cache
        # call): one compile per (config, shapes), and the executable stays
        # inspectable — tests grep compiled_step.as_text() for the
        # dp all-reduce / mp collectives (VERDICT r3 weak #3).  Carry-out
        # shardings are pinned to carry-in so epoch N+1's inputs (epoch
        # N's outputs) always match the executable — XLA would otherwise
        # be free to re-shard outputs (e.g. a bias to P('mp')) and the
        # strict AOT call would reject them on the next epoch.
        akey = (self._config_key(), xs.shape, str(xs.dtype),
                ys.shape, str(ys.dtype))
        cached = getattr(self, "_aot_cache", None)
        if cached is None or cached[0] != akey:
            carry_sh = jax.tree_util.tree_map(
                lambda a: a.sharding, (variables, opt_state, rng))
            out_sh = (*carry_sh, mesh_lib.replicated(mesh))  # losses
            pinned = jax.jit(run, donate_argnums=(0, 1, 2),
                             out_shardings=out_sh)
            # retrace sentinel for the AOT seam (ISSUE 6): the explicit
            # compile is the entry point here, so feed the sentinel the
            # data shapes directly — a second compile under the same
            # config is a shape-change retrace, counted like the
            # implicit-jit paths
            sentinel = self._sentinels.get(("aot", self._config_key()))
            if sentinel is None:
                sentinel = self._sentinels[("aot", self._config_key())] = \
                    obs_profile.RetraceSentinel(
                        f"{type(self).__name__}.aot",
                        registry=self._obs_registry, sink=self.metrics)
            state = sentinel.observe((xs, ys))
            # explicit AOT compile: the one place compile time is exactly
            # measurable rather than inferred from a cold first step
            with self.tracer.span("aot_compile",
                                  trainer=type(self).__name__,
                                  **({"retrace": True}
                                     if state == "retrace" else {})
                                  ) as record:
                self._aot_cache = (akey,
                                   pinned.lower(variables, opt_state, rng,
                                                xs, ys).compile())
                record.update(
                    obs_profile.program_memory(self._aot_cache[1]))
        compiled = self.compiled_step = self._aot_cache[1]
        samples = int(xs.shape[0]) * self.batch_size
        pipe = _EpochPipeline(self, samples)
        for epoch in range(start_epoch, self.num_epoch):
            variables, opt_state, rng, losses = self._profiled_run(
                compiled, epoch, variables, opt_state, rng, xs, ys)
            pipe.push(epoch, losses)
            if ckpt is not None:  # note: saving implies a per-epoch sync
                ckpt.save(epoch, (variables, opt_state, rng), {"epoch": epoch})
        pipe.flush()
        return self._finish(variables)


class _PipelinedSequential:
    """Forward proxy splitting a Sequential into pre → S pipeline stages →
    post, with the stage segment running GPipe over the ``pp`` mesh axis
    (``parallel.pipeline.pipeline_apply_sharded``).  Quacks enough like a
    Model for ``make_local_step`` (``.layer.apply``); params/state arrive
    regrouped as ``{"pre": [...], "stages": <stacked>, "post": [...]}``.

    Stages run ``train=False`` and rng-free inside the schedule (the
    GPipe scan cannot thread per-layer rng; transformer blocks —
    LayerNorm/attention/Dense — behave identically either way, and
    ``PipelineTrainer`` refuses stage segments with mutable state)."""

    def __init__(self, pre, stage_layers, post, mesh, num_microbatches,
                 stage_state_template, axis="pp", dp_axis=None):
        self.pre = pre
        self.stage_layers = stage_layers
        self.post = post
        self.pp_mesh = mesh
        self.num_microbatches = int(num_microbatches)
        #: per-stage-layer state trees (leafless — enforced by the
        #: trainer) with the layers' expected nesting (e.g. Residual's
        #: {"inner": {}}), threaded through stage applies unchanged
        self.stage_state_template = stage_state_template
        self.axis = axis
        self.dp_axis = dp_axis
        self.layer = self  # make_local_step calls model.layer.apply

    def _run(self, layers, params, state, x, train, rng):
        new_state = []
        for i, lyr in enumerate(layers):
            sub = None
            if rng is not None:
                rng, sub = jax.random.split(rng)
            x, s = lyr.apply(params[i], state[i], x, train=train, rng=sub)
            new_state.append(s)
        return x, new_state

    def apply(self, params, state, x, *, train=False, rng=None):
        from .parallel.pipeline import pipeline_apply_sharded
        r1 = r2 = None
        if rng is not None:
            r1, r2 = jax.random.split(rng)
        h, pre_state = self._run(self.pre, params["pre"], state["pre"], x,
                                 train, r1)
        tmpl = self.stage_state_template

        def stage_fn(sp, t):
            for j, lyr in enumerate(self.stage_layers):
                t, _ = lyr.apply(sp[j], tmpl[j], t, train=False, rng=None)
            return t

        h = pipeline_apply_sharded(
            self.pp_mesh, stage_fn, params["stages"], h,
            num_microbatches=self.num_microbatches, axis=self.axis,
            dp_axis=self.dp_axis)
        y, post_state = self._run(self.post, params["post"], state["post"],
                                  h, train, r2)
        return y, {"pre": pre_state, "stages": state["stages"],
                   "post": post_state}


class PipelineTrainer(Trainer):
    """Pipeline-parallel trainer (GPipe) — pp as a first-class trainer
    strategy, like mp on ``SpmdTrainer`` (VERDICT r3 missing #2; no
    reference equivalent — SURVEY.md §2 lists data parallelism as the
    reference's only strategy).

    The model's homogeneous block segment (auto-detected:
    ``parallel.pipeline.find_stage_segment``; e.g. ``zoo.gpt_lm``'s
    repeated transformer blocks) is laid out one-group-per-device along
    the ``pp`` mesh axis; embedding/head layers before/after the segment
    run replicated.  M microbatches flow through the schedule inside ONE
    jit train step, composing with dp via ``mesh_shape={"pp": S,
    "dp": D}`` (each dp replica pipelines its batch slice; XLA inserts
    the grad all-reduce).

    Gradient math is EXACT vs sequential training (GPipe reorders
    microbatch compute, it does not approximate), so the loss trajectory
    matches ``SingleTrainer`` on the same data/seed.
    """

    def __init__(self, keras_model: Model, worker_optimizer="sgd",
                 loss="categorical_crossentropy",
                 mesh_shape: Optional[dict] = None,
                 num_microbatches: Optional[int] = None, **kw):
        super().__init__(keras_model, worker_optimizer, loss, **kw)
        self.mesh_shape = mesh_shape or {"pp": len(jax.devices())}
        if "pp" not in self.mesh_shape:
            raise ValueError(f"mesh_shape needs a 'pp' axis, got "
                             f"{self.mesh_shape}")
        self.num_microbatches = num_microbatches

    def _split_model(self, mesh):
        """Regroup the Sequential's variables into pre/stages/post and
        build the pipelined forward proxy."""
        from .parallel.pipeline import find_stage_segment, stack_stage_params
        layer = self.model.layer
        if not isinstance(layer, Sequential):
            raise ValueError("PipelineTrainer needs a Sequential model "
                             f"(got {type(layer).__name__})")
        S = mesh.shape["pp"]
        a, g = find_stage_segment(layer.layers, S,
                                  input_shape=self.model.input_shape)
        variables = self.model.init(self.seed)
        params, state = variables["params"], variables["state"]
        span = S * g
        stage_state = state[a:a + span]
        if jax.tree_util.tree_leaves(stage_state):
            raise ValueError(
                "pipeline stages must be stateless (the GPipe scan cannot "
                "thread per-stage mutable state); the detected segment "
                f"[{a}:{a + span}] carries state — train this model with "
                "SpmdTrainer or the dp trainers instead")
        rng_layers = [type(sub).__name__
                      for lyr in layer.layers[a:a + g]
                      for sub in lyr.iter_layers() if sub.rng_in_train]
        if rng_layers:
            raise ValueError(
                f"pipeline stages contain rng-consuming layers "
                f"{rng_layers} (Dropout): the GPipe schedule cannot thread "
                f"per-layer rng, and running them eval-mode would silently "
                f"train different math than SingleTrainer — remove them "
                f"from the repeated blocks or train with SpmdTrainer")
        stacked = stack_stage_params(
            [params[a + i * g:a + (i + 1) * g] for i in range(S)])
        grouped = {
            "params": {"pre": params[:a], "stages": stacked,
                       "post": params[a + span:]},
            "state": {"pre": state[:a], "stages": [],
                      "post": state[a + span:]},
        }
        #: leafless per-layer state structure of one stage group, for the
        #: stage applies and for rebuilding the flat variables at collect
        self._stage_state_template = stage_state[:g]
        self._stage_state_full = stage_state
        M = self.num_microbatches or S
        dp_axis = "dp" if "dp" in self.mesh_shape else None
        proxy = _PipelinedSequential(layer.layers[:a], layer.layers[a:a + g],
                                     layer.layers[a + span:], mesh, M,
                                     self._stage_state_template,
                                     dp_axis=dp_axis)
        return proxy, grouped, (a, g, S)

    def _config_key(self) -> tuple:
        return super()._config_key() + (
            tuple(sorted(self.mesh_shape.items())), self.num_microbatches)

    def _train(self, dataset: Dataset, shuffle: bool) -> Model:
        from .parallel import spmd
        if shuffle:
            dataset = dataset.shuffle(self.seed)

        axes, sizes = zip(*self.mesh_shape.items())
        mesh = mesh_lib.make_mesh(axis_names=axes, shape=sizes)
        proxy, variables, (a, g, S) = self._split_model(mesh)

        key = self._config_key()
        cached = getattr(self, "_run_cache", None)
        if cached is None or cached[0] != key:
            loss_fn, optimizer = self._resolve()
            run = make_window_fn(proxy, loss_fn, optimizer,
                                 compute_dtype=self.compute_dtype,
                                 remat=self.remat,
                                 aux_weight=self.aux_weight)
            self._run_cache = (key, run, optimizer)
        run, optimizer = self._run_cache[1:]
        run = self._instrumented(run)

        ds = dataset.coalesce(1)
        stacked_data, steps = ds.stacked([self.features_col, self.label_col],
                                         self.batch_size)
        if "dp" in self.mesh_shape:
            bsh = spmd.batch_sharding(mesh, "dp", batch_dim=1)
        else:
            bsh = jax.sharding.NamedSharding(mesh,
                                             jax.sharding.PartitionSpec())
        xs = spmd.put(stacked_data[self.features_col][0], bsh)
        ys = spmd.put(stacked_data[self.label_col][0], bsh)

        # placement: stage stacks sharded one-stage-per-device over pp;
        # pre/post replicated
        pp_sh = jax.sharding.NamedSharding(mesh,
                                           jax.sharding.PartitionSpec("pp"))
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        place = jax.tree_util.tree_map
        variables = {
            "params": {"pre": place(lambda x: spmd.put(x, rep),
                                    variables["params"]["pre"]),
                       "stages": place(lambda x: spmd.put(x, pp_sh),
                                       variables["params"]["stages"]),
                       "post": place(lambda x: spmd.put(x, rep),
                                     variables["params"]["post"])},
            "state": variables["state"],
        }
        opt_state = optimizer.init(variables["params"])
        rng = spmd.put(jax.random.PRNGKey(self.seed + 1), rep)

        ckpt = self._ckpt_manager()
        # shardings of the fresh opt state (stage subtrees inherit the pp
        # placement from the params), to re-apply exactly on resume — a
        # replicated re-placement would blow per-device memory S× and
        # force a second resharding compile
        opt_shardings = place(lambda x: x.sharding, opt_state)
        (variables, opt_state, rng), start_epoch = self._maybe_restore(
            ckpt, (variables, opt_state, rng))
        if start_epoch:  # restored host arrays: re-apply placement
            variables = {
                "params": {"pre": place(lambda x: spmd.put(x, rep),
                                        variables["params"]["pre"]),
                           "stages": place(
                               lambda x: spmd.put(x, pp_sh),
                               variables["params"]["stages"]),
                           "post": place(lambda x: spmd.put(x, rep),
                                         variables["params"]["post"])},
                "state": variables["state"],
            }
            # mesh-spanning shardings (stage moments inherit P('pp') via
            # zeros_like) re-apply as captured; scalar leaves (optax step
            # counts) were single-device uncommitted on the fresh path —
            # commit them replicated so no mixed-device-set conflict
            opt_state = place(
                lambda x, sh: spmd.put(
                    x, sh if len(sh.device_set) > 1 else rep),
                opt_state, opt_shardings)
            rng = spmd.put(rng, rep)

        samples = int(xs.shape[0]) * self.batch_size
        pipe = _EpochPipeline(self, samples)
        for epoch in range(start_epoch, self.num_epoch):
            variables, opt_state, rng, losses = self._profiled_run(
                run, epoch, variables, opt_state, rng, xs, ys)
            pipe.push(epoch, losses)
            if ckpt is not None:  # note: saving implies a per-epoch sync
                ckpt.save(epoch, (variables, opt_state, rng), {"epoch": epoch})
        pipe.flush()
        return self._collect_pipeline(variables, a, g, S)

    def _collect_pipeline(self, variables, a, g, S) -> Model:
        """Regroup trained pre/stages/post back into the Sequential's flat
        per-layer params list."""
        host = jax.tree_util.tree_map(_to_host, variables)
        pre = host["params"]["pre"]
        stacked = host["params"]["stages"]
        post = host["params"]["post"]
        stages_flat = []
        for i in range(S):
            group = jax.tree_util.tree_map(lambda l: l[i], stacked)
            stages_flat.extend(group)
        params = list(pre) + stages_flat + list(post)
        state = list(host["state"]["pre"]) + list(self._stage_state_full) \
            + list(host["state"]["post"])
        self.trained_variables = {"params": params, "state": state}
        self.model.variables = self.trained_variables
        return self.model


class AsynchronousDistributedTrainer(DistributedTrainer):
    """Base for the asynchronous algorithm family (reference
    ``AsynchronousDistributedTrainer``).  In sync mode these run their
    synchronous limit; ``mode='async'`` gives faithful staleness semantics
    via the host PS."""


class DOWNPOUR(AsynchronousDistributedTrainer):
    """DOWNPOUR SGD (Dean et al. 2012; reference ``DOWNPOUR`` trainer)."""

    _default_window = 5
    _async_mode = "pull_commit"

    def _sync_algorithm(self):
        return DownpourSync()

    def _ps_factory(self):
        from .ps.servers import DeltaParameterServer
        return DeltaParameterServer


class ADAG(AsynchronousDistributedTrainer):
    """ADAG — asynchronous distributed adaptive gradients (reference
    ``ADAG`` trainer; the upstream README's recommended algorithm).  The
    synchronous limit is allreduce-mean windowed SGD: the flagship TPU
    configuration."""

    _default_window = 12
    _async_mode = "pull_commit"

    def _sync_algorithm(self):
        return AdagSync()

    def _ps_factory(self):
        from .ps.servers import ADAGParameterServer
        return ADAGParameterServer


class DynSGD(AsynchronousDistributedTrainer):
    """DynSGD — staleness-aware dynamic SGD (reference ``DynSGD`` trainer +
    ``DynSGDParameterServer``): commits scaled by 1/(staleness+1)."""

    _default_window = 5
    _async_mode = "staleness"

    def _sync_algorithm(self):
        return DynSgdSync()

    def _ps_factory(self):
        from .ps.servers import DynSGDParameterServer
        return DynSGDParameterServer


class AEASGD(AsynchronousDistributedTrainer):
    """Asynchronous elastic averaging SGD (Zhang et al. 2015; reference
    ``AEASGD`` trainer).  ``rho`` is the elastic force coefficient; the
    elastic alpha is ``rho * learning_rate`` as in the reference."""

    _default_window = 32
    _async_mode = "elastic"

    def __init__(self, keras_model, worker_optimizer="sgd",
                 loss="categorical_crossentropy", num_workers: int = 2,
                 rho: float = 5.0, learning_rate: float = 0.01, **kw):
        super().__init__(keras_model, worker_optimizer, loss, num_workers,
                         learning_rate=learning_rate, **kw)
        self.rho = float(rho)

    @property
    def alpha(self) -> float:
        return self.rho * self.learning_rate

    def _sync_algorithm(self):
        return EasgdSync(self.alpha)

    def _ps_factory(self):
        from .ps.servers import DeltaParameterServer
        return DeltaParameterServer


class EAMSGD(AEASGD):
    """Elastic averaging with (Nesterov) momentum (reference ``EAMSGD``):
    identical elastic exchange, Nesterov momentum in the local optimizer."""

    def __init__(self, keras_model, worker_optimizer="sgd",
                 loss="categorical_crossentropy", num_workers: int = 2,
                 rho: float = 5.0, learning_rate: float = 0.01,
                 momentum: float = 0.9, **kw):
        if not (worker_optimizer == "sgd" or worker_optimizer is None):
            raise ValueError(
                "EAMSGD defines its own local optimizer (Nesterov-momentum "
                "SGD, per the algorithm); worker_optimizer must be left as "
                f"'sgd', got {worker_optimizer!r}")
        super().__init__(keras_model, "sgd", loss, num_workers,
                         rho=rho, learning_rate=learning_rate, **kw)
        self.momentum = float(momentum)

    def _resolve(self):
        loss_fn, _ = super()._resolve()
        optimizer = optax.sgd(self.learning_rate, momentum=self.momentum,
                              nesterov=True)
        return loss_fn, optimizer
