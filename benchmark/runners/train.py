"""Runner for training cells: one trainer, one job, timed by epochs.

The trainer class, its arguments, the batch and the steps of an epoch are
data in the traffic file; model, optimizer, loss, data and dtype are data
in the configuration's file.  So ``SingleTrainer`` and the distributed
trainers, and any model the zoo builds, run through this one file.

How a run is timed.  ``train()`` with ``num_epoch = 2`` compiles (or
finds the program in the cache) and gives the length ``e`` of a warm
epoch: set-up ends there.  Then, on the same trainer object, so that the
compiled program and the retrace sentinel stay, ``train()`` again with
``num_epoch = 1 + max(2, ceil(seconds / e))``.  The trainer logs an
``epoch`` record at the moment an epoch's losses have been read back
from the device, which is a fence; ``StampedLog`` puts the benchmark's
own clock on that moment.  The call's first epoch holds re-initialisation
and staging and is dropped: the window runs from the first epoch's
readback to the last one's, and the rate is the rows of the epochs in it
over its length, per chip.
"""

import importlib
import math
import time

import numpy as np

from common import SEED_MOD, resolve, trace_options
from distkeras_tpu.utils.metrics import MetricsLogger

#: epochs of the traced call (the reduction takes all of them: the device
#: is never idle between them)
TRACE_EPOCHS = 3


class StampedLog(MetricsLogger):
    """The trainer's metrics sink, with the benchmark's clock on each
    record as it is logged."""

    def log(self, event, **fields):
        rec = super().log(event, **fields)
        rec["bench_t"] = time.perf_counter()
        return rec


def make_dataset(data: dict, rows: int, seed: int):
    train = resolve(data["loader"])(n_train=rows, seed=seed,
                                    **data.get("args", {}))[0]
    if data.get("one_hot"):
        from distkeras_tpu.data.transformers import OneHotTransformer
        train = OneHotTransformer(data["one_hot"], "label",
                                  data["label_col"]).transform(train)
    return train


def timed_call(trainer, ds, epochs: int, name: str) -> dict:
    """One ``train()`` call under the benchmark's fenced clock (the call
    returns only after the trained variables are on the host)."""
    import jax
    first = len(trainer.metrics.records)
    trainer.num_epoch = epochs
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation(f"bench:{name}"):
        model = trainer.train(ds)
    fenced = time.perf_counter() - t
    records = list(trainer.metrics.records)[first:]
    return {"model": model, "fenced_s": fenced,
            "epochs": [r for r in records if r["event"] == "epoch"],
            "compiles": [r for r in records if r["event"] == "span"
                         and r["name"] == "jit_compile"]}


def window_of(call: dict, rows_per_epoch: int, chips: int) -> dict:
    """The measured window of a timed call: first epoch dropped."""
    stamps = [r["bench_t"] for r in call["epochs"]]
    counted = call["epochs"][1:]
    seconds = stamps[-1] - stamps[0]
    return {"seconds": seconds, "epochs": len(counted),
            "rate_per_chip": rows_per_epoch * len(counted) / seconds / chips,
            "program_seconds": sum(r["epoch_seconds"] for r in counted),
            # what a train() call costs beside its epochs: initialisation
            # and staging before the first, the variables' way back to the
            # host after the last
            "call_overhead_s": call["fenced_s"]
            - len(call["epochs"]) * seconds / len(counted)}


def check_reference(config: dict, model, ds, not_correct: list) -> None:
    """``predict_fn`` over the trained variables against the plain
    reference, outside every window."""
    import jax
    import jax.numpy as jnp
    name = config.get("reference")
    if not name:
        return
    reference = importlib.import_module(f"reference.{name}")
    tol = config["reference_tolerance"]
    x = np.asarray(ds["features"][:2])
    with jax.default_matmul_precision("highest"):
        got = jax.jit(model.predict_fn())(model.variables, x)
    want = reference.forward(model.variables, x, config["sizes"])
    if got.shape != want.shape:
        not_correct.append(f"logits {got.shape} against the reference's "
                           f"{want.shape}")
        return
    # compared on the device: the logits are 0.4 GB a side
    miss = jnp.abs(got - want) - (tol["atol"] + tol["rtol"] * jnp.abs(want))
    if not float(jnp.max(miss)) <= 0.0:  # not-form: NaN fails too
        not_correct.append(
            f"logits differ from reference/{name}.py by "
            f"{float(jnp.max(jnp.abs(got - want))):.3g} (rtol = "
            f"{tol['rtol']}, atol = {tol['atol']})")


def run(ctx: dict) -> dict:
    import jax

    import distkeras_tpu as dk
    from distkeras_tpu.obs import Registry
    config, job, chips = ctx["config"], ctx["traffic"], ctx["chips"]
    marks = {"imported": time.time()}
    seed = ctx["seed"] % SEED_MOD
    train_cfg = config["train"]
    workers = int(job.get("trainer_args", {}).get("num_workers", 1))
    rows_per_epoch = job["batch"] * job["steps_per_epoch"] * workers

    ds = make_dataset(train_cfg["data"], rows_per_epoch, seed)
    model = resolve(config["builder"])(**config["sizes"],
                                       **config.get("builder_args", {}))
    trainer = getattr(dk, job["trainer"])(
        model, train_cfg["optimizer"], train_cfg["loss"],
        label_col=train_cfg["data"]["label_col"], num_epoch=2,
        batch_size=job["batch"], learning_rate=train_cfg["learning_rate"],
        seed=seed, compute_dtype=train_cfg["compute_dtype"],
        remat=train_cfg.get("remat", False), metrics=StampedLog(None),
        **job.get("trainer_args", {}))
    registry = Registry()  # this trainer's own, with the sentinel's counters
    compiles = registry.counter("jit.compiles")
    retraces = registry.counter("jit.retraces")
    trainer.tracer.registry = registry
    marks["data_and_trainer_built"] = time.time()

    # -- set-up: compile or load, and learn how long a warm epoch is ------
    setup = timed_call(trainer, ds, 2, "setup_call")
    epoch_s = setup["epochs"][-1]["epoch_seconds"]
    compiles_before = compiles.value
    marks["setup_call_done"] = time.time()
    setup_s = marks["setup_call_done"] - ctx["t0"]

    # -- the window ---------------------------------------------------------
    n = 1 + max(2, math.ceil(ctx["seconds"] / epoch_s))
    call = timed_call(trainer, ds, n, "window_call")
    window = window_of(call, rows_per_epoch, chips)
    marks["window_call_done"] = time.time()
    in_window = {"jit.retraces": retraces.value,
                 "jit.compiles": compiles.value - compiles_before}

    not_correct = []
    losses = [r["mean_loss"] for r in call["epochs"]]
    if len(losses) != n or not np.all(np.isfinite(losses)):
        not_correct.append(f"{len(losses)} epochs of {n}, losses {losses}")
    elif not losses[-1] < losses[0]:
        not_correct.append(f"the loss did not fall: {losses}")
    if in_window["jit.retraces"] or in_window["jit.compiles"] \
            or call["compiles"]:
        not_correct.append(f"compiled inside the window: {in_window}, "
                           f"{len(call['compiles'])} jit_compile spans")
    logged = {round(r["samples_per_sec"] * r["epoch_seconds"])
              for r in call["epochs"]}
    if logged != {rows_per_epoch}:
        not_correct.append(f"the trainer logged epochs of {logged} rows, "
                           f"the job has {rows_per_epoch}")
    if window["program_seconds"] > call["fenced_s"]:
        not_correct.append(
            f"the program's epochs sum to {window['program_seconds']} s, "
            f"more than the call's {call['fenced_s']} s")

    # -- a short traced call of the same program ----------------------------
    if ctx["trace"]:
        jax.profiler.start_trace(ctx["trace_dir"],
                                 profiler_options=trace_options())
        try:
            traced = timed_call(trainer, ds, TRACE_EPOCHS, "traced_call")
        finally:
            jax.profiler.stop_trace()
        if traced["compiles"]:
            not_correct.append("compiled inside the traced call")

    check_reference(config, call["model"], ds, not_correct)
    marks["checked"] = time.time()

    return {
        "marks": marks,
        "not_correct": not_correct,
        # steps of the window; one whose epoch's loss is not finite failed
        "attempted": window["epochs"] * job["steps_per_epoch"],
        "failed": job["steps_per_epoch"] * sum(
            not np.isfinite(x) for x in losses[1:]),
        "end_to_end": {"train_samples_per_s": window["rate_per_chip"],
                       "setup_s": setup_s},
        "sources": {
            "setup_compile_spans": setup["compiles"],
            "window": window, "in_window": in_window,
            "batch": job["batch"], "steps_per_epoch": job["steps_per_epoch"],
        },
    }
