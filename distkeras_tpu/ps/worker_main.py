"""OS-process async worker: ``python -m distkeras_tpu.ps.worker_main SPEC``.

The reference's workers are separate OS processes on separate machines
(Spark executor tasks shipped via ``rdd.mapPartitionsWithIndex`` — SURVEY.md
§3.1 boundary #1).  This module is that process: it rebuilds the model from
a spec file, loads its partition, connects to the parameter server over TCP
(boundary #2) and runs the epochs × windows pull/commit loop, then writes
its loss history to the output file.

The spec is a msgpack tree (``utils.serde``):

    {"model_blob": <serialize_model bytes>,
     "worker_optimizer": str, "loss": str, "learning_rate": float,
     "compute_dtype": str|None, "mode": "pull_commit"|"staleness"|"elastic",
     "comm_codec": str (``ps.codecs`` spec, default "none"),
     "comm_down": str (DOWN pull-compression spec — "none"/"int8"/"bf16"/
     "topk<frac>"/"adaptive", default "none"; ISSUE 12),
     "ps_shm": bool (offer the same-host shared-memory transport in the
     hello — co-located workers skip TCP; default False),
     "pull_overlap": bool (dispatch-ahead pulls — issue window k+1's
     pull while window k's device step runs, hiding the center transfer
     behind compute; default False, ISSUE 15),
     "alpha": float, "worker_id": int, "host": str, "port": int,
     "num_epoch": int, "seed": int, "data_npz": path, "out_npz": path,
     "metrics_jsonl": path (optional — this process's own telemetry
     stream: heartbeats + ``ps.commit``/``ps.pull`` spans under trace id
     ``w<worker_id>``; the runner folds it back into the trainer's sink
     so ``obsview --export-trace`` links BOTH halves of every wire span,
     ISSUE 6),
     "telemetry_s": float|None (push-telemetry cadence — ship registry
     ``snapshot_delta`` frames to the PS aggregator every that many
     seconds over the existing connection; default None = off,
     ISSUE 20)}

Used by ``ps.runner.run_async_training`` when the trainer asks for
``async_workers="processes"``; also runnable by hand for manual clusters
(one spec per host, all pointing at the same PS address).
"""

from __future__ import annotations

import sys
import traceback

import numpy as np


def run_spec(spec_path: str) -> None:
    # the platform is whatever JAX_PLATFORMS says in this process's
    # environment: ``ps.runner._worker_env`` sets it for spawned workers
    # (the CPU unless DKTPU_WORKER_PLATFORM names another), a hand-run
    # cluster sets it per host
    from ..utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from ..parallel.sync import make_window_fn
    from ..trainers import Trainer
    from ..utils import serde
    from .runner import _WORKER_CLASSES

    with open(spec_path, "rb") as f:
        spec = serde.tree_from_bytes(f.read())

    model, center = serde.deserialize_model(spec["model_blob"])
    # borrow the Trainer's loss/optimizer resolution (probs-variant
    # detection included) so process workers train the same math as threads
    shim = Trainer(model, spec["worker_optimizer"], spec["loss"],
                   learning_rate=spec["learning_rate"],
                   compute_dtype=spec.get("compute_dtype"),
                   remat=bool(spec.get("remat", False)),
                   aux_weight=float(spec.get("aux_weight", 0.0)))
    loss_fn, optimizer = shim._resolve()
    window_fn = make_window_fn(model, loss_fn, optimizer,
                               compute_dtype=shim.compute_dtype,
                               remat=shim.remat,
                               aux_weight=shim.aux_weight)

    import jax
    worker_cls = _WORKER_CLASSES[spec["mode"]]
    kw = {"alpha": spec["alpha"]} if spec["mode"] == "elastic" else {}
    # this process's own telemetry stream (ISSUE 6): the worker's tracer
    # pins trace id ``w<worker_id>`` on its thread, so the commit/pull
    # spans recorded HERE carry the same identity the server's adopted
    # apply spans reference in the parent's stream — the runner merges
    # the two halves after join
    metrics = None
    if spec.get("metrics_jsonl"):
        from ..utils.metrics import MetricsLogger
        metrics = MetricsLogger(spec["metrics_jsonl"])
    # a LIST of ports is a shard fleet (ISSUE 10): the worker builds a
    # ShardedPSClient and fans its windows across every shard
    port = spec["port"]
    port = [int(p) for p in port] if isinstance(port, (list, tuple)) \
        else int(port)
    worker = worker_cls(
        int(spec["worker_id"]), window_fn, center,
        optimizer.init(center["params"]),
        jax.random.PRNGKey(int(spec["seed"])),
        spec["host"], port, int(spec["num_epoch"]),
        start_window=int(spec.get("start_window", 0)),
        comm_codec=spec.get("comm_codec", "none"), metrics=metrics,
        comm_down=spec.get("comm_down", "none"),
        shm=bool(spec.get("ps_shm", False)),
        pull_overlap=bool(spec.get("pull_overlap", False)),
        profile_memory=bool(spec.get("profile_memory", True)),
        generation=int(spec.get("gen", 0)),
        telemetry_s=spec.get("telemetry_s"), **kw)
    if "stream" in spec:
        # disk-streaming partition: this process reads ITS shards straight
        # from the (shared) dataset directory — nothing was staged for it.
        # ``data_worker`` decouples the partition index from the PS
        # identity (an elastic-joined id beyond the configured fleet
        # shares the partition ring — ISSUE 9)
        from ..data.streaming import ShardedFileDataset, worker_window_factory
        s = spec["stream"]
        factory = worker_window_factory(
            ShardedFileDataset(s["dir"]), list(s["cols"]),
            int(s["batch_size"]),
            int(spec.get("data_worker", spec["worker_id"])),
            int(s["num_workers"]), int(s["window"]), int(s["base_seed"]),
            bool(s["shuffle"]))
        worker.set_stream(factory, int(s["n_windows"]))
    else:
        with np.load(spec["data_npz"]) as d:
            worker.set_data(d["xs"], d["ys"])
    worker.run()  # synchronously in THIS process (it is the worker process)
    # write the complete epochs this attempt produced BEFORE surfacing any
    # failure: the runner merges them with the retry's epochs, so a crash
    # mid-epoch-1 doesn't lose epoch 0 (thread-placement parity)
    np.savez(spec["out_npz"],
             **{f"epoch_{e}": l for e, l in worker.epoch_losses.items()})
    if metrics is not None:
        metrics.close()
    if worker.error is not None:
        raise worker.error


def main(argv=None) -> int:
    from ..obs import emit
    argv = argv if argv is not None else sys.argv[1:]
    if len(argv) != 1:
        emit("usage: python -m distkeras_tpu.ps.worker_main SPEC", err=True)
        return 2
    try:
        run_spec(argv[0])
    except Exception:
        traceback.print_exc()
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
