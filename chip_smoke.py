"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` drives the main paths ONCE, through the entry
points a user calls, at the full width of a model the repo supports, in
one process on one TPU chip:

1. device   — what JAX sees, the compile-cache directory, the native lib
2. fence    — one bf16 matmul chain timed three ways (enqueue only,
              ``block_until_ready``, device->host readback): which fence
              is honest on this machine
3. train/resnet20 — ``SingleTrainer`` on ResNet-20, bf16, batch 1024
4. train/gpt_lm   — ``SingleTrainer`` on ``zoo.gpt_lm`` at GPT-2-small
              widths with the Pallas flash kernels (asserted present in
              the program), then flash vs dense logits on the chip
5. serve    — ``ServeServer(DecodeEngine(...).warmup())`` at the same
              widths, answering ``ServeClient`` requests, checked against
              ``generate_tokens``
6. async PS — ``DOWNPOUR(mode="async", async_workers="threads")``

``python chip_smoke.py --chips 4`` runs ONLY the cross-chip path and what
it is compared with: sync ``ADAG`` over a 4-device mesh against
``SingleTrainer`` on one chip (placement asserted: data shards, the
all-reduce, bytes in use on every chip), then async ``DOWNPOUR`` with one
worker per chip.

Every phase prints one JSON line; a rate in it is an observation of this
run, never a benchmark.  A phase that fails raises and the run exits
non-zero — nothing is caught and carried past, and nothing falls back to
the CPU: with no TPU the script exits non-zero before any phase and
prints no result.  The LAST line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

``--rehearse`` runs the same control flow at tiny sizes so it can be
tried on the CPU (``JAX_PLATFORMS=cpu``, Pallas in interpret mode); off
the chip it still exits non-zero and never prints ``"ok": true``.
"""

import argparse
import concurrent.futures
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

#: GPT-2 small (Radford et al. 2019): the widths of phases 4 and 5
GPT2_SMALL = dict(vocab_size=50257, dim=768, num_heads=12, num_blocks=12,
                  seq_len=1024)

SIZES = {
    "chip": dict(
        fence_n=8192, fence_chain=32,
        resnet_batch=1024, resnet_steps=32, resnet_epochs=3,
        gpt=GPT2_SMALL, gpt_batch=8, gpt_steps=4, gpt_epochs=3,
        serve_slots=4, serve_new=16, serve_prompts=(24, 150, 300),
        serve_shared=128, serve_tail=40,
        # a cached prefix is a whole seq_len row of float32 K/V in every
        # block: 75.5 MB at these widths, so the default 64 MB budget
        # evicts each entry as it is inserted (first chip run, PR 21)
        serve_prefix_mb=512,
        ps_batch=256, ps_window=4, ps_windows=4,
        adag_batch=256, adag_window=4, adag_windows=8, adag_epochs=2),
    "rehearse": dict(
        fence_n=256, fence_chain=4,
        resnet_batch=16, resnet_steps=4, resnet_epochs=3,
        gpt=dict(vocab_size=512, dim=32, num_heads=2, num_blocks=1,
                 seq_len=128),
        gpt_batch=4, gpt_steps=4, gpt_epochs=3,
        serve_slots=2, serve_new=6, serve_prompts=(5, 20, 40),
        serve_shared=32, serve_tail=7, serve_prefix_mb=64,
        ps_batch=8, ps_window=2, ps_windows=2,
        adag_batch=8, adag_window=2, adag_windows=2, adag_epochs=2),
}

#: the counting corpus uses this many distinct tokens of the model's
#: vocabulary, so a handful of steps already moves the loss
CORPUS_VOCAB = 512

#: flash vs dense logits, both at float32 HIGHEST matmul precision
FLASH_DENSE_RTOL = FLASH_DENSE_ATOL = 2e-3

#: a served token may differ from ``generate_tokens`` only where the
#: reference's own logits for the two tokens are this close (a numerical
#: tie: the two programs batch and pad differently, and the TPU rounds
#: float32 matmul inputs to bfloat16); a wrong cache row or position
#: gives a token whose logit is far below the top one
SERVE_TIE_TOL = 2e-2

#: the async center must have moved by more than float noise
CENTER_MOVED_MIN = 1e-6

#: 4 chips: ADAG's last-epoch loss must land within this factor of
#: SingleTrainer's on the same data (same samples per step: 4 x 256 vs
#: 1024; ADAG averages every ``window`` local steps, so not bit-equal)
ADAG_LOSS_BAND = 1.5


def emit(phase: str, seconds: float, **fields) -> None:
    # "passed", not "ok": only the LAST line of a run on the chip may say
    # "ok": true (a phase that did not pass raised and printed nothing)
    print(json.dumps({"phase": phase, "passed": True,
                      "seconds": round(seconds, 3), **fields}), flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def counter(snapshot: dict, name: str) -> float:
    return snapshot[name]["value"]


def images(rows: int, seed: int):
    """Seeded CIFAR-shaped rows with one-hot labels (the loader's
    class-template surrogate when no CIFAR archive is on disk)."""
    import distkeras_tpu as dk
    from distkeras_tpu.data.transformers import OneHotTransformer
    train, _, _ = dk.datasets.load_cifar10(n_train=rows, seed=seed)
    return OneHotTransformer(10, "label", "label_onehot").transform(train)


def epoch_records(trainer) -> list:
    return [r for r in trainer.metrics.records if r["event"] == "epoch"]


def scoped_registry(trainer):
    """A registry of this trainer's own, with the retrace sentinel's
    counters pre-created (0 is present, not missing)."""
    from distkeras_tpu.obs import Registry
    reg = Registry()
    reg.counter("jit.compiles")
    reg.counter("jit.retraces")
    trainer.tracer.registry = reg
    return reg


def check_training(trainer, reg, what: str) -> list:
    losses = [r["mean_loss"] for r in epoch_records(trainer)]
    check(all(np.isfinite(losses)), f"{what}: non-finite loss {losses}")
    check(losses[-1] < losses[0], f"{what}: loss did not fall: {losses}")
    check(reg.counter("jit.retraces").value == 0,
          f"{what}: jit.retraces = {reg.counter('jit.retraces').value}")
    return [round(float(l), 4) for l in losses]


def compile_seconds(trainer) -> float:
    return round(sum(r["seconds"] for r in trainer.metrics.records
                     if r["event"] == "span"
                     and r["name"] == "jit_compile"), 3)


# ---------------------------------------------------------------------------
# 1. device
# ---------------------------------------------------------------------------

def phase_device(cache_dir: str) -> dict:
    import jax
    import jaxlib

    from distkeras_tpu.utils import native
    t0 = time.perf_counter()
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    emit("device", time.perf_counter() - t0, **info, jax=jax.__version__,
         jaxlib=jaxlib.__version__, compile_cache_dir=cache_dir,
         native_available=native.available())
    return info


# ---------------------------------------------------------------------------
# 2. fence
# ---------------------------------------------------------------------------

def phase_fence(sz: dict, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    t0 = time.perf_counter()
    n, chain = sz["fence_n"], sz["fence_chain"]
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, n), jnp.bfloat16)

    @jax.jit
    def f(x):
        y = x
        for _ in range(chain):  # scaled so the chain neither dies nor blows up
            y = (y @ x) * jnp.bfloat16(1.0 / np.sqrt(n))
        return y

    np.asarray(f(x)[0, 0])  # compile + first run, drained
    enqueue, blocked, readback = [], [], []
    for _ in range(3):
        t = time.perf_counter()
        y = f(x)
        enqueue.append(time.perf_counter() - t)
        jax.block_until_ready(y)
        blocked.append(time.perf_counter() - t)
        t = time.perf_counter()
        v = float(f(x)[0, 0])
        readback.append(time.perf_counter() - t)
    check(np.isfinite(v), f"fence: matmul chain gave {v}")
    enq, blk, rb = (float(np.median(a)) for a in (enqueue, blocked, readback))
    flops = 2.0 * n ** 3 * chain
    emit("fence", time.perf_counter() - t0,
         matmul=f"{chain} x ({n}x{n} @ {n}x{n}) bf16",
         enqueue_s=enq, block_until_ready_s=blk, readback_s=rb,
         # block_until_ready is an honest fence iff it waits as long as
         # the readback, which cannot return before the value exists
         block_until_ready_waits=bool(blk > 0.5 * rb),
         observed_tflops_at_readback=round(flops / rb / 1e12, 1))


# ---------------------------------------------------------------------------
# 3. train/resnet20
# ---------------------------------------------------------------------------

def phase_resnet(sz: dict, seed: int, tpu: bool) -> None:
    import jax

    import distkeras_tpu as dk
    t0 = time.perf_counter()
    ds = images(sz["resnet_batch"] * sz["resnet_steps"], seed)
    trainer = dk.SingleTrainer(
        dk.zoo.resnet20(width=16), "sgd", "categorical_crossentropy",
        label_col="label_onehot", num_epoch=sz["resnet_epochs"],
        batch_size=sz["resnet_batch"], learning_rate=0.1, seed=seed,
        compute_dtype="bfloat16")
    reg = scoped_registry(trainer)
    model = trainer.train(ds)
    losses = check_training(trainer, reg, "train/resnet20")
    # the trainer hands the trained parameters back on the host; where
    # they LIVED is read off the device: a forward over them lands on
    # the chip, and the chip's peak bytes cover the staged epoch
    dev = jax.devices()[0]
    out = jax.jit(model.predict_fn())(model.variables,
                                      ds["features"][:8])
    check(out.devices() == {dev}, f"forward ran on {out.devices()}")
    check(np.all(np.isfinite(np.asarray(out))), "non-finite predictions")
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    if tpu:
        check(peak is not None and peak >= ds["features"].nbytes,
              f"device peak {peak} B never held the "
              f"{ds['features'].nbytes} B epoch")
    emit("train/resnet20", time.perf_counter() - t0, epoch_losses=losses,
         jit_retraces=0, compile_s=compile_seconds(trainer),
         device_peak_bytes=peak, forward_device=str(dev),
         observed_samples_per_sec=round(
             epoch_records(trainer)[-1]["samples_per_sec"]))


# ---------------------------------------------------------------------------
# 4. train/gpt_lm, flash
# ---------------------------------------------------------------------------

def phase_gpt(sz: dict, seed: int, tpu: bool) -> None:
    import jax
    import jax.numpy as jnp

    import distkeras_tpu as dk
    t0 = time.perf_counter()
    cfg = sz["gpt"]
    rows = sz["gpt_batch"] * sz["gpt_steps"]
    ds, _, _ = dk.datasets.load_lm_corpus(
        n_train=rows, seq_len=cfg["seq_len"],
        vocab_size=min(CORPUS_VOCAB, cfg["vocab_size"]), seed=seed)
    trainer = dk.SingleTrainer(
        dk.zoo.gpt_lm(**cfg, attention_impl="flash"), "adam",
        "sparse_categorical_crossentropy", num_epoch=sz["gpt_epochs"],
        batch_size=sz["gpt_batch"], learning_rate=1e-3, seed=seed,
        compute_dtype="bfloat16")
    reg = scoped_registry(trainer)
    model = trainer.train(ds)
    losses = check_training(trainer, reg, "train/gpt_lm")
    step_s = epoch_records(trainer)[-1]["epoch_seconds"] / sz["gpt_steps"]

    # the kernel is really there: the window program train() just ran,
    # lowered for this backend, carries the Mosaic custom call — interpret
    # mode or a dense path lowers to plain HLO and cannot stand in
    _, run, optimizer = trainer._run_cache
    variables = jax.eval_shape(lambda: model.init(seed))
    carry = (variables,
             jax.eval_shape(optimizer.init, variables["params"]),
             jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    xs = jax.ShapeDtypeStruct(
        (sz["gpt_steps"], sz["gpt_batch"], cfg["seq_len"]), jnp.int32)
    ys = xs  # next-token targets reach the device as int32 too
    kernel = "tpu_custom_call" in run.lower(*carry, xs, ys).as_text()
    if tpu:
        check(kernel, "train/gpt_lm: no Mosaic custom call in the train "
                      "program — the flash kernel did not lower for the TPU")

    # same trained weights through flash and dense attention, on the chip
    dense = dk.zoo.gpt_lm(**cfg, attention_impl="dense")
    x = np.asarray(ds["features"][:2])
    with jax.default_matmul_precision("highest"):
        yf = np.asarray(jax.jit(model.predict_fn())(model.variables, x))
        yd = np.asarray(jax.jit(dense.predict_fn())(model.variables, x))
    check(yf.shape == (2, cfg["seq_len"], cfg["vocab_size"]),
          f"logits shape {yf.shape}")
    check(np.all(np.isfinite(yf)), "non-finite flash logits")
    np.testing.assert_allclose(yf, yd, rtol=FLASH_DENSE_RTOL,
                               atol=FLASH_DENSE_ATOL)
    emit("train/gpt_lm", time.perf_counter() - t0, model=cfg,
         epoch_losses=losses, jit_retraces=0,
         compile_s=compile_seconds(trainer), mosaic_kernel_in_program=kernel,
         flash_vs_dense_max_abs_diff=float(np.max(np.abs(yf - yd))),
         tolerance={"rtol": FLASH_DENSE_RTOL, "atol": FLASH_DENSE_ATOL},
         observed_step_s=round(step_s, 4),
         observed_tokens_per_sec=round(
             sz["gpt_batch"] * cfg["seq_len"] / step_s))


# ---------------------------------------------------------------------------
# 5. serve
# ---------------------------------------------------------------------------

def served_equals_reference(model, variables, prompt, got, want) -> str:
    """"exact", or "tie@<i>" when the first differing token is a
    numerical tie in the reference's own logits (see SERVE_TIE_TOL);
    anything else fails the phase."""
    import jax
    got, want = np.asarray(got), np.asarray(want)
    check(got.shape == want.shape,
          f"served {got.shape} tokens, reference {want.shape}")
    diff = np.nonzero(got != want)[0]
    if diff.size == 0:
        return "exact"
    i = int(diff[0])
    t = int(model.input_shape[0])
    buf = np.zeros((1, t), np.int32)
    ctx = np.concatenate([prompt, want[:i]])
    buf[0, :ctx.size] = ctx
    logits = np.asarray(
        jax.jit(model.predict_fn())(variables, buf))[0, ctx.size - 1]
    gap = float(abs(logits[want[i]] - logits[got[i]]))
    check(gap <= SERVE_TIE_TOL,
          f"served token {i} is {got[i]}, generate_tokens gives "
          f"{want[i]}, and the reference logits differ by {gap:.4f} "
          f"(> {SERVE_TIE_TOL}): not a numerical tie")
    return f"tie@{i}"


def phase_serve(sz: dict, seed: int) -> None:
    import distkeras_tpu as dk
    t0 = time.perf_counter()
    cfg = sz["gpt"]
    model = dk.zoo.gpt_lm(**cfg, attention_impl="flash")
    variables = model.init(seed)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg["vocab_size"], size=n).astype(np.int32)
               for n in sz["serve_prompts"]]
    # the last request shares a block-aligned prefix with an earlier one
    shared = np.concatenate([
        prompts[-1][:sz["serve_shared"]],
        rng.integers(0, cfg["vocab_size"],
                     size=sz["serve_tail"]).astype(np.int32)])
    new = sz["serve_new"]

    t_w = time.perf_counter()
    engine = dk.DecodeEngine(model, variables, dk.ServeConfig(
        slots=sz["serve_slots"], max_new_tokens=new, prefix_cache=True,
        prefix_cache_mb=sz["serve_prefix_mb"])).warmup()
    warmup_s = time.perf_counter() - t_w
    server = dk.ServeServer(engine).start()

    def ask(prompt):
        with dk.ServeClient("127.0.0.1", server.port) as client:
            return client.generate(prompt, max_new_tokens=new)

    try:
        with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
            replies = list(pool.map(ask, prompts))  # joined mid-decode
        replies.append(ask(shared))
        with dk.ServeClient("127.0.0.1", server.port) as client:
            doc = client.stats()
    finally:
        server.stop()
    prompts.append(shared)

    # a crashed decode loop answers every request with a rejection and
    # leaves the engine draining: both fail here
    for n, r in zip(map(len, prompts), replies):
        check(r.get("ok"), f"prompt of {n} tokens was not answered: {r}")
    stats = doc["stats"]
    check(not doc["draining"], "the engine ended up draining")
    check(counter(stats, "serve.rejected") == 0, "requests were rejected")
    check(counter(stats, "serve.completed") == len(prompts),
          f"completed {counter(stats, 'serve.completed')} of "
          f"{len(prompts)}")
    check(counter(stats, "jit.retraces") == 0,
          f"jit.retraces = {counter(stats, 'jit.retraces')}")
    check(replies[-1].get("warm") is True
          and counter(stats, "serve.prefix.hits") >= 1,
          "the shared-prefix request did not join warm")

    # the offline decode on the same weights: one ragged batch
    lens = np.asarray([p.size for p in prompts], np.int32)
    padded = np.zeros((len(prompts), int(lens.max())), np.int32)
    for row, p in zip(padded, prompts):
        row[:p.size] = p
    ref = np.asarray(dk.generate_tokens(model, variables, padded, new,
                                        prompt_lengths=lens))
    agreement = [
        served_equals_reference(model, variables, p, r["tokens"],
                                ref[b, p.size:p.size + new])
        for b, (p, r) in enumerate(zip(prompts, replies))]
    emit("serve", time.perf_counter() - t0,
         model={**cfg, "dtype": "float32"},
         prompt_lengths=lens.tolist(), new_tokens=new,
         vs_generate_tokens=agreement, tie_tolerance=SERVE_TIE_TOL,
         completed=len(prompts), rejected=0, jit_retraces=0,
         jit_compiles=int(counter(stats, "jit.compiles")),
         prefix_hits=int(counter(stats, "serve.prefix.hits")),
         warmup_s=round(warmup_s, 3),
         observed_ttft_s=[round(r["ttft_s"], 4) for r in replies],
         observed_e2e_s=[round(r["e2e_s"], 4) for r in replies])


# ---------------------------------------------------------------------------
# 6. async parameter server
# ---------------------------------------------------------------------------

def phase_async(sz: dict, seed: int, workers: int, name: str) -> None:
    import jax

    import distkeras_tpu as dk
    t0 = time.perf_counter()
    windows = sz["ps_windows"]
    ds = images(workers * windows * sz["ps_window"] * sz["ps_batch"], seed)
    model = dk.zoo.resnet20()
    trainer = dk.DOWNPOUR(
        model, "sgd", "categorical_crossentropy", mode="async",
        async_workers="threads", num_workers=workers,
        communication_window=sz["ps_window"], batch_size=sz["ps_batch"],
        label_col="label_onehot", num_epoch=1, learning_rate=0.05,
        seed=seed, compute_dtype="bfloat16")
    trained = trainer.train(ds)
    commits = trainer.ps_stats["num_updates"]
    check(commits == workers * windows,
          f"the PS counted {commits} commits, expected {workers} workers "
          f"x {windows} windows")
    loss = epoch_records(trainer)[-1]["mean_loss"]
    check(np.isfinite(loss), f"{name}: loss {loss}")
    moved = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                for a, b in zip(
                    jax.tree_util.tree_leaves(trained.variables["params"]),
                    jax.tree_util.tree_leaves(model.init(seed)["params"])))
    check(moved > CENTER_MOVED_MIN, f"{name}: the center did not move")
    # each worker states the device its carry sits on (ps/workers.py)
    placed = {r["worker_id"]: r["device"] for r in trainer.metrics.records
              if r["event"] == "worker_platform"}
    check(len(placed) == workers, f"{name}: placement of {placed}")
    want = min(workers, len(jax.devices()))
    check(len(set(placed.values())) == want,
          f"{name}: {workers} workers on {sorted(set(placed.values()))}, "
          f"expected {want} distinct devices")
    emit(name, time.perf_counter() - t0, workers=workers,
         windows_per_worker=windows, ps_commits=commits,
         commits_by_worker=trainer.ps_stats["commits_by_worker"],
         mean_loss=round(float(loss), 4), center_max_abs_move=moved,
         worker_devices=placed)


# ---------------------------------------------------------------------------
# --chips 4: sync ADAG across the mesh vs SingleTrainer on one chip
# ---------------------------------------------------------------------------

def phase_adag(sz: dict, seed: int, tpu: bool) -> None:
    import jax

    import distkeras_tpu as dk
    from distkeras_tpu.parallel import mesh as mesh_lib
    from distkeras_tpu.parallel.sync import tmap
    t0 = time.perf_counter()
    P, w, bs = 4, sz["adag_window"], sz["adag_batch"]
    ds = images(P * sz["adag_windows"] * w * bs, seed)
    common = dict(label_col="label_onehot", num_epoch=sz["adag_epochs"],
                  learning_rate=0.1, seed=seed, compute_dtype="bfloat16")

    single = dk.SingleTrainer(dk.zoo.resnet20(), "sgd",
                              "categorical_crossentropy",
                              batch_size=P * bs, **common)
    sreg = scoped_registry(single)
    single.train(ds)
    s_losses = check_training(single, sreg, "train/single (1 chip)")
    emit("train/single (1 chip)", time.perf_counter() - t0,
         epoch_losses=s_losses, observed_samples_per_sec=round(
             epoch_records(single)[-1]["samples_per_sec"]))

    t1 = time.perf_counter()
    adag = dk.ADAG(dk.zoo.resnet20(), "sgd", "categorical_crossentropy",
                   num_workers=P, mode="sync", communication_window=w,
                   batch_size=bs, **common)
    areg = scoped_registry(adag)
    adag.train(ds)
    a_losses = check_training(adag, areg, "train/adag (4 chips)")
    lo, hi = s_losses[-1] / ADAG_LOSS_BAND, s_losses[-1] * ADAG_LOSS_BAND
    check(lo <= a_losses[-1] <= hi,
          f"ADAG's last-epoch loss {a_losses[-1]} is outside "
          f"[{lo:.4f}, {hi:.4f}] around SingleTrainer's {s_losses[-1]}")

    # placement, staged exactly as DistributedTrainer._train_sync stages it
    devices = jax.devices()
    engine, mesh, optimizer, programs = adag._engine_parts()
    xs, ys, _ = adag._stage_data(ds, w)
    xs, ys = mesh_lib.host_to_mesh(mesh, xs), mesh_lib.host_to_mesh(mesh, ys)
    shard_devices = {s.device for s in xs.addressable_shards}
    check(shard_devices == set(devices[:P]) and len(shard_devices) == P,
          f"staged shards sit on {shard_devices}")
    center = adag.model.init(seed)
    local = mesh_lib.host_to_mesh(mesh, tmap(
        lambda x: np.broadcast_to(np.asarray(x)[None], (P, *np.shape(x))),
        center))
    center = mesh_lib.broadcast_to_mesh(mesh, center)
    rngs = mesh_lib.host_to_mesh(
        mesh, jax.random.split(jax.random.PRNGKey(seed + 1), P))
    text = programs["epoch"].lower(
        center, local, jax.vmap(optimizer.init)(local["params"]), rngs,
        xs, ys).compile().as_text()
    check("all-reduce" in text, "no all-reduce in the ADAG epoch program")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    if tpu:
        check(all(b for b in in_use[:P]), f"bytes in use per chip: {in_use}")
    emit("train/adag (4 chips)", time.perf_counter() - t1,
         epoch_losses=a_losses, single_chip_last_loss=s_losses[-1],
         loss_band=ADAG_LOSS_BAND, jit_retraces=0,
         compile_s=compile_seconds(adag),
         shard_devices=sorted(map(str, shard_devices)),
         all_reduce_in_program=True, bytes_in_use=in_use,
         observed_samples_per_sec=round(
             epoch_records(adag)[-1]["samples_per_sec"]))


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the cross-chip path and its one-chip "
                         "comparison (needs four devices)")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, for a dry run off the chip; never "
                         "prints ok: true there")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from distkeras_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    tpu = jax.devices()[0].platform == "tpu"
    if not tpu and not args.rehearse:
        sys.stderr.write(
            f"chip_smoke: JAX found no TPU (devices: {jax.devices()}); "
            f"nothing was run.  --rehearse tries the control flow at tiny "
            f"sizes off the chip.\n")
        return 2
    if len(jax.devices()) < args.chips:
        sys.stderr.write(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, JAX found "
                         f"{len(jax.devices())}\n")
        return 2
    sz = SIZES["rehearse" if args.rehearse else "chip"]

    device = phase_device(cache_dir)
    if args.chips == 4:
        phase_adag(sz, args.seed, tpu)
        phase_async(sz, args.seed, 4, "async PS (4 chips)")
    else:
        phase_fence(sz, args.seed)
        phase_resnet(sz, args.seed, tpu)
        phase_gpt(sz, args.seed, tpu)
        phase_serve(sz, args.seed)
        phase_async(sz, args.seed, 2, "async PS")
    if not tpu:
        print(json.dumps({"ok": False, "rehearsal": True,
                          "device": device}), flush=True)
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
