"""MFU measurement for the headline workload (SURVEY.md §6 north star).

Computes Model FLOPs Utilization for a zoo-model epoch program
(ResNet-20/CIFAR-10 by default; ``--model resnet50`` for the
ImageNet-subset config):

    MFU = (XLA-counted FLOPs per epoch / measured epoch seconds) / chip peak

FLOPs come from the compiled executable's own cost analysis
(``jit(...).lower(...).compile().cost_analysis()['flops']``) — the same
program the trainer runs, counted by the compiler, not an analytic guess.
Timing is the trainer's own: every epoch's wall time is marked at the
completion of its loss readback (``trainers._EpochPipeline``).

Usage::

    python scripts/mfu.py [--batch 1024] [--width 16] [--steps 32]
    python scripts/mfu.py --model resnet50 --image-size 96 --classes 100 \
        --batch 256

Prints one JSON line; BASELINE.md records the numbers.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

#: peak dense bf16 matmul TFLOP/s per chip, keyed by the EXACT jax
#: ``device_kind`` — only devices this repo has run on.  An unlisted
#: device is an error, never a default: ``--peak-tflops`` is the only way
#: to run on one.
PEAK_TFLOPS = {
    # TPU v5e: 197 TFLOP/s bf16 per chip — Google Cloud documentation,
    # "TPU v5e" (system architecture, key chip specifications)
    "TPU v5 lite": 197.0,
}


def peak_flops(device_kind: str, override_tflops=None) -> float:
    """Peak FLOP/s for ``device_kind`` (or the ``--peak-tflops``
    override); an unknown kind raises."""
    if override_tflops:
        return float(override_tflops) * 1e12
    if device_kind not in PEAK_TFLOPS:
        raise ValueError(
            f"no peak FLOP/s on record for device_kind {device_kind!r} "
            f"(known: {sorted(PEAK_TFLOPS)}); an MFU against a guessed "
            f"peak is not a measurement — pass --peak-tflops with its "
            f"source")
    return PEAK_TFLOPS[device_kind] * 1e12


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet20",
                    choices=["resnet20", "resnet50", "lstm", "gpt"])
    ap.add_argument("--dim", type=int, default=512,
                    help="gpt: model width")
    ap.add_argument("--blocks", type=int, default=4,
                    help="gpt: transformer blocks")
    ap.add_argument("--seq-len", type=int, default=None,
                    help="lstm/gpt sequence length (default: 200 for "
                         "lstm — the IMDB config — and 512 for gpt)")
    ap.add_argument("--units", type=int, default=64,
                    help="lstm: hidden units (the bench config's 64)")
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--width", type=int, default=16,
                    help="ResNet-20 base width (16 = the standard model)")
    ap.add_argument("--image-size", type=int, default=32)
    ap.add_argument("--stem", default="conv7", choices=["conv7", "s2d"],
                    help="ResNet-50 stem: classic conv7 or the TPU "
                         "space-to-depth rewrite")
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--epochs", type=int, default=6,
                    help="timed epochs (after 2 warmup)")
    ap.add_argument("--peak-tflops", type=float, default=None,
                    help="peak bf16 TFLOP/s of a device PEAK_TFLOPS does "
                         "not list (the only way to run on one)")
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from distkeras_tpu.models import zoo
    from distkeras_tpu.trainers import SingleTrainer
    from distkeras_tpu.utils.compile_cache import enable_compile_cache

    if args.seq_len is None:
        args.seq_len = 512 if args.model == "gpt" else 200
    VOCAB = 4000  # probe vocab: lstm/gpt data + analytic formulas

    enable_compile_cache()
    kind = jax.devices()[0].device_kind
    peak = peak_flops(kind, args.peak_tflops)

    rng = np.random.default_rng(0)
    n = args.steps * args.batch
    s, k = args.image_size, args.classes
    loss = "categorical_crossentropy"
    if args.model == "resnet20":
        model = zoo.resnet20(num_classes=k, width=args.width)
        label = f"resnet20(width={args.width})"
    elif args.model == "gpt":
        if args.width != 16 or args.stem != "conv7" or s != 32 or k != 10:
            ap.error("--width/--stem/--image-size/--classes apply to the "
                     "resnet models only (gpt takes --dim/--blocks/"
                     "--seq-len)")
        # the transformer family's MFU probe: flash attention, bf16 —
        # completes the ladder across conv / recurrent / attention models
        model = zoo.gpt_lm(vocab_size=VOCAB, dim=args.dim, num_heads=8,
                           num_blocks=args.blocks, seq_len=args.seq_len,
                           attention_impl="flash")
        label = (f"gpt_lm(T={args.seq_len}, dim={args.dim}, "
                 f"blocks={args.blocks}, flash)")
        loss = "sparse_categorical_crossentropy"
    elif args.model == "lstm":
        if args.width != 16 or args.stem != "conv7" or s != 32 or k != 10:
            ap.error("--width/--stem/--image-size/--classes apply to the "
                     "resnet models only (lstm takes --seq-len/--units)")
        # the AEASGD/EAMSGD bench config's model (the only BASELINE
        # workload without an MFU row until r5), rebuilt WITHOUT its
        # Dropout(0.5) so the probe's compiled program is exactly the
        # embed->LSTM->head math being costed
        from distkeras_tpu.models.layers import (Dense, Embedding, LSTM,
                                                 Sequential)
        from distkeras_tpu.models.model import Model
        model = Model(Sequential([
            Embedding(VOCAB, 64),
            LSTM(args.units),
            Dense(1, "sigmoid"),
        ]), input_shape=(args.seq_len,), name="lstm_probe")
        label = f"lstm_imdb(T={args.seq_len}, units={args.units})"
        loss = "binary_crossentropy"
    else:
        if args.width != 16:
            ap.error("--width applies to resnet20 only")
        model = zoo.resnet50(num_classes=k, input_size=s, stem=args.stem)
        label = f"resnet50({s}px, stem={args.stem})"
    if args.model == "gpt":
        xs = rng.integers(0, VOCAB, size=(n, args.seq_len)).astype(np.int32)
        ys = rng.integers(0, VOCAB,
                          size=(n, args.seq_len)).astype(np.int64)
    elif args.model == "lstm":
        xs = rng.integers(0, VOCAB, size=(n, args.seq_len)).astype(np.int32)
        ys = rng.integers(0, 2, size=(n,)).astype(np.float32)
    else:
        xs = rng.random((n, s, s, 3), dtype=np.float32)
        ys = np.eye(k, dtype=np.float32)[rng.integers(0, k, size=n)]

    warmup = 2
    trainer = SingleTrainer(
        model, "sgd", loss,
        num_epoch=warmup + args.epochs, batch_size=args.batch,
        learning_rate=0.1, compute_dtype=args.dtype)
    # the jitted window program itself: _window_run() hands back the
    # instrumented wrapper, which has no .lower
    trainer._window_run()
    _, run, optimizer = trainer._run_cache

    variables = trainer.model.init(0)
    opt_state = optimizer.init(variables["params"])
    key = jax.random.PRNGKey(1)
    sx = jnp.asarray(xs.reshape(args.steps, args.batch, *xs.shape[1:]))
    sy = jnp.asarray(ys.reshape(args.steps, args.batch, *ys.shape[1:]))

    # compiler-counted FLOPs (fwd+bwd+opt).  XLA's HloCostAnalysis counts
    # a while/scan BODY once and does not multiply by trip count (verified
    # empirically: flops identical for steps=4 and steps=8), so the
    # reported number is per-step cost; the epoch is steps × that.
    compiled = run.lower(variables, opt_state, key, sx, sy).compile()
    epoch_flops = float(compiled.cost_analysis()["flops"]) * args.steps
    if args.model == "gpt":
        # the flash-attention pallas kernels are custom calls whose FLOPs
        # HloCostAnalysis cannot see: count the transformer analytically —
        # per token, 6·(non-embedding params) for the matmul stack
        # (fwd 2 + bwd 4) plus the attention scores/values product:
        # 2·2·T·d per token fwd PER BLOCK, ×3 with backward (review r5:
        # the first formulation dropped the ×L and understated MFU)
        d, L, t_ = args.dim, args.blocks, args.seq_len
        matmul_params = L * (4 * d * d + 2 * d * 4 * d) + VOCAB * d
        per_token = 6 * matmul_params + 3 * L * (4 * t_ * d)
        epoch_flops = float(per_token) * t_ * n
    elif args.model == "lstm":
        # HloCostAnalysis counts the LSTM's INNER time-axis scan body
        # once too (same while-body rule as the outer loop), so the
        # compiler number misses ~T× of the recurrence and its BPTT —
        # count the recurrence analytically instead: per sample per
        # time step the fused gate matmul is (E+H)·4H MACs; backward
        # re-runs it twice (dx and dW products), so ≈ 3× forward.
        e, h, t_ = 64, args.units, args.seq_len
        gate_flops = 2 * (e + h) * 4 * h          # fwd MACs → FLOPs
        epoch_flops = 3.0 * gate_flops * t_ * n
    del variables, opt_state  # donated dummies; the trainer re-inits

    # timed through the PUBLIC trainer path — pipelined epochs, per-epoch
    # readback fences, final drain: the bench.py methodology, so this MFU
    # corresponds 1:1 to the recorded headline samples/sec.
    from distkeras_tpu.data.dataset import Dataset
    trainer.train(Dataset({"features": xs, "label": ys}))
    epochs = [r for r in trainer.metrics.records if r["event"] == "epoch"]
    dt = sum(r["epoch_seconds"] for r in epochs[warmup:]) / args.epochs

    achieved = epoch_flops / dt
    print(json.dumps({
        "model": label,
        "batch": args.batch, "steps_per_epoch": args.steps,
        "compute_dtype": args.dtype, "device_kind": kind,
        "epoch_flops": epoch_flops,
        "flops_per_sample": round(epoch_flops / n),
        "epoch_seconds": round(dt, 4),
        "samples_per_sec": round(n / dt),
        "achieved_tflops": round(achieved / 1e12, 2),
        "peak_tflops": round(peak / 1e12, 1),
        "mfu": round(achieved / peak, 4),
    }))


if __name__ == "__main__":
    main()
