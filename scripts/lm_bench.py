"""Long-context LM throughput ladder: tokens/sec vs sequence length.

Measures the ``zoo.gpt_lm`` training step (fwd+bwd+adam, bf16 compute)
at increasing sequence lengths, dense (XLA O(T²)) vs flash (Pallas
O(T·D)-HBM) attention, holding tokens-per-batch constant so every row
does comparable non-attention work.  The reference's sequence ceiling
was one worker's LSTM (SURVEY.md §5.7); this table is the beyond-parity
long-context story BASELINE.md records.

Timing: compile + warmup steps, then timed steps ending in the one
timing fence (``distkeras_tpu.obs.profile.fence``).

Usage::

    python scripts/lm_bench.py [--seqs 512,2048,8192] [--impls dense,flash]
        [--tokens-per-batch 16384] [--dim 256] [--steps 8]

Prints one JSON line per (impl, T) config.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

VOCAB = 256


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="512,2048,8192")
    ap.add_argument("--impls", default="dense,flash")
    ap.add_argument("--tokens-per-batch", type=int, default=16384)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--blocks", type=int, default=2)
    ap.add_argument("--steps", type=int, default=8,
                    help="timed steps (after 1 compile + 2 warmup)")
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import optax
    from distkeras_tpu.models import zoo
    from distkeras_tpu.obs.profile import fence
    from distkeras_tpu.ops.losses import sparse_categorical_crossentropy
    from distkeras_tpu.parallel.sync import make_local_step
    from distkeras_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    kind = jax.devices()[0].device_kind
    rng = np.random.default_rng(0)

    for impl in args.impls.split(","):
        for t_str in args.seqs.split(","):
            seq = int(t_str)
            batch = max(args.tokens_per_batch // seq, 1)
            model = zoo.gpt_lm(vocab_size=VOCAB, dim=args.dim,
                               num_heads=args.heads,
                               num_blocks=args.blocks, seq_len=seq,
                               attention_impl=impl.strip())
            variables = model.init(0)
            optimizer = optax.adam(1e-3)
            opt_state = optimizer.init(variables["params"])

            # the framework's own train step (mixed-precision path the
            # trainers run), jitted with donated carry
            step = make_local_step(model, sparse_categorical_crossentropy,
                                   optimizer, compute_dtype=args.dtype)
            jstep = jax.jit(step, donate_argnums=0)
            carry = (variables, opt_state, jax.random.PRNGKey(0))
            xs = rng.integers(0, VOCAB, size=(batch, seq)).astype(np.int32)
            ys = rng.integers(0, VOCAB, size=(batch, seq)).astype(np.int32)
            x, y = jnp.asarray(xs), jnp.asarray(ys)

            try:
                for _ in range(3):  # compile + warmup
                    carry, loss = jstep(carry, (x, y))
                fence(loss)  # drain
                t0 = time.perf_counter()
                for _ in range(args.steps):
                    carry, loss = jstep(carry, (x, y))
                fence(loss)
                dt = time.perf_counter() - t0
            except jax.errors.JaxRuntimeError as e:
                # an out-of-memory row is data (dense attention past its
                # ceiling is what the ladder shows); anything else — a
                # compiler refusal included — is a failure of this run
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                print(json.dumps({
                    "impl": impl, "seq_len": seq, "batch": batch,
                    "error": "RESOURCE_EXHAUSTED"}))
                continue

            toks = args.steps * batch * seq
            print(json.dumps({
                "impl": impl, "seq_len": seq, "batch": batch,
                # batch clamps at 1, so rows with seq > --tokens-per-batch
                # do MORE tokens/step than the others — recorded so the
                # table stays comparable
                "tokens_per_step": batch * seq,
                "dim": args.dim, "compute_dtype": args.dtype,
                "device_kind": kind,
                "tokens_per_sec": round(toks / dt),
                "step_ms": round(1e3 * dt / args.steps, 2)}))


if __name__ == "__main__":
    main()
