"""Flash-attention block-size micro-benchmark (the measurement behind
``ops.pallas_attention._auto_block``'s big-block default, and beside it
the default path: the in-kernel causal walk where it engages).

Times the attention op alone — dense (XLA) vs the Pallas flash kernels at
several (block_q, block_k) — with the repeat loop INSIDE one jit
(``lax.scan``) and a scalar output, so one dispatch covers ``--iters``
kernel runs and dispatch latency does not swamp kernel time.

r4 measurements (1x v5e, B=2 T=8192 H=4 Dh=64, bf16, causal, ms/iter):

    dense:            fwd  7.40   fwd+bwd 14.49
    flash  128x128:   fwd 16.55   fwd+bwd 20.62   (old default)
    flash  256x256:   fwd  8.03   fwd+bwd 10.52
    flash  512x512:   fwd  5.50   fwd+bwd  6.75
    flash 1024x1024:  fwd  4.57   fwd+bwd  5.98   (auto default)

The T = 1,024 ladder (PR 27's builder's chip runs, 1 Oct 2026, kept by
PR 28; 1x v5e, the benchmark's attention: ``--seq 1024 --batch 32
--heads 12``, Dh=64, bf16, causal, ms/iter, the (B, T, H, Dh) transposes
around the kernels included):

    dense:            fwd  3.81   fwd+bwd  9.18
    flash default:    fwd  1.51   fwd+bwd  2.71   (in-kernel walk, tile 256)
    flash  128x128:   fwd 11.51   fwd+bwd 20.61
    flash  256x256:   fwd  5.74   fwd+bwd  9.20
    flash  512x512:   fwd  3.28   fwd+bwd  5.39
    flash 1024x1024:  fwd  2.18   fwd+bwd  4.07   (the grid walk's auto
                                                   block: the default
                                                   before PR 28)

and the kernels alone, us a batch·head at (384, 1024, 64) bf16, forward +
dQ + dK/dV (the same runs):

    grid walk, 1024² block:   4.41 + 4.55 + 6.27 = 15.22  (before PR 28)
    grid walk,  512² blocks:  18.99      256² blocks: 35.88
    in-kernel walk, tile 256: 2.66 + 2.76 + 3.71 =  9.13  (the default)
    in-kernel walk, tile 128: 9.28       tile 512: 10.17
    an online softmax over runs of keys: 10.13

"flash default" passes no blocks: causal lengths that fit VMEM whole take
the in-kernel causal walk (``_causal_tile``), every other call the grid
walk with ``_auto_block``'s blocks.  Explicit blocks are always the grid
walk, where a skipped block still pays its grid step and its K/V DMA:
that is why smaller blocks lose there, and why the causal walk moved
inside the kernel.

Usage: python scripts/attn_block_bench.py [--seq 8192] [--dh 64]
       python scripts/attn_block_bench.py --seq 1024 --batch 32 --heads 12
"""

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--dh", type=int, default=64)
    ap.add_argument("--iters", type=int, default=24)
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args()

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax import lax
    from distkeras_tpu.obs.profile import fence
    from distkeras_tpu.ops.attention import dot_product_attention
    from distkeras_tpu.ops.pallas_attention import _blocks, flash_attention
    from distkeras_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    B, T, H, DH, N = args.batch, args.seq, args.heads, args.dh, args.iters
    rng = np.random.default_rng(0)
    dt = jnp.dtype(args.dtype)
    q0, k, v = (jnp.asarray(rng.normal(size=(B, T, H, DH)), dt)
                for _ in range(3))

    def measure(attn, mode, reps=5):
        if mode == "fwd":
            def body(c, _):
                return c + attn(c, k, v) * jnp.asarray(1e-6, dt), ()
        else:
            g = jax.grad(lambda q, k, v: jnp.sum(
                attn(q, k, v).astype(jnp.float32) ** 2), argnums=(0, 1, 2))
            def body(c, _):
                dq, _, _ = g(c, k, v)
                return c + dq.astype(c.dtype) * jnp.asarray(1e-6, dt), ()
        f = jax.jit(lambda q: jnp.sum(
            lax.scan(body, q, None, length=N)[0].astype(jnp.float32)))
        fence(f(q0))  # compile + first run
        best = min(_timed(f, q0) for _ in range(reps))
        return best / N * 1e3

    def _timed(f, x):
        t0 = time.perf_counter()
        fence(f(x))
        return time.perf_counter() - t0

    d = lambda q, k, v: dot_product_attention(q, k, v, causal=True)  # noqa
    print(f"dense: fwd {measure(d, 'fwd'):.2f} ms  "
          f"fwd+bwd {measure(d, 'bwd'):.2f} ms", flush=True)
    # no blocks given: the in-kernel causal walk where _blocks engages it
    default = lambda q, k, v: flash_attention(q, k, v, True)  # noqa
    tile = _blocks(q0, k, True, None, None)[2]
    path = f"in-kernel walk, tile {tile}" if tile else "grid walk"
    print(f"flash default ({path}): fwd {measure(default, 'fwd'):.2f} ms  "
          f"fwd+bwd {measure(default, 'bwd'):.2f} ms", flush=True)
    # explicit blocks: always the grid walk
    for bq, bk in [(128, 128), (256, 256), (512, 512), (1024, 1024)]:
        if T % bq or T % bk:
            continue
        fl = lambda q, k, v, bq=bq, bk=bk: flash_attention(  # noqa
            q, k, v, True, bq, bk)
        print(f"flash {bq}x{bk}: fwd {measure(fl, 'fwd'):.2f} ms  "
              f"fwd+bwd {measure(fl, 'bwd'):.2f} ms", flush=True)


if __name__ == "__main__":
    main()
