"""Flash-attention block-size micro-benchmark (the measurement behind
``ops.pallas_attention._auto_block``'s big-block default).

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

Usage: python scripts/attn_block_bench.py [--seq 8192] [--dh 64]
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
    from distkeras_tpu.ops.pallas_attention import flash_attention
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
    for bq, bk in [(128, 128), (256, 256), (512, 512), (1024, 1024)]:
        if T % bq or T % bk:
            continue
        fl = lambda q, k, v, bq=bq, bk=bk: flash_attention(  # noqa
            q, k, v, True, bq, bk)
        print(f"flash {bq}x{bk}: fwd {measure(fl, 'fwd'):.2f} ms  "
              f"fwd+bwd {measure(fl, 'bwd'):.2f} ms", flush=True)


if __name__ == "__main__":
    main()
