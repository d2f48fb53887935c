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

The T = 8,192, head 128 ladder (PR 33's builder's chip runs, 3 Oct 2026;
1x v5e, the Laguna cell's full layers: ``--kernels --batch 1 --seq 8192
--heads 48 --dh 128``, bf16, causal, default 512-blocks; the kernels alone
from a device trace, us a batch·head, forward + dQ + dK/dV):

    grid walk, every pair a step (PR 32's tree):
                                    293.86 + 230.12 + 286.08 = 810.07
    table walk (136 of 256 pairs a step, 16 masked), bodies as before:
                                    252.13 + 183.04 + 257.35 = 692.51
      + dK/dV builds S, dP transposed:        ... + 232.85 = 668.01
      + running max / sum as (512, 1) columns: 252.12 (no change)
      + running max / sum lane-replicated (the default since PR 33):
                                    152.68 + 183.22 + 233.12 = 569.02
    the table packed into one int32 a step:   154.52 + 185.03 + 235.39
    a fori_loop over resident K/V (q / dO), carried accumulators:
                                    157.09 + 171.64 + 249.88 = 578.62
      the same with its accumulators in VMEM scratch: forward 232.91
    table grid, K/V whole-resident (no K/V DMA a step): forward 253.56
    dense grid, no mask (256 steps): 444.23 + 315.70 + 406.90
    table walk at 256² blocks: 1135.96   at 1,024² blocks: 538.49
    (``_auto_block`` caps head 128 at 512: the window layers share it)

and the window layers (``--heads 64 --window 512``, 31 of 256 pairs):

    band walk (PR 32's tree):        68.80 + 45.87 + 59.57 = 174.24
    band walk, PR 33's bodies:       50.39 + 45.56 + 56.72 = 152.66
    the same on the table walk:      52.14 + 48.10 + 58.76 = 159.00

What the ladder says: an idle grid step costs 0.2-0.35 us and a table
step about 0.08 us more than an arithmetic one (so the window, with one
idle step in 32, keeps its band); the forward's time was not its mask
but its statistics (a lane gather and a rotate a vreg a step to keep
them as a column); K/V DMA a step is free (hidden under the block).

The 8k cells' three shapes with K and V at their own head count (PR 35's
builder's chip run, 4 Oct 2026; ``--kv-heads 8`` / ``8`` / ``2``; native =
the kernels read (B, T, KV, Dh), repeated = ``jnp.repeat`` to the query
heads before the call, the program before PR 35), us a batch·head,
forward + dQ + dK/dV, and beside them ALL the device's operations of one
forward + backward (``all``, ms: the kernels, the transposes around them,
the repeat and the sum over a group):

    48 over 8 (Laguna's full layers)
      native:    152.29 + 187.81 + 237.64 = 577.74    all 28.712
      repeated:  152.18 + 189.99 + 233.20 = 575.36    all 29.109
    64 over 8, window 512 (its window layers)
      native:     50.01 +  45.00 +  52.58 = 147.59    all 10.843
      repeated:   50.38 +  45.56 +  56.71 = 152.65    all 11.916
    32 over 2 (Nemotron's attention layer)
      native:    145.08 + 174.23 + 217.16 = 536.47    all 17.770
      repeated:  156.65 + 182.86 + 232.10 = 571.61    all 19.469

The kernels themselves hardly care (the K/V DMA was hidden already; 16
heads to a K/V head is where reading it once shows: − 6 %); what goes is
around them (− 0.4, − 1.1 and − 1.7 ms a layer): the repeat, the group
sum and K/V-side transposes at the query head count.

"flash default" passes no blocks: causal lengths that fit VMEM whole take
the in-kernel causal walk (``_causal_tile``), every other call the grid
walk with ``_auto_block``'s blocks.  Explicit blocks are always the grid
walk; since PR 33 a causal call there takes a grid step only for the
block pairs with work (``_walk_table``), so smaller blocks lose by their
per-step overhead and small matmuls alone, no longer by idle steps.

Usage: python scripts/attn_block_bench.py [--seq 8192] [--dh 64]
       python scripts/attn_block_bench.py --seq 1024 --batch 32 --heads 12
       python scripts/attn_block_bench.py --kernels --batch 1 --heads 48 \
           --dh 128 [--window 512] [--kv-heads 8]
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
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--kv-heads", type=int, default=None,
                    help="K/V heads (a divisor of --heads): with --kernels, "
                         "native against repeated to the query heads")
    ap.add_argument("--kernels", action="store_true",
                    help="the Mosaic kernels alone, from a device trace")
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
    KV = args.kv_heads or H
    rng = np.random.default_rng(0)
    dt = jnp.dtype(args.dtype)
    q0, k, v = (jnp.asarray(rng.normal(size=(B, T, heads, DH)), dt)
                for heads in (H, KV, KV))

    def repeated(attn):  # K/V at the query heads before the call
        return lambda q, k, v: attn(q, jnp.repeat(k, H // KV, axis=2),
                                    jnp.repeat(v, H // KV, axis=2))

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

    def kernels_alone(attn):
        """us a batch·head of every Mosaic kernel of one forward +
        backward: the rows a benchmark trace reads, by the same reader."""
        import tempfile
        sys.path.insert(0, os.path.join(ROOT, "benchmark"))
        import reduce_trace
        g = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            attn(q, k, v).astype(jnp.float32) ** 2), argnums=(0, 1, 2)))
        fence(g(q0, k, v))
        with tempfile.TemporaryDirectory() as trace_dir:
            with jax.profiler.trace(trace_dir):
                for _ in range(N):
                    fence(g(q0, k, v))
            devices = reduce_trace.load(
                reduce_trace.newest_xplane(trace_dir))["devices"]
        if 0 not in devices:
            raise SystemExit("no device plane in the trace: a kernel's "
                             "time comes from a chip run")
        ops = devices[0][reduce_trace.OPS_LINE]
        total, everything = {}, 0.0
        for name, start, end in ops:
            everything += end - start
            if reduce_trace.MOSAIC in name:
                name = reduce_trace.op_name(name).split(":")[1]
                total[name] = total.get(name, 0.0) + (end - start)
        return ({name: ns / 1e3 / N / (B * H) for name, ns in total.items()},
                everything / 1e6 / N)

    if args.kernels:
        walks = [("default", (), False)] + [
            (f"{b}x{b}", (b, b), False) for b in (256, 512, 1024)
            if T % b == 0 and args.window is None]
        if KV != H:  # the default blocks alone, native against repeated
            walks = [(f"{H} over {KV} heads, native", (), False),
                     (f"{H} over {KV} heads, repeated", (), True)]
        for label, blocks, repeat in walks:
            blocks = blocks or (None, None)
            attn = lambda q, k, v, blocks=blocks: flash_attention(  # noqa
                q, k, v, True, *blocks, args.window)
            try:
                us, all_ms = kernels_alone(repeated(attn) if repeat else attn)
            except Exception as e:  # noqa: BLE001 — a block VMEM refuses
                print(f"kernels {label}: {str(e).splitlines()[0][:120]}")
                continue
            print(f"kernels {label} (blocks, tile "
                  f"{_blocks(q0, q0, True, *blocks, args.window)}): "
                  + "  ".join(f"{n} {t:.2f}" for n, t in sorted(us.items()))
                  + f"  sum {sum(us.values()):.2f} us a batch·head"
                  + f"  all {all_ms:.3f} ms", flush=True)
        return
    if KV != H:  # the block ladder below is the equal-heads one
        k, v = (jnp.repeat(x, H // KV, axis=2) for x in (k, v))

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
