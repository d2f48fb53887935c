"""Single-chip-equivalent cost of the causal ring-attention schedules.

One chip cannot host the sp mesh, but the ring is bulk-synchronous, so
its wall clock is (collectives aside) the SLOWEST device's per-hop
compute × hops.  This script times exactly that per-device compute with
the real flash kernels on the TPU:

- ``contiguous``: the straggler shard (device P−1) — 1 causal home hop +
  (P−1) full unmasked hops at T_loc (what gates the old layout's clock).
- ``zigzag``: any shard (all identical) — the 3-half-block home hop +
  (P−1) hops of 2 half-blocks each (``parallel.ring.
  zigzag_ring_attention``'s schedule), including the lse merges.
- ``shuffle``: the one-time zigzag gather/scatter of the whole (B, T, H,
  Dh) array (paid once per batch when a pipeline keeps activations
  zigzag-ordered; per attention call otherwise).

Measured with the repeat loop INSIDE one jit (``lax.scan`` with a
threaded carry) so one dispatch covers ``--iters`` runs; best-of-5.

Usage: python scripts/ring_schedule_bench.py [--seq 32768] [--ring 8]
"""

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seq", type=int, default=32768,
                    help="GLOBAL sequence length")
    ap.add_argument("--ring", type=int, default=8, help="sp axis size P")
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--dh", type=int, default=64)
    ap.add_argument("--iters", type=int, default=16)
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args()

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax import lax
    from distkeras_tpu.obs.profile import fence
    from distkeras_tpu.ops.pallas_attention import flash_attention_lse
    from distkeras_tpu.utils.compile_cache import enable_compile_cache
    from distkeras_tpu.parallel.ring import (_merge_lse, zigzag_shuffle,
                                             zigzag_unshuffle)

    enable_compile_cache()
    B, T, P, H, DH, N = (args.batch, args.seq, args.ring, args.heads,
                         args.dh, args.iters)
    t_loc = T // P
    c = t_loc // 2
    dt = jnp.dtype(args.dtype)
    rng = np.random.default_rng(0)

    def mk(t):
        return tuple(jnp.asarray(rng.normal(size=(B, t, H, DH)), dt)
                     for _ in range(3))

    def contiguous_worst(q, k, v):
        """Device P−1's hops: causal home + P−1 full unmasked blocks."""
        o, lse = flash_attention_lse(q, k, v, True)
        o = o.astype(jnp.float32)
        for _ in range(P - 1):
            o_i, lse_i = flash_attention_lse(q, k, v, False)
            o, lse = _merge_lse(o, lse, o_i.astype(jnp.float32), lse_i)
        return o.astype(q.dtype)

    def zigzag_any(q, k, v):
        """Any device's zigzag hops (all equal): the 3-half-block home
        hop + ONE rectangular (2c × c) call per further hop, lse-merged
        like the real schedule."""
        q_e, q_l = q[:, :c], q[:, c:]
        k_e, k_l = k[:, :c], k[:, c:]
        v_e, v_l = v[:, :c], v[:, c:]
        o_e, lse_e = flash_attention_lse(q_e, k_e, v_e, True)
        o_1, lse_1 = flash_attention_lse(q_l, k_e, v_e, False)
        o_2, lse_2 = flash_attention_lse(q_l, k_l, v_l, True)
        o_l, lse_l = _merge_lse(o_1.astype(jnp.float32), lse_1,
                                o_2.astype(jnp.float32), lse_2)
        o = jnp.concatenate([o_e.astype(jnp.float32), o_l], 1)
        lse = jnp.concatenate([lse_e, lse_l], 2)
        for _ in range(P - 1):
            o_i, lse_i = flash_attention_lse(q, k_e, v_e, False)
            o, lse = _merge_lse(o, lse, o_i.astype(jnp.float32), lse_i)
        return o.astype(q.dtype)

    def measure(fn, qkv, mode, reps=5):
        q0, k, v = qkv
        if mode == "fwd":
            def body(carry, _):
                return carry + fn(carry, k, v) * jnp.asarray(1e-6, dt), ()
        else:
            g = jax.grad(lambda q, k, v: jnp.sum(
                fn(q, k, v).astype(jnp.float32) ** 2), argnums=(0, 1, 2))

            def body(carry, _):
                dq, _, _ = g(carry, k, v)
                return carry + dq * jnp.asarray(1e-9, dt), ()

        @jax.jit
        def run(q):
            out, _ = lax.scan(body, q, None, length=N)
            return jnp.sum(out.astype(jnp.float32))

        fence(run(q0))  # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fence(run(q0))
            best = min(best, (time.perf_counter() - t0) / N)
        return best * 1e3  # ms/iter

    rows = {}
    for mode in ("fwd", "fwd+bwd"):
        rows[("contiguous", mode)] = measure(contiguous_worst, mk(t_loc),
                                             mode)
        rows[("zigzag", mode)] = measure(zigzag_any, mk(t_loc), mode)

    # one-time layout shuffle of the whole global array
    x0 = jnp.asarray(rng.normal(size=(B, T, H, DH)), dt)

    @jax.jit
    def shuf(x):
        def body(carry, _):
            y = zigzag_unshuffle(zigzag_shuffle(carry, P), P)
            return y * jnp.asarray(1.0, dt), ()
        out, _ = lax.scan(body, x, None, length=N)
        return jnp.sum(out.astype(jnp.float32))

    fence(shuf(x0))
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        fence(shuf(x0))
        best = min(best, (time.perf_counter() - t0) / N)
    shuffle_ms = best * 1e3 / 2  # one shuffle = half the roundtrip

    print(f"# causal ring schedules, single-chip equivalent "
          f"(B={B} T={T} P={P} H={H} Dh={DH} {args.dtype}, t_loc={t_loc})")
    for mode in ("fwd", "fwd+bwd"):
        co = rows[("contiguous", mode)]
        zz = rows[("zigzag", mode)]
        print(f"{mode:8s}  contiguous-straggler {co:8.2f} ms   "
              f"zigzag {zz:8.2f} ms   speedup {co / zz:.2f}x")
    print(f"zigzag shuffle (one way, whole (B,T,H,Dh) array): "
          f"{shuffle_ms:.3f} ms")


if __name__ == "__main__":
    main()
