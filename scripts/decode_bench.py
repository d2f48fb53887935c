"""Autoregressive decode throughput (the BASELINE.md decode tables).

Measures `generate_tokens` / `generate_beam` over the decode surface:
greedy vs sampled (top-k/top-p), KV-cached vs full-context recompute,
ragged prompt batches, beam search.  Timing: compile + one warmup call,
then best-of-3 wall for a full generation (one compiled scan per call —
per-call dispatch overhead is amortized across ``num_steps`` scan
iterations; see scripts/attn_block_bench.py).

The numbers flow through the obs/drift tooling, not just prints
(ISSUE 7): every config's step wall and token rate observe into a
bench-scoped registry (``decode.step_seconds`` / ``decode.tok_per_sec``
histograms), the decode entry points' recompile sentinels
(``jit.compiles``/``jit.retraces`` — one compile per distinct config is
this bench's expected shape) are routed into the same registry via
``generation.set_decode_registry``, and the whole snapshot persists to
``--obs-out`` (default ``DECODE_BENCH_OBS.json`` beside the other bench
snapshots) with the standard clobber guard — so two decode runs diff
with ``obsview --diff A B`` exactly like the trainer/PS/serve benches.

Usage: python scripts/decode_bench.py [--dim 256] [--seq 1024] [--batch 8]
Prints one JSON line per config plus a final snapshot row.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--blocks", type=int, default=2)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--obs-out",
                    default=os.path.join(ROOT, "DECODE_BENCH_OBS.json"),
                    help="registry-snapshot destination (the drift-"
                         "tooling document; clobber-guarded like every "
                         "bench snapshot)")
    args = ap.parse_args()

    import numpy as np
    import jax.numpy as jnp
    import distkeras_tpu as dk
    from distkeras_tpu.models import generation
    from distkeras_tpu.obs import Registry, TIME_BUCKETS
    from distkeras_tpu.utils.compile_cache import enable_compile_cache
    from bench import RATE_BUCKETS, _baseline_cfg, _persist_obs_snapshot

    enable_compile_cache()

    model = dk.zoo.gpt_lm(vocab_size=args.vocab, dim=args.dim,
                          num_heads=args.heads, num_blocks=args.blocks,
                          seq_len=args.seq)
    v = model.init(0)
    rng = np.random.default_rng(0)

    reg = Registry()
    # route the decode entry points' recompile counters into this bench's
    # snapshot (pre-created so 0 is present, not missing), and observe
    # each config's perf into mergeable histograms
    reg.counter("jit.compiles")
    reg.counter("jit.retraces")
    generation.set_decode_registry(reg)
    h_step = reg.histogram("decode.step_seconds", TIME_BUCKETS)
    h_rate = reg.histogram("decode.tok_per_sec", RATE_BUCKETS)
    c_configs = reg.counter("decode.configs")
    c_tokens = reg.counter("decode.tokens")

    def bench(name, fn, p, steps, batch=None, **kw):
        b = batch or args.batch
        prompt = jnp.asarray(rng.integers(0, args.vocab, size=(b, p)),
                             jnp.int32)
        np.asarray(fn(model, v, prompt, steps, **kw))  # compile + warmup
        best = 1e9
        for s in range(args.reps):
            t0 = time.perf_counter()
            np.asarray(fn(model, v, prompt, steps, **kw))
            best = min(best, time.perf_counter() - t0)
        toks = b * steps
        h_step.observe(best / steps)
        h_rate.observe(toks / best)
        c_configs.inc()
        c_tokens.inc(toks)
        print(json.dumps({
            "config": name, "prompt": p, "steps": steps, "batch": b,
            "tok_per_sec": round(toks / best),
            "ms_per_step": round(best / steps * 1e3, 3)}), flush=True)

    # config table scales with --seq (at the 1024 default these are the
    # BASELINE.md numbers: 16+512, 512+256, ...); topk is clamped so
    # tiny smoke vocabularies stay valid
    half, quarter, eighth = args.seq // 2, args.seq // 4, args.seq // 8
    topk = min(50, args.vocab)
    try:
        bench("greedy cached", dk.generate_tokens, 16, half)
        bench("greedy recompute", dk.generate_tokens, 16, half,
              use_cache=False)
        bench("greedy cached long-prompt", dk.generate_tokens, half,
              quarter)
        bench(f"topk{topk}+topp0.95 T0.8 cached", dk.generate_tokens, 16,
              half, temperature=0.8, top_k=topk, top_p=0.95, seed=1)
        lens = rng.integers(max(1, args.seq // 16), half + 1,
                            size=(args.batch,)).astype(np.int32)
        bench("ragged cached", dk.generate_tokens, half, quarter,
              prompt_lengths=lens)   # r5: per-row cache positions
        bench("ragged recompute", dk.generate_tokens, half, quarter,
              prompt_lengths=lens, use_cache=False)
        bench("beam4 cached", dk.generate_beam, 16, quarter, num_beams=4)
        bench("beam4 ragged cached", dk.generate_beam, half, eighth,
              num_beams=4, prompt_lengths=lens)
    finally:
        generation.set_decode_registry(None)

    obs_doc = {"config": {"mode": "decode_bench", "vocab": args.vocab,
                          "dim": args.dim, "heads": args.heads,
                          "blocks": args.blocks, "seq": args.seq,
                          "batch": args.batch, "reps": args.reps},
               "decode": reg.snapshot()}
    # no designated committed baseline (this is an ad-hoc perf table) —
    # the clobber guard still keeps config-incompatible runs apart, and
    # two snapshots diff via ``obsview --diff``
    _, snap_path = _persist_obs_snapshot(args.obs_out, obs_doc,
                                         _baseline_cfg(), check=False)
    print(json.dumps({
        "mode": "decode_bench",
        "snapshot": os.path.relpath(snap_path, ROOT),
        "jit_compiles": reg.counter("jit.compiles").value,
        "jit_retraces": reg.counter("jit.retraces").value}), flush=True)


if __name__ == "__main__":
    main()
