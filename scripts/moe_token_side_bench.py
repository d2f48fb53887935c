"""The routed experts' token side, candidate by candidate, on the chip.

A round of ``ops.moe.routed_experts`` holds ``R_c`` rows sorted by expert.
Two of its steps run on the TOKEN side: the combine (``out[n] = sum of
w * y[row]`` over the token's rows in the round) and the transpose of the
dispatch in the backward (``dx[n] = sum of drows[row]``).  Both are one
operation, rows -> tokens (``ops.moe._to_tokens``).  This script times
its candidates at a configuration's shapes, alone and inside one
``SparseMoE`` layer's forward and backward, from a device trace (PR 37's
ladder, ``PERF.md`` §6):

* ``kernel``: ``ops.pallas_moe.rows_to_tokens``, the rows added one by
  one into a column block of the sum held in VMEM;
* ``scatter``: XLA's scatter-add of the round's rows into (N, D) float32;
* ``gathers``: the form of the layer that had one buffer, a (N, k, D)
  gather from the round's rows and a sum over k;

and the layer as the tree has it (``asis``: the one thing a checkout
without rounds can run).

    chiprun -- python scripts/moe_token_side_bench.py --shapes nemotron
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from distkeras_tpu.ops import moe  # noqa: E402

SHAPES = {  # tokens, width, SparseMoE's arguments
    "nemotron": (8192, 2688, dict(
        num_experts=128, experts_per_token=6, d_hidden=1856,
        shared_hidden=3712, routed_scale=2.5, experts_held=8,
        expert_activation="relu2", scoring="sigmoid")),
    "laguna": (8192, 2048, dict(
        num_experts=256, experts_per_token=8, d_hidden=512,
        shared_hidden=512, routed_scale=2.5, experts_held=32)),
    "tiny": (512, 128, dict(
        num_experts=16, experts_per_token=3, d_hidden=64, shared_hidden=64,
        experts_held=4)),
}
REPEATS = 5


def device_ms(fn, *args) -> dict:
    """Milliseconds a call on the device, by operation, from a trace of
    ``REPEATS`` calls: ``{"all": busy, name: own time, ...}`` (the ten
    longest)."""
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import reduce_trace
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(REPEATS):
                jax.block_until_ready(fn(*args))
        devices = reduce_trace.load(
            reduce_trace.newest_xplane(trace_dir))["devices"]
    if 0 not in devices:
        raise SystemExit("no device plane in the trace: a time comes "
                         "from a chip run")
    total = {}
    for name, start, end in devices[0][reduce_trace.OPS_LINE]:
        name = reduce_trace.op_name(name)
        total[name] = total.get(name, 0.0) + (end - start) / 1e6 / REPEATS
    rows = sorted(total.items(), key=lambda row: -row[1])
    return {"all": round(sum(total.values()), 4),
            **{name: round(ms, 4) for name, ms in rows[:10]}}


def gathers(k):
    def to_tokens(rows, scale, rnd, n):
        # every assignment's row in the round (the plan's ``dest``, which
        # the one-buffer layer had), rebuilt here from the round
        r = rows.shape[0]
        where = jnp.where(rnd.used, rnd.assign, n * k + jnp.arange(r))
        dest = jnp.full((n * k,), r, jnp.int32).at[where].set(
            jnp.arange(r, dtype=jnp.int32), mode="drop", unique_indices=True)
        valid = (dest < r).reshape(n, k)
        dest = jnp.minimum(dest, r - 1).reshape(n, k)
        picked = jnp.where(valid[..., None], rows.at[dest].get(
            mode="promise_in_bounds"), 0)
        weights = jnp.where(valid, scale.at[dest].get(
            mode="promise_in_bounds"), 0)
        return jnp.einsum("nk,nkd->nd", weights.astype(rows.dtype), picked,
                          preferred_element_type=jnp.float32)
    return to_tokens


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--shapes", default="nemotron", choices=sorted(SHAPES))
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)
    n, d, layer_args = SHAPES[args.shapes]
    layer = moe.SparseMoE(**layer_args)
    k, held = layer.experts_per_token, layer.experts_held
    params, state, _ = layer.init(jax.random.PRNGKey(args.seed), (n, d))
    half = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    params = dict(half, router=params["router"])  # a bf16 step's
    rng = np.random.default_rng(args.seed)
    x = jnp.asarray(rng.normal(size=(1, n, d)), jnp.bfloat16)
    mix = jnp.asarray(rng.normal(size=(1, n, d)), jnp.bfloat16)
    results = {"shapes": args.shapes,
               "device": jax.devices()[0].device_kind}

    def layer_step():
        """One layer's forward and backward, traced afresh (a candidate
        is patched into ``ops.moe`` between calls)."""
        def loss(p, x):
            out, st = layer.apply(p, state, x)
            return jnp.sum((out * mix).astype(jnp.float32)), st
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))

    if not hasattr(moe, "_to_tokens"):  # a checkout without rounds
        results["layer_ms"] = {"asis": device_ms(layer_step(), params, x)}
        print(json.dumps(results))
        return 0

    candidates = {"kernel": moe._to_tokens,
                  "scatter": moe._scatter_to_tokens,
                  "gathers": gathers(k)}
    tile = moe._pallas_moe().TILE_ROWS
    rc = moe.round_rows(n, k, held, layer.num_experts, tile)
    idx = jnp.asarray(np.stack([rng.choice(layer.num_experts, k,
                                           replace=False)
                                for _ in range(n)]), jnp.int32)
    plan = moe.dispatch_plan(idx, 0, held, tile, rc)
    rnd = moe._round_of(plan, 0, k, rc, tile)
    rows = jnp.where(rnd.used[:, None], jnp.asarray(
        rng.normal(size=(rc, d)), jnp.bfloat16), 0)
    scale = jnp.where(rnd.used, jnp.asarray(rng.uniform(size=(rc,)),
                                            jnp.float32), 0)
    results.update(round_rows=rc, rows_parent=-(-n * k // tile) * tile
                   + held * tile, rows_arrived=int(jnp.sum(plan.counts)),
                   tiles_used=int(plan.num_tiles[0]))
    want = np.asarray(moe._scatter_to_tokens(rows, scale, rnd, n))
    results["alone_ms"], results["layer_ms"] = {}, {}
    for name, fn in candidates.items():
        alone = jax.jit(lambda rows, scale, rnd, fn=fn: fn(rows, scale, rnd,
                                                           n))
        # the gathers multiply in the rows' dtype, as the layer did
        np.testing.assert_allclose(
            np.asarray(alone(rows, scale, rnd)), want, err_msg=name,
            **(dict(rtol=2e-2, atol=2e-2) if name == "gathers"
               else dict(rtol=1e-5, atol=1e-5)))
        results["alone_ms"][name] = device_ms(alone, rows, scale, rnd)
        moe._to_tokens = fn
        results["layer_ms"][name] = device_ms(layer_step(), params, x)
    moe._to_tokens = candidates["kernel"]
    results["alone_ms"]["to_rows"] = device_ms(
        jax.jit(moe._to_rows), x[0], rnd)
    print(json.dumps(results))
    return 0


if __name__ == "__main__":
    sys.exit(main())
