"""Pipeline parallelism (GPipe schedule) over the ``pp`` mesh axis.

Absent from the reference (SURVEY.md §2 parallelism inventory: data
parallelism only) but first-class here: a stack of S structurally
identical stages is laid out one-stage-per-device along ``pp``; M
microbatches flow through the pipeline, activations hopping to the next
stage via ``lax.ppermute`` (neighbor traffic — rides ICI, never a host).

The whole schedule — fill, steady state, drain: M + S − 1 ticks — is ONE
``lax.scan`` inside ONE ``shard_map``-ed jit program, so XLA sees a
static loop and overlaps each tick's compute with the activation
ppermute.  Bubble ticks compute on garbage and are masked out of the
result (the classic GPipe trade: bubble fraction (S−1)/(M+S−1); raise M
to amortize).  Reverse-mode AD simply runs the scan backward —
activations re-flow through the inverse permutation, giving backward
pipelining without any hand-written schedule.

Stage contract: ``stage_fn(stage_params, x) -> y`` with ``x`` and ``y``
the same shape (homogeneous blocks — transformer layers, residual MLP
blocks).  This is the standard constraint of SPMD pipelining: one
program runs on every device, so every stage must be the same program
with different weights.

Ref (pattern): jax shard_map pipelining idiom; GPipe (Huang et al. 2019)
for the schedule.  No reference-code equivalent exists (SURVEY.md §2:
strategy ABSENT upstream).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import shard_map

Tree = Any


def stack_stage_params(stage_params: Sequence[Tree]) -> Tree:
    """Stack S per-stage param pytrees into one tree with a leading
    (stage,) axis — the layout ``pipeline_apply_sharded`` shards over
    ``pp``."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *stage_params)


def find_stage_segment(layers: Sequence, n_stages: int,
                       input_shape: Sequence[int] | None = None):
    """Locate the homogeneous stage segment of a Sequential layer list.

    Returns ``(start, group_len)`` such that
    ``layers[start : start + n_stages*group_len]`` splits into
    ``n_stages`` structurally identical groups (class + full config
    equality, nested layers included) — e.g. ``zoo.gpt_lm``'s repeated
    (Residual-attention, FF) blocks.  Picks the longest such span.
    Raises when the stack has none (the model cannot pipeline over
    ``n_stages`` stages).

    ``input_shape`` (the model's per-sample input shape) enables the
    pp=1 fallback for stacks whose repeated unit occurs only ONCE
    (e.g. ``gpt_lm(num_blocks=1)``): with a single trivially-runnable
    stage, any shape-preserving span qualifies, so the longest one is
    chosen by tracking ``Layer.out_shape`` through the stack
    (ADVICE r4).
    """
    def sig(lyr):
        return (type(lyr).__name__, repr(lyr.config()))

    sigs = [sig(l) for l in layers]
    if n_stages == 1:
        # degenerate mesh (pp=1): "any span" would trivially qualify and
        # the longest-span rule would swallow embedding/head layers whose
        # shapes don't pipeline.  Anchor on the model's actual repeated
        # unit instead: locate it as a 2-stage split, then extend the run.
        try:
            a, g = find_stage_segment(layers, 2)
        except ValueError:
            # no repeated unit at all (e.g. a single transformer block):
            # fall back to the longest shape-preserving span — pp=1 runs
            # it as the one stage with no schedule constraints beyond
            # shape preservation (state/rng checks stay with the caller)
            if input_shape is None:
                raise ValueError(
                    "pp=1 with no repeated layer group: pass input_shape "
                    "so the stage segment can be chosen by shape "
                    "preservation, or raise num_blocks so the repeated "
                    "unit occurs at least twice")
            shapes = [tuple(input_shape)]
            for lyr in layers:
                shapes.append(tuple(lyr.out_shape(shapes[-1])))
            best = None
            for a in range(len(layers)):
                for end in range(len(layers), a, -1):
                    if shapes[a] == shapes[end]:
                        if best is None or end - a > best[1] - best[0]:
                            best = (a, end)
                        break
            if best is None:
                raise ValueError(
                    "pp=1 fallback found no shape-preserving span in "
                    "this stack; the model cannot pipeline")
            return best[0], best[1] - best[0]
        end = a + 2 * g
        while end + g <= len(layers) and sigs[end:end + g] == sigs[a:a + g]:
            end += g
        return a, end - a
    best = None
    for g in range(1, len(layers) // n_stages + 1):
        span = n_stages * g
        for a in range(0, len(layers) - span + 1):
            if all(sigs[a + i * g + j] == sigs[a + j]
                   for i in range(1, n_stages) for j in range(g)):
                if best is None or span > best[0]:
                    best = (span, a, g)
    if best is None:
        raise ValueError(
            f"no contiguous run of {n_stages} structurally identical "
            f"layer groups in this {len(layers)}-layer stack; pipeline "
            f"parallelism needs homogeneous stages (e.g. zoo.gpt_lm with "
            f"num_blocks divisible by the pp axis size)")
    return best[1], best[2]


def pipeline_apply(stage_fn: Callable, stage_params: Tree, x_mb, *,
                   axis_name: str = "pp"):
    """GPipe forward; call INSIDE ``shard_map``.

    ``stage_params``: this device's stage (leaves carry a leading
    singleton stage axis, as produced by a ``P(axis_name)`` in_spec on
    the stacked tree).  ``x_mb``: the full (M, mb, ...) microbatch stack,
    replicated.  Returns (M, mb, ...) outputs, replicated (psum'd off the
    last stage).
    """
    n_stages = lax.axis_size(axis_name)
    stage_idx = lax.axis_index(axis_name)
    params = jax.tree_util.tree_map(lambda p: p[0], stage_params)
    n_micro = x_mb.shape[0]
    ticks = n_micro + n_stages - 1
    fwd = [(j, j + 1) for j in range(n_stages - 1)]  # non-cyclic: 0 gets 0s

    # stage output aval: activations may promote past the token dtype
    # (bf16 tokens × f32 params → f32) — the carry/out buffers must live
    # in the promoted (fixed-point) dtype or the scan dtypes mismatch
    y_aval = jax.eval_shape(stage_fn, params,
                            jax.ShapeDtypeStruct(x_mb.shape[1:],
                                                 x_mb.dtype))
    y_aval = jax.eval_shape(stage_fn, params,
                            jax.ShapeDtypeStruct(x_mb.shape[1:],
                                                 y_aval.dtype))
    if y_aval.shape != x_mb.shape[1:]:
        raise ValueError(
            f"stage_fn must preserve the activation shape (homogeneous "
            f"stages): got {y_aval.shape} from {x_mb.shape[1:]}")

    def tick(carry, t):
        state, out = carry
        # stage 0 injects microbatch t while any remain; later stages use
        # the activation ppermuted in from the previous stage last tick
        inject = x_mb[jnp.clip(t, 0, n_micro - 1)].astype(y_aval.dtype)
        state = jnp.where((stage_idx == 0) & (t < n_micro), inject, state)
        y = stage_fn(params, state).astype(y_aval.dtype)
        # at tick t this stage holds microbatch m = t - stage_idx
        m = t - stage_idx
        is_last = stage_idx == n_stages - 1
        valid = is_last & (m >= 0) & (m < n_micro)
        mc = jnp.clip(m, 0, n_micro - 1)
        out = lax.dynamic_update_index_in_dim(
            out, jnp.where(valid, y, lax.dynamic_index_in_dim(
                out, mc, keepdims=False)), mc, 0)
        state = lax.ppermute(y, axis_name, fwd)
        return (state, out), None

    state0 = jnp.zeros(x_mb.shape[1:], y_aval.dtype)
    out0 = jnp.zeros((n_micro,) + x_mb.shape[1:], y_aval.dtype)
    (_, out), _ = lax.scan(tick, (state0, out0), jnp.arange(ticks))
    # results live on the last stage only; broadcast so every device
    # returns the same (replicated) output
    return lax.psum(jnp.where(stage_idx == n_stages - 1, out, 0), axis_name)


def pipeline_apply_sharded(mesh: Mesh, stage_fn: Callable,
                           stacked_params: Tree, x, *,
                           num_microbatches: int, axis: str = "pp",
                           dp_axis: str | None = None):
    """Whole-array entry point: run S = ``mesh.shape[axis]`` stages over
    the pipeline.  ``stacked_params``: leading (S, ...) stage axis on
    every leaf (see :func:`stack_stage_params`).  ``x``: (B, ...) with B
    divisible by ``num_microbatches``.  Returns (B, ...).

    ``dp_axis``: optional second mesh axis to ALSO shard each
    microbatch's batch dim over — pp×dp composition: every dp replica
    runs the same pipeline schedule on its slice of every microbatch
    (params replicated across ``dp_axis``; the caller's grad psum over
    ``dp_axis`` falls out of AD through the sharded batch)."""
    n_stages = mesh.shape[axis]
    batch = x.shape[0]
    if batch % num_microbatches:
        raise ValueError(f"batch {batch} not divisible by "
                         f"num_microbatches {num_microbatches}")
    lead = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if lead != n_stages:
        raise ValueError(f"stacked_params lead dim {lead} != pipeline "
                         f"stages {n_stages} (mesh axis {axis!r})")
    mb = batch // num_microbatches
    if dp_axis is not None and mb % mesh.shape[dp_axis]:
        raise ValueError(f"microbatch size {mb} not divisible by the "
                         f"{dp_axis!r} axis size {mesh.shape[dp_axis]}")
    x_mb = x.reshape(num_microbatches, mb, *x.shape[1:])
    param_specs = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)
    data_spec = P(None, dp_axis) if dp_axis is not None else P()
    fn = shard_map(
        partial(pipeline_apply, stage_fn, axis_name=axis),
        mesh=mesh,
        in_specs=(param_specs, data_spec),
        out_specs=data_spec,
        check_vma=False)
    out = fn(stacked_params, x_mb)
    return out.reshape(batch, *out.shape[2:])
