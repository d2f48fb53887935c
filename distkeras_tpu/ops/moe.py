"""Mixture-of-Experts with expert parallelism over the ``ep`` mesh axis.

Absent from the reference (SURVEY.md §2: expert parallelism ABSENT) but
first-class here: E feed-forward experts are sharded one-group-per-device
along ``ep``; tokens are routed (switch/top-1, Fedus et al. 2021) to
their expert via ``lax.all_to_all`` — the canonical MoE collective, one
fused ICI exchange each way instead of a host-side shuffle.

Dataflow per device (inside ``shard_map``; P = ep size, E = P·E_loc):

    tokens (n_loc, d) ──router──▶ dispatch one-hot (n_loc, E, C)
      ──einsum──▶ (E, C, d) ──all_to_all──▶ (E_loc, P·C, d)
      ──expert FFN──▶ ──all_to_all back──▶ combine ▶ (n_loc, d)

Capacity: each source device sends at most C = ceil(n_loc/E ·
capacity_factor) tokens to any one expert; overflow tokens are dropped
(zero output — callers add a residual, the standard switch contract).
The whole block is differentiable (einsum dispatch + all_to_all), so it
trains under ``jax.grad`` with no custom backward.

Load-balance auxiliary loss: ``aux = E · Σ_e f_e · p_e`` (fraction of
tokens routed to e × mean router probability of e), pmean'd over the
mesh — add ``aux_weight * aux`` to the task loss to keep experts busy.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.mesh import shard_map

Tree = Any


def init_moe_params(seed: int, num_experts: int, d_model: int,
                    d_hidden: int) -> Tree:
    """Router + E expert FFNs (relu MLPs).  Expert leaves carry a leading
    (E,) axis — the dim ``switch_moe_sharded`` shards over ``ep``."""
    k = jax.random.PRNGKey(seed)
    kg, k1, k2 = jax.random.split(k, 3)
    s1 = 1.0 / math.sqrt(d_model)
    s2 = 1.0 / math.sqrt(d_hidden)
    return {
        "router": {"wg": jax.random.normal(kg, (d_model, num_experts)) * s1},
        "experts": {
            "w1": jax.random.normal(k1, (num_experts, d_model, d_hidden)) * s1,
            "b1": jnp.zeros((num_experts, d_hidden)),
            "w2": jax.random.normal(k2, (num_experts, d_hidden, d_model)) * s2,
            "b2": jnp.zeros((num_experts, d_model)),
        },
    }


def _capacity(n_loc: int, num_experts: int, capacity_factor: float) -> int:
    return max(1, math.ceil(n_loc / num_experts * capacity_factor))


def switch_moe(params: Tree, x, *, axis_name: str = "ep",
               capacity_factor: float = 1.25):
    """Switch-MoE block; call INSIDE ``shard_map``.

    ``x``: (n_loc, d) local token shard.  ``params["experts"]`` leaves:
    local (E_loc, ...) expert shard; ``params["router"]["wg"]``:
    replicated (d, E).  Returns ``(out (n_loc, d), aux_loss scalar)``.
    """
    p_size = lax.axis_size(axis_name)
    wg = params["router"]["wg"]
    ex = params["experts"]
    n_loc, d = x.shape
    num_experts = wg.shape[1]
    e_loc = ex["w1"].shape[0]
    if e_loc * p_size != num_experts:
        raise ValueError(f"router knows {num_experts} experts but shards "
                         f"hold {e_loc}×{p_size}")
    cap = _capacity(n_loc, num_experts, capacity_factor)

    # -- route: top-1 expert per token, position within its send buffer --
    gates = jax.nn.softmax(x @ wg, axis=-1)            # (n_loc, E)
    expert_idx = jnp.argmax(gates, axis=-1)            # (n_loc,)
    gate = jnp.take_along_axis(gates, expert_idx[:, None], 1)[:, 0]
    # slot bookkeeping in int32: a low-precision token dtype (bf16) cannot
    # represent consecutive integers past 256, which would collide
    # capacity slots silently
    onehot_i = jax.nn.one_hot(expert_idx, num_experts, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot_i, axis=0) - 1) * onehot_i  # arrival order
    keep = pos < cap
    onehot = onehot_i.astype(x.dtype)
    dispatch = onehot[..., None] * keep.astype(x.dtype)[..., None] * \
        jax.nn.one_hot(pos, cap, dtype=x.dtype)
    # (n_loc, E, C): exactly one 1 per kept token

    # -- dispatch to expert owners: one all_to_all each way -------------
    send = jnp.einsum("nec,nd->ecd", dispatch, x)      # (E, C, d)
    recv = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)                  # block s = src dev s
    recv = recv.reshape(p_size, e_loc, cap, d) \
        .transpose(1, 0, 2, 3).reshape(e_loc, p_size * cap, d)

    h = jax.nn.relu(jnp.einsum("egd,edh->egh", recv, ex["w1"])
                    + ex["b1"][:, None])
    y = jnp.einsum("egh,ehd->egd", h, ex["w2"]) + ex["b2"][:, None]

    back = y.reshape(e_loc, p_size, cap, d).transpose(1, 0, 2, 3) \
        .reshape(p_size * e_loc, cap, d)
    combined = lax.all_to_all(back, axis_name, split_axis=0, concat_axis=0,
                              tiled=True)              # (E, C, d) at source

    out = jnp.einsum("nec,ecd->nd", dispatch * gate[:, None, None],
                     combined)

    # -- switch load-balance loss (global: pmean over the mesh) ---------
    frac = jnp.mean(onehot, axis=0)                    # tokens per expert
    prob = jnp.mean(gates, axis=0)                     # router mass
    aux = num_experts * jnp.sum(lax.pmean(frac, axis_name)
                                * lax.pmean(prob, axis_name))
    return out, aux


def dense_moe(params: Tree, x):
    """Single-device reference FORMULA: every token through its top-1
    expert, no capacity limit (nothing to overflow without a dispatch
    buffer), by computing every expert for every token.  Same math the
    sharded path computes for kept tokens and ``MoEDense`` computes
    without a mesh (``routed_experts``, which a trainer runs instead:
    it multiplies each token by its own expert alone)."""
    wg = params["router"]["wg"]
    ex = params["experts"]
    gates = jax.nn.softmax(x @ wg, axis=-1)
    idx = jnp.argmax(gates, axis=-1)
    gate = jnp.take_along_axis(gates, idx[:, None], 1)[:, 0]
    h = jax.nn.relu(jnp.einsum("nd,edh->neh", x, ex["w1"]) + ex["b1"])
    y = jnp.einsum("neh,ehd->ned", h, ex["w2"]) + ex["b2"]
    picked = jnp.take_along_axis(y, idx[:, None, None], 1)[:, 0]
    onehot = jax.nn.one_hot(idx, wg.shape[1], dtype=x.dtype)
    aux = wg.shape[1] * jnp.sum(jnp.mean(onehot, 0) * jnp.mean(gates, 0))
    return gate[:, None] * picked, aux


def switch_moe_sharded(mesh: Mesh, params: Tree, x, *, axis: str = "ep",
                       capacity_factor: float = 1.25):
    """Whole-array entry point: tokens (N, d) sharded over ``mesh[axis]``,
    expert leaves sharded on their leading (E,) dim, router replicated.
    Returns ``(out (N, d), aux_loss scalar)``."""
    p_size = mesh.shape[axis]
    n_tokens = x.shape[0]
    num_experts = params["router"]["wg"].shape[1]
    if n_tokens % p_size:
        raise ValueError(f"token count {n_tokens} not divisible by the "
                         f"{axis!r} axis size {p_size}")
    if num_experts % p_size:
        raise ValueError(f"{num_experts} experts not divisible by the "
                         f"{axis!r} axis size {p_size}")
    specs = {"router": jax.tree_util.tree_map(lambda _: P(),
                                              params["router"]),
             "experts": jax.tree_util.tree_map(lambda _: P(axis),
                                               params["experts"])}
    fn = shard_map(
        partial(switch_moe, axis_name=axis,
                capacity_factor=capacity_factor),
        mesh=mesh,
        in_specs=(specs, P(axis)),
        out_specs=(P(axis), P()),
        check_vma=False)
    return fn(params, x)


# ---------------------------------------------------------------------------
# dropless top-k routing over the experts held here (one device, no mesh)
# ---------------------------------------------------------------------------

class DispatchPlan(NamedTuple):
    """Where every (token, choice) assignment of one routed layer goes in
    the experts' row layout (``ops.pallas_moe``'s).  N tokens, k choices
    a token, ``held`` experts here, R rows: a row for every assignment
    that COULD land here.  These integers are all that is R long: the
    rows themselves exist a :class:`Round` at a time."""
    dest: Any         # (N, k) int32: the assignment's row (0 if not here)
    here: Any         # (N, k) bool: its expert is held here
    row_assign: Any   # (R,) int32: the row's assignment n * k + j (0 if none)
    row_used: Any     # (R,) bool: the row holds an assignment
    tile_expert: Any  # (R / tile_rows,) int32
    num_tiles: Any    # (1,) int32: tiles the experts' stretches take
    counts: Any       # (held,) int32: assignments of each expert here


#: A round's room over the rows EXPECTED here (N·k·held / experts).  What
#: arrives is the sum of the held experts' loads: a router kept in balance
#: holds ONE expert within about twice its mean (the capacity factors of
#: the layers that drop: 1.25 in Switch, 2 in GShard) and a sum over
#: several experts spreads less, so at 2 a second round is a rare step,
#: and a rare step only costs its rounds.
ROUND_HEADROOM = 2.0


def round_rows(n: int, k: int, experts_held: int, num_experts: int,
               tile_rows: int) -> int:
    """R_c, the rows one round of :func:`routed_experts` holds, from the
    shapes alone: ``ROUND_HEADROOM`` times the rows expected here in
    whole tiles, one tile more an expert held (its last tile's padding),
    and never more than the layout's worst case, every assignment landing
    here (N·k in whole tiles + a tile an expert), which is what a layer
    holding every expert gets."""
    worst = -(-n * k // tile_rows) + experts_held
    expected = n * k * experts_held / num_experts
    tiles = math.ceil(ROUND_HEADROOM * expected / tile_rows) + experts_held
    return min(worst, tiles) * tile_rows


def dispatch_plan(expert_idx, first_expert: int, experts_held: int,
                  tile_rows: int, round_rows: Optional[int] = None
                  ) -> DispatchPlan:
    """Sort the assignments whose expert is one of the ``experts_held``
    from ``first_expert`` by expert, token order kept inside an expert,
    each expert's stretch rounded up to whole tiles (one tile at least).
    Nothing is dropped: the layout has a row for every assignment that
    could land here (R = N·k rounded up to tiles + one tile an expert,
    then up to whole rounds of ``round_rows``).  Only these integers are
    R long; the rows themselves are gathered a round at a time."""
    n, k = expert_idx.shape
    local = expert_idx.reshape(n * k).astype(jnp.int32) - first_expert
    here = (local >= 0) & (local < experts_held)
    group = jnp.where(here, local, experts_held)  # elsewhere: a last group
    onehot = jax.nn.one_hot(group, experts_held + 1, dtype=jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0), group[:, None],
                               axis=1)[:, 0] - 1
    counts = jnp.sum(onehot, axis=0)[:experts_held]
    tiles = jnp.maximum(-(-counts // tile_rows), 1)
    ends = jnp.cumsum(tiles) * tile_rows
    starts = ends - tiles * tile_rows
    rows = -(-n * k // tile_rows) * tile_rows + experts_held * tile_rows
    if round_rows is not None:
        rows = -(-rows // round_rows) * round_rows
    dest = jnp.where(here, starts[jnp.minimum(group, experts_held - 1)]
                     + rank, rows)  # elsewhere: past the buffer, dropped
    row_assign = jnp.full((rows,), -1, jnp.int32).at[dest].set(
        jnp.arange(n * k, dtype=jnp.int32), mode="drop")
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(rows // tile_rows) * tile_rows,
                         side="right"), experts_held - 1).astype(jnp.int32)
    return DispatchPlan(
        dest=jnp.where(here, dest, 0).reshape(n, k).astype(jnp.int32),
        here=here.reshape(n, k), row_assign=jnp.maximum(row_assign, 0),
        row_used=row_assign >= 0, tile_expert=tile_expert,
        num_tiles=(ends[-1:] // tile_rows).astype(jnp.int32), counts=counts)


def _rows(src, index):
    return src.at[index].get(mode="promise_in_bounds")


class Round(NamedTuple):
    """Round r of a plan: the window of ``T_c`` tiles ``[r·T_c, (r+1)·T_c)``
    of its layout, R_c = T_c · tile_rows rows.  What a grouped matmul
    reads of a plan (``tile_expert``, ``num_tiles``) it reads of a round:
    an expert's stretch may begin in the round before or end in the next,
    and an expert may have no tile in it."""
    assign: Any       # (R_c,) int32: the row's assignment n * k + j
    token: Any        # (R_c,) int32: its token n (0 where the row is unused)
    used: Any         # (R_c,) bool
    tile_expert: Any  # (T_c,) int32
    num_tiles: Any    # (1,) int32: the round's used tiles, T_c but in the last


def _round_of(plan: DispatchPlan, r, k: int, round_rows: int,
              tile_rows: int) -> Round:
    tiles = round_rows // tile_rows
    assign = lax.dynamic_slice_in_dim(plan.row_assign, r * round_rows,
                                      round_rows)
    return Round(
        assign=assign, token=assign // k,
        used=lax.dynamic_slice_in_dim(plan.row_used, r * round_rows,
                                      round_rows),
        tile_expert=lax.dynamic_slice_in_dim(plan.tile_expert, r * tiles,
                                             tiles),
        num_tiles=jnp.minimum(plan.num_tiles - r * tiles, tiles))


def _to_rows(src, rnd: Round):
    """(N, D) -> (R_c, D): each used row its token's row of ``src``, the
    others zero.  A gather of R_c rows."""
    return jnp.where(rnd.used[:, None], _rows(src, rnd.token), 0)


def _to_tokens(rows, scale, rnd: Round, n: int):
    """(R_c, D) -> (N, D) float32: each token the sum of ``scale[i] *
    rows[i]`` over its rows in the round (``scale`` (R_c,) float32, 0
    where a row is unused), products and sums in float32: the transpose
    of :func:`_to_rows`.  ``ops.pallas_moe.rows_to_tokens`` adds the used
    tiles' rows into a block of the sum's columns held in VMEM; where not
    even a 128-column block of (N, D) fits there, XLA's scatter-add of
    the R_c rows.  Neither builds the (N, k, D) gather of the layer that
    had one buffer, which cost the same whether a choice was here or not
    (``scripts/moe_token_side_bench.py`` ranks the candidates)."""
    kernels = _pallas_moe()
    block = kernels.rows_to_tokens_block(n, rows.shape[-1])
    if block is None:
        return _scatter_to_tokens(rows, scale, rnd, n)
    return kernels.rows_to_tokens(
        rows, scale, rnd.token, rnd.num_tiles, n=n, block=block,
        tile_rows=rows.shape[0] // rnd.tile_expert.shape[0],
        interpret=kernels._interpret())


def _scatter_to_tokens(rows, scale, rnd: Round, n: int):
    return jnp.zeros((n, rows.shape[-1]), jnp.float32).at[rnd.token].add(
        scale[:, None] * rows.astype(jnp.float32),
        mode="promise_in_bounds")


def _row_weights(weights, rnd: Round, dtype):
    """(R_c,) float32: each used row its assignment's routing weight as
    the rows' dtype holds it (the combine multiplies in that dtype, as it
    did), 0 where the row is unused."""
    return jnp.where(rnd.used, _rows(weights.reshape(-1), rnd.assign),
                     0).astype(dtype).astype(jnp.float32)


def _rounds_walked(plan: DispatchPlan, round_rows: int, tile_rows: int):
    """int32 scalar, >= 1: the rounds the plan's used tiles take."""
    return -(-plan.num_tiles[0] // (round_rows // tile_rows))


def _walk(plan: DispatchPlan, round_rows: int, tile_rows: int, one_round,
          merge):
    """``one_round(the plan's round r)`` for every round with a used tile
    (at least one; how many is known on the device alone) folded by
    ``merge(so far, round r's)``.  Round 0 runs outside the loop and is
    the result as it stands where there is no other, so the step every
    cell runs initialises no sum and converts nothing; the others run in
    a ``lax.while_loop``, which a layout of one round does not build."""
    k = plan.dest.shape[1]
    total = one_round(_round_of(plan, 0, k, round_rows, tile_rows))
    if plan.row_assign.shape[0] == round_rows:
        return total

    def another(carry):
        r, total = carry
        return r + 1, merge(total, one_round(
            _round_of(plan, r, k, round_rows, tile_rows)))

    rounds = _rounds_walked(plan, round_rows, tile_rows)
    return lax.while_loop(lambda carry: carry[0] < rounds, another,
                          (jnp.int32(1), total))[1]


def _experts_present(rnd: Round, experts_held: int):
    """(held,) bool: the expert has a used tile in the round."""
    tiles = rnd.tile_expert.shape[0]
    return jnp.any(
        (rnd.tile_expert[None, :] == jnp.arange(experts_held)[:, None])
        & (jnp.arange(tiles) < rnd.num_tiles[0])[None, :], axis=1)


def _merge_by_expert(so_far, part, seen, present):
    """The experts' parameter gradients after one more round.  A leaf is
    (held, ...); a round's part of it is defined for the experts PRESENT
    in the round alone (``grouped_matmul``'s backward leaves an absent
    expert's block unwritten), so it is selected, never multiplied.  An
    expert seen in an earlier round too (its stretch straddles the
    boundary) gets the float32 sum of both parts."""
    def merge(a, b):
        shape = (-1,) + (1,) * (a.ndim - 1)
        both = (a.astype(jnp.float32) + b.astype(jnp.float32)).astype(a.dtype)
        return jnp.where((seen & present).reshape(shape), both,
                         jnp.where(present.reshape(shape), b, a))
    return jax.tree_util.tree_map(merge, so_far, part)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _rounds(expert_rows, round_rows: int, tile_rows: int, x, weights,
            expert_params, plan: DispatchPlan):
    """(N, D) float32: the held experts' part of the layer, a round of
    ``round_rows`` rows at a time.  Reverse mode does not pass a
    ``while_loop``: the backward walks the same rounds, rebuilding a
    round's rows from ``x`` and the plan, so nothing a round long outlives
    the forward (the residuals are the arguments)."""
    n = x.shape[0]

    def one_round(rnd: Round):
        with jax.named_scope("dispatch"):
            rows = _to_rows(x, rnd)
        with jax.named_scope("experts"):
            rows = expert_rows(expert_params, rows, rnd)
        with jax.named_scope("combine"):
            return _to_tokens(rows, _row_weights(weights, rnd, x.dtype),
                              rnd, n)

    return _walk(plan, round_rows, tile_rows, one_round, jnp.add)


def _rounds_fwd(expert_rows, round_rows, tile_rows, x, weights,
                expert_params, plan):
    return _rounds(expert_rows, round_rows, tile_rows, x, weights,
                   expert_params, plan), (x, weights, expert_params, plan)


def _rounds_bwd(expert_rows, round_rows, tile_rows, res, g):
    x, weights, expert_params, plan = res
    n, k = plan.dest.shape
    held = plan.counts.shape[0]

    def one_round(rnd: Round):
        """This round's part of (dx, the weights' gradient by assignment,
        the experts' parameters' gradients), and the experts it held."""
        with jax.named_scope("dispatch"):
            rows = _to_rows(x, rnd)
        with jax.named_scope("experts"):
            y, back = jax.vjp(lambda p, rows: expert_rows(p, rows, rnd),
                              expert_params, rows)
        with jax.named_scope("combine"):
            g_rows = _to_rows(g, rnd).astype(jnp.float32)
            w = _row_weights(weights, rnd, x.dtype)
            # an unused row's index is past the end, and dropped
            d_weights = jnp.zeros((n * k,), jnp.float32).at[
                jnp.where(rnd.used, rnd.assign,
                          n * k + jnp.arange(round_rows))].set(
                    jnp.sum(y.astype(jnp.float32) * g_rows, axis=-1),
                    mode="drop", unique_indices=True)
            dy = (w[:, None] * g_rows).astype(y.dtype)
        with jax.named_scope("experts"):
            d_params, d_rows = back(dy)
        with jax.named_scope("dispatch"):
            dx = _to_tokens(d_rows, rnd.used.astype(jnp.float32), rnd, n)
        return dx, d_weights, d_params, _experts_present(rnd, held)

    def merge(so_far, part):
        dx, d_weights, d_params, seen = so_far
        return (dx + part[0], d_weights + part[1],
                _merge_by_expert(d_params, part[2], seen, part[3]),
                seen | part[3])

    dx, d_weights, d_params, _ = _walk(plan, round_rows, tile_rows,
                                       one_round, merge)
    return (dx.astype(x.dtype), d_weights.reshape(n, k).astype(weights.dtype),
            d_params, None)


_rounds.defvjp(_rounds_fwd, _rounds_bwd)


def route_top_k(x, kernel, k: int, *, normalise: bool, scale: float,
                bias=None):
    """Router scores in float32 over ALL experts, the k best, their
    weights (divided by their sum if ``normalise``) times ``scale``.
    ``bias`` None: softmax probabilities, the k largest.  ``bias``
    (num_experts,): sigmoid scores (DeepSeek-V3's router), the k largest
    of score + bias; the bias bears on the choice alone, the weights are
    the scores themselves, so it has no gradient.  The tokens are cast to
    float32 and multiply the float32 kernel at HIGHEST (a TPU's default
    matmul would round both to bf16); under mixed precision the trainers
    hand the router its master weights (``parallel.sync.make_local_step``
    leaves ``router`` leaves uncast).
    -> (idx (N, k), weights (N, k) float32, probabilities (N, E): the
    scores over their sum where they are sigmoids)."""
    logits = jnp.dot(x.astype(jnp.float32), kernel.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if bias is None:
        probs = jax.nn.softmax(logits, axis=-1)
        weights, idx = lax.top_k(probs, k)
        if normalise:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return idx, weights * scale, probs
    scores = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(scores + lax.stop_gradient(
        bias.astype(jnp.float32)), k)
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    if normalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return idx, weights * scale, \
        scores / jnp.sum(scores, axis=-1, keepdims=True)


def routed_experts(x, idx, weights, expert_rows, expert_params, *,
                   first_expert: int, experts_held: int, num_experts: int,
                   tile_rows: int):
    """``sum_j weights[n, j] * E_{idx[n, j]}(x[n])`` over the choices whose
    expert is held here; the others add nothing (another chip's part).

    ``expert_rows(expert_params, rows (R_c, D), round) -> (R_c, D)``
    applies each expert to its own tiles of a round's rows (grouped
    matmuls over ``round.tile_expert`` / ``round.num_tiles``); it is a
    function of its arguments alone (the backward calls it again).  The
    assignments that arrived are walked in rounds of R_c rows
    (:func:`round_rows`), as many as their tiles take, counted on the
    device: nothing is dropped at any load, and a step in which about the
    expected number arrives, or a layer that holds every expert, is one
    round.  Returns the (N, D) result, the plan, and what the walk leaves
    in a layer's state: ``rounds`` and ``round_rows`` (R_c), float32."""
    n, k = idx.shape
    rows = round_rows(n, k, experts_held, num_experts, tile_rows)
    plan = dispatch_plan(idx, first_expert, experts_held, tile_rows, rows)
    out = _rounds(expert_rows, rows, tile_rows, x, weights, expert_params,
                  plan)
    return out.astype(x.dtype), plan, {
        "rounds": _rounds_walked(plan, rows, tile_rows).astype(jnp.float32),
        "round_rows": jnp.full((), rows, jnp.float32)}


def routing_state(idx, probs, plan, tile_rows: int) -> dict:
    """What a routed layer leaves in its state each step: the switch
    load-balance loss over all experts, the rows its grouped matmuls
    needed (assignments landing here) and ran (the tiles their stretches
    take), and the fullest expert's load over the mean."""
    num_experts = probs.shape[-1]
    load = jnp.sum(jax.nn.one_hot(idx, num_experts, dtype=jnp.float32),
                   axis=(0, 1))
    frac = load / jnp.sum(load)
    return {
        "aux_loss": num_experts * jnp.sum(frac * jnp.mean(probs, axis=0)),
        "rows_needed": jnp.sum(plan.counts).astype(jnp.float32),
        "rows_run": (plan.num_tiles[0] * tile_rows).astype(jnp.float32),
        "load_max_over_mean": jnp.max(load) / jnp.mean(load),
    }


def routing_stats(state: Tree):
    """Sum of ``rows_needed`` / ``rows_run`` / ``rounds`` and the largest
    ``round_rows`` and ``load_max_over_mean`` over every routed layer's
    state in a variables-state tree (host values), or None where there is
    none."""
    found = []

    def visit(node):
        if isinstance(node, dict):
            if "rows_needed" in node and "rows_run" in node:
                found.append(node)
            for v in node.values():
                visit(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                visit(v)

    visit(state)
    if not found:
        return None
    return {"rows_needed": float(sum(np.sum(s["rows_needed"])
                                     for s in found)),
            "rows_run": float(sum(np.sum(s["rows_run"]) for s in found)),
            "rounds": float(sum(np.sum(s["rounds"]) for s in found)),
            "round_rows": float(max(np.max(s["round_rows"])
                                    for s in found)),
            "load_max_over_mean": float(max(np.max(s["load_max_over_mean"])
                                            for s in found))}


# ---------------------------------------------------------------------------
# layer API integration (models.layers contract)
# ---------------------------------------------------------------------------

from ..models.layers import (Layer, glorot_uniform, register,  # noqa: E402
                             relu2, relu2_mlp, swiglu)


def _pallas_moe():
    """``ops.pallas_moe``, imported at first use as ``ops.attention``
    imports its kernels: Pallas costs a second or two at start-up, which
    every ``import distkeras_tpu`` would pay (``setup_s``)."""
    from . import pallas_moe
    return pallas_moe


def _relu_bias_rows(ex, rows, rnd):
    """``MoEDense``'s experts over a round's rows: relu MLPs with biases."""
    grouped_matmul = _pallas_moe().grouped_matmul
    row_expert = jnp.repeat(rnd.tile_expert,
                            rows.shape[0] // rnd.tile_expert.shape[0])
    h = grouped_matmul(rows, ex["w1"].astype(rows.dtype), rnd.tile_expert,
                       rnd.num_tiles)
    h = jax.nn.relu(h + ex["b1"].astype(rows.dtype)[row_expert])
    y = grouped_matmul(h, ex["w2"].astype(rows.dtype), rnd.tile_expert,
                       rnd.num_tiles)
    return y + ex["b2"].astype(rows.dtype)[row_expert]


def _gated_rows(ex, rows, rnd):
    """``SparseMoE``'s experts over a round's rows: SwiGLU where they hold
    ``gate_up`` (gate and up side by side), else relu² over ``up``."""
    grouped_matmul = _pallas_moe().grouped_matmul
    if "gate_up" in ex:
        h = grouped_matmul(rows, ex["gate_up"].astype(rows.dtype),
                           rnd.tile_expert, rnd.num_tiles)
        f = h.shape[-1] // 2
        h = jax.nn.silu(h[:, :f]) * h[:, f:]
    else:
        h = relu2(grouped_matmul(rows, ex["up"].astype(rows.dtype),
                                 rnd.tile_expert, rnd.num_tiles))
    return grouped_matmul(h, ex["down"].astype(rows.dtype), rnd.tile_expert,
                          rnd.num_tiles)


@register
class MoEDense(Layer):
    """Switch-MoE feed-forward as a model layer: a drop-in for the
    transformer FF block (wrap in ``Residual`` like any FF).

    Without a mesh it runs the dropless routed path
    (:func:`routed_experts` at k = 1: each token's row goes to its one
    expert's stretch of a row buffer and through grouped matmuls) — the
    numbers of the per-token formula :func:`dense_moe`, without computing
    every expert for every token.  With a mesh attached (``layer.mesh =
    mesh``; find instances via ``model.iter_layers()``) execution
    switches to :func:`switch_moe_sharded` over its ``ep`` axis (capacity
    and drops).  The mesh is runtime placement, not architecture, so it
    is deliberately NOT part of the serialized config (a deserialized
    model runs unsharded until a mesh is re-attached).

    The mesh branch is TRACE-time state: attach it BEFORE any function
    over the model is jitted.  An already-compiled executable (e.g.
    ``ModelPredictor`` jits at construction) keeps its captured path —
    re-jit (rebuild the predictor / trainer) after switching.

    The router load-balance aux loss is written to ``state["aux_loss"]``
    each step.  By default the stock trainers optimize the task loss only
    (reference parity: its trainers have no auxiliary-loss concept); pass
    ``aux_weight=...`` to any trainer to fold the load-balance losses
    into the objective (``parallel.sync.make_local_step``) — the standard
    mitigation for router/expert collapse in long MoE runs.
    """

    def __init__(self, num_experts: int, d_hidden: Optional[int] = None,
                 capacity_factor: float = 1.25):
        self.num_experts = int(num_experts)
        self.d_hidden = d_hidden if d_hidden is None else int(d_hidden)
        self.capacity_factor = float(capacity_factor)
        self.mesh: Optional[Mesh] = None  # runtime attachment, not config

    def init(self, rng, in_shape):
        d = in_shape[-1]
        hidden = self.d_hidden if self.d_hidden is not None else 4 * d
        seed = int(jax.random.randint(rng, (), 0,
                                      jnp.iinfo(jnp.int32).max))
        params = init_moe_params(seed, self.num_experts, d, hidden)
        return params, {"aux_loss": jnp.zeros(())}, in_shape

    def apply(self, params, state, x, *, train=False, rng=None):
        tokens = x.reshape(-1, x.shape[-1])
        if self.mesh is not None:
            # ``switch_moe`` routes in the tokens' dtype: a bf16 step
            # hands the router's master weights over uncast
            router = {"wg": params["router"]["wg"].astype(tokens.dtype)}
            out, aux = switch_moe_sharded(
                self.mesh, dict(params, router=router), tokens,
                capacity_factor=self.capacity_factor)
            return out.reshape(x.shape), \
                {"aux_loss": aux.astype(jnp.float32)}
        tile_rows = _pallas_moe().TILE_ROWS
        with jax.named_scope("router"):
            idx, weights, probs = route_top_k(
                tokens, params["router"]["wg"], 1, normalise=False,
                scale=1.0)
        out, plan, _ = routed_experts(
            tokens, idx, weights, _relu_bias_rows, params["experts"],
            first_expert=0, experts_held=self.num_experts,
            num_experts=self.num_experts, tile_rows=tile_rows)
        stats = routing_state(idx, probs, plan, tile_rows)
        return out.reshape(x.shape), {"aux_loss": stats["aux_loss"]}

    def get_config(self):
        return {"num_experts": self.num_experts, "d_hidden": self.d_hidden,
                "capacity_factor": self.capacity_factor}


@register
class SparseMoE(Layer):
    """Dropless top-k mixture of experts with a shared expert, as one
    chip of an expert-parallel deployment runs it: the router scores ALL
    ``num_experts`` in float32, a token takes its ``experts_per_token``
    best, weighted by their scores (divided by their sum if
    ``normalise``) times ``routed_scale``; THIS layer holds the
    ``experts_held`` experts from ``first_expert`` and adds their part of
    the sum alone, plus the ungated shared expert that every chip
    computes.  No capacity, no dropped token, and nothing stands in for
    the experts held elsewhere: with ``experts_held = num_experts`` (the
    default) it is the whole layer, and the parts of all shares add up to
    it (shared expert counted once).  A share works on the rows that
    arrive, in rounds of a size read from the shapes
    (:func:`routed_experts`, :func:`round_rows`): one round in a step
    that brings about its expected load, more where more arrives.

    ``expert_activation``: ``"swiglu"`` (``(silu(x W_gate) * x W_up)
    W_down``, gate and up side by side) or ``"relu2"`` (``relu(x W_up)^2
    W_down``, no gate), routed and shared experts alike.  ``scoring``:
    ``"softmax"`` over the experts, or ``"sigmoid"`` a score an expert
    with ``router.bias`` added for the CHOICE alone (DeepSeek-V3's
    router; the bias gets no gradient, a balancing rule would set it).

    Parameters: ``router.kernel`` (D, num_experts), and ``router.bias``
    (num_experts,) under ``"sigmoid"``; ``experts.gate_up``
    (experts_held, D, 2·d_hidden) or ``experts.up`` (experts_held, D,
    d_hidden), and ``experts.down`` (experts_held, d_hidden, D);
    ``shared.gate_up`` or ``shared.up``, and ``shared.down``, where
    ``shared_hidden > 0``.  State each step: ``aux_loss`` (the switch
    load-balance loss), ``rows_needed`` / ``rows_run`` (rows the grouped
    matmuls needed and ran), ``rounds`` / ``round_rows`` (the rounds they
    ran in, and a round's rows) and ``load_max_over_mean``."""

    def __init__(self, num_experts: int, experts_per_token: int,
                 d_hidden: int, shared_hidden: int = 0,
                 routed_scale: float = 1.0, normalise: bool = True,
                 experts_held: Optional[int] = None, first_expert: int = 0,
                 expert_activation: str = "swiglu",
                 scoring: str = "softmax"):
        if expert_activation not in ("swiglu", "relu2"):
            raise ValueError(f"expert_activation must be 'swiglu' or "
                             f"'relu2', got {expert_activation!r}")
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring must be 'softmax' or 'sigmoid', "
                             f"got {scoring!r}")
        self.num_experts = int(num_experts)
        self.experts_per_token = int(experts_per_token)
        self.d_hidden = int(d_hidden)
        self.shared_hidden = int(shared_hidden)
        self.routed_scale = float(routed_scale)
        self.normalise = bool(normalise)
        self.experts_held = self.num_experts if experts_held is None \
            else int(experts_held)
        self.first_expert = int(first_expert)
        self.expert_activation = expert_activation
        self.scoring = scoring
        if not 0 <= self.first_expert <= self.num_experts \
                - self.experts_held or self.experts_held < 1:
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert}+"
                f"{self.experts_held} are not among {self.num_experts}")

    @property
    def _up(self) -> tuple:
        """(the first matrix's name, its width in ``d_hidden``s)."""
        return ("gate_up", 2) if self.expert_activation == "swiglu" \
            else ("up", 1)

    def init(self, rng, in_shape):
        d, f = in_shape[-1], self.d_hidden
        kr, kg, kd, ksg, ksd = jax.random.split(rng, 5)
        held = self.experts_held
        up, wide = self._up
        params = {
            "router": {"kernel": glorot_uniform(kr, (d, self.num_experts))},
            "experts": {
                up: glorot_uniform(kg, (held, d, wide * f), fan_in=d,
                                   fan_out=f),
                "down": glorot_uniform(kd, (held, f, d), fan_in=f,
                                       fan_out=d)},
        }
        if self.scoring == "sigmoid":
            params["router"]["bias"] = jnp.zeros((self.num_experts,))
        if self.shared_hidden:
            fs = self.shared_hidden
            params["shared"] = {
                up: glorot_uniform(ksg, (d, wide * fs), fan_in=d,
                                   fan_out=fs),
                "down": glorot_uniform(ksd, (fs, d))}
        # one buffer a leaf: the trainers donate the state
        state = {name: jnp.zeros((), jnp.float32) for name in (
            "aux_loss", "rows_needed", "rows_run", "rounds", "round_rows",
            "load_max_over_mean")}
        return params, state, in_shape

    def apply(self, params, state, x, *, train=False, rng=None):
        tokens = x.reshape(-1, x.shape[-1])
        tile_rows = _pallas_moe().TILE_ROWS
        up, _ = self._up
        with jax.named_scope("router"):
            idx, weights, probs = route_top_k(
                tokens, params["router"]["kernel"], self.experts_per_token,
                normalise=self.normalise, scale=self.routed_scale,
                bias=params["router"].get("bias"))
        out, plan, walked = routed_experts(
            tokens, idx, weights, _gated_rows, params["experts"],
            first_expert=self.first_expert, experts_held=self.experts_held,
            num_experts=self.num_experts, tile_rows=tile_rows)
        if self.shared_hidden:
            with jax.named_scope("shared_expert"):
                shared = swiglu if up == "gate_up" else relu2_mlp
                out = out + shared(tokens, params["shared"][up],
                                   params["shared"]["down"])
        return out.reshape(x.shape), dict(
            routing_state(idx, probs, plan, tile_rows), **walked)

    def get_config(self):
        return {"num_experts": self.num_experts,
                "experts_per_token": self.experts_per_token,
                "d_hidden": self.d_hidden,
                "shared_hidden": self.shared_hidden,
                "routed_scale": self.routed_scale,
                "normalise": self.normalise,
                "experts_held": self.experts_held,
                "first_expert": self.first_expert,
                "expert_activation": self.expert_activation,
                "scoring": self.scoring}
