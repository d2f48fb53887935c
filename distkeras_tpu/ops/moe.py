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
    the experts' row buffer (``ops.pallas_moe``'s layout).  N tokens, k
    choices a token, R buffer rows, ``held`` experts here."""
    dest: Any         # (N, k) int32: the assignment's row (0 if not here)
    here: Any         # (N, k) bool: its expert is held here
    row_assign: Any   # (R,) int32: the row's assignment n * k + j (0 if none)
    row_used: Any     # (R,) bool: the row holds an assignment
    tile_expert: Any  # (R / tile_rows,) int32
    num_tiles: Any    # (1,) int32: tiles the experts' stretches take
    counts: Any       # (held,) int32: assignments of each expert here


def dispatch_plan(expert_idx, first_expert: int, experts_held: int,
                  tile_rows: int) -> DispatchPlan:
    """Sort the assignments whose expert is one of the ``experts_held``
    from ``first_expert`` by expert, token order kept inside an expert,
    each expert's stretch rounded up to whole tiles (one tile at least).
    Nothing is dropped: the buffer has room for every assignment landing
    here (R = N·k rounded up to tiles + one tile an expert)."""
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


@jax.custom_vjp
def _take_rows(src, index, mask, back_index, back_mask):
    """``out[i] = src[index[i]]`` where ``mask[i]``, else 0, for an index
    (in bounds everywhere) that reaches each row of ``src`` from at most
    J places known beforehand: ``back_index`` (rows of src, J) names
    them in the flattened output, so the backward is a gather too and no
    scatter-add."""
    return jnp.where(mask[..., None], _rows(src, index), 0)


def _take_rows_fwd(src, index, mask, back_index, back_mask):
    return _take_rows(src, index, mask, back_index, back_mask), \
        (back_index, back_mask)


def _take_rows_bwd(res, g):
    back_index, back_mask = res
    flat = g.reshape(-1, g.shape[-1])
    back = jnp.where(back_mask[..., None], _rows(flat, back_index), 0)
    return (jnp.sum(back.astype(jnp.float32), axis=1).astype(g.dtype),
            None, None, None, None)


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


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


def routed_experts(x, idx, weights, expert_rows, *, first_expert: int,
                   experts_held: int, tile_rows: int):
    """``sum_j weights[n, j] * E_{idx[n, j]}(x[n])`` over the choices whose
    expert is held here; the others add nothing (another chip's part).

    ``expert_rows(rows (R, D), plan) -> (R, D)`` applies each expert to
    its own stretch of the row buffer (grouped matmuls).  Returns the
    (N, D) result and the plan."""
    plan = dispatch_plan(idx, first_expert, experts_held, tile_rows)
    k = idx.shape[1]
    with jax.named_scope("dispatch"):
        rows = _take_rows(x, plan.row_assign // k, plan.row_used,
                          plan.dest, plan.here)
    with jax.named_scope("experts"):
        rows = expert_rows(rows, plan)
    with jax.named_scope("combine"):
        picked = _take_rows(rows, plan.dest, plan.here,
                            plan.row_assign[:, None],
                            plan.row_used[:, None])           # (N, k, D)
        out = jnp.einsum("nk,nkd->nd", weights.astype(x.dtype), picked,
                         preferred_element_type=jnp.float32)
    return out.astype(x.dtype), plan


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
    """Sum of ``rows_needed`` / ``rows_run`` and the largest
    ``load_max_over_mean`` over every routed layer's state in a
    variables-state tree (host values), or None where there is none."""
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
        ex = params["experts"]
        grouped_matmul = _pallas_moe().grouped_matmul
        tile_rows = _pallas_moe().TILE_ROWS

        def relu_experts(rows, plan):
            row_expert = jnp.repeat(plan.tile_expert, tile_rows)
            h = grouped_matmul(rows, ex["w1"].astype(rows.dtype),
                               plan.tile_expert, plan.num_tiles)
            h = jax.nn.relu(h + ex["b1"].astype(rows.dtype)[row_expert])
            y = grouped_matmul(h, ex["w2"].astype(rows.dtype),
                               plan.tile_expert, plan.num_tiles)
            return y + ex["b2"].astype(rows.dtype)[row_expert]

        with jax.named_scope("router"):
            idx, weights, probs = route_top_k(
                tokens, params["router"]["wg"], 1, normalise=False,
                scale=1.0)
        out, plan = routed_experts(
            tokens, idx, weights, relu_experts, first_expert=0,
            experts_held=self.num_experts, tile_rows=tile_rows)
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
    it (shared expert counted once).

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
    matmuls needed and ran) and ``load_max_over_mean``."""

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
            "aux_loss", "rows_needed", "rows_run", "load_max_over_mean")}
        return params, state, in_shape

    def apply(self, params, state, x, *, train=False, rng=None):
        tokens = x.reshape(-1, x.shape[-1])
        ex = params["experts"]
        grouped_matmul = _pallas_moe().grouped_matmul
        tile_rows = _pallas_moe().TILE_ROWS
        up, _ = self._up

        def experts(rows, plan):
            h = grouped_matmul(rows, ex[up].astype(rows.dtype),
                               plan.tile_expert, plan.num_tiles)
            if up == "gate_up":
                f = h.shape[-1] // 2
                h = jax.nn.silu(h[:, :f]) * h[:, f:]
            else:
                h = relu2(h)
            return grouped_matmul(h, ex["down"].astype(rows.dtype),
                                  plan.tile_expert, plan.num_tiles)

        with jax.named_scope("router"):
            idx, weights, probs = route_top_k(
                tokens, params["router"]["kernel"], self.experts_per_token,
                normalise=self.normalise, scale=self.routed_scale,
                bias=params["router"].get("bias"))
        out, plan = routed_experts(
            tokens, idx, weights, experts,
            first_expert=self.first_expert, experts_held=self.experts_held,
            tile_rows=tile_rows)
        if self.shared_hidden:
            with jax.named_scope("shared_expert"):
                shared = swiglu if up == "gate_up" else relu2_mlp
                out = out + shared(tokens, params["shared"][up],
                                   params["shared"]["down"])
        return out.reshape(x.shape), routing_state(idx, probs, plan,
                                                   tile_rows)

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
