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
from typing import Any, Optional

import jax
import jax.numpy as jnp
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
    """Single-device reference formula: every token through its top-1
    expert, no capacity limit (nothing to overflow without a dispatch
    buffer).  Same math the sharded path computes for kept tokens."""
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
# layer API integration (models.layers contract)
# ---------------------------------------------------------------------------

from ..models.layers import Layer, register  # noqa: E402


@register
class MoEDense(Layer):
    """Switch-MoE feed-forward as a model layer: a drop-in for the
    transformer FF block (wrap in ``Residual`` like any FF).

    Runs the dense per-token formula (:func:`dense_moe`) — identical math
    to the ``ep``-sharded path, single-program — unless a mesh is
    attached (``layer.mesh = mesh``; find instances via
    ``model.iter_layers()``), which switches execution to
    :func:`switch_moe_sharded` over its ``ep`` axis.  The mesh is
    runtime placement, not architecture, so it is deliberately NOT part
    of the serialized config (a deserialized model runs dense until a
    mesh is re-attached).

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
            out, aux = switch_moe_sharded(
                self.mesh, params, tokens,
                capacity_factor=self.capacity_factor)
        else:
            out, aux = dense_moe(params, tokens)
        return out.reshape(x.shape), {"aux_loss": aux.astype(jnp.float32)}

    def get_config(self):
        return {"num_experts": self.num_experts, "d_hidden": self.d_hidden,
                "capacity_factor": self.capacity_factor}
