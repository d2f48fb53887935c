"""Synchronous SPMD training engine — the TPU formulation of dist-keras.

Every reference algorithm (reference ``distkeras/workers.py`` +
``distkeras/parameter_servers.py``) is re-expressed here as:

  * a **local rule**: w minibatch steps of local optimization inside a
    ``lax.scan`` (w = the reference's ``communication_window``), and
  * a **communication rule** at the window edge: one XLA collective
    (``pmean``/``psum``) over the ``workers`` mesh axis replacing the entire
    socket pull/commit round-trip of the reference's parameter server.

The whole epoch — windows × local steps × collectives — is ONE jit-compiled
program: no host round-trips, collectives ride ICI, XLA overlaps the
allreduce with adjacent compute.  Staleness is identically zero in this
formulation (every window edge is a barrier), which is the synchronous limit
of each algorithm; the faithful staleness-preserving semantics live in
``distkeras_tpu.ps`` (async host parameter server).

Center/local variables are FULL variable pytrees (params + mutable state),
mirroring the reference where Keras ``get_weights()`` — the unit of
pull/commit — includes BatchNorm running statistics.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..models import remat as remat_plans
from ..models.layers import Sequential, state_leaves
from .mesh import make_mesh, shard_map

Tree = Any


# ---------------------------------------------------------------------------
# pytree helpers
# ---------------------------------------------------------------------------

def tmap(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def tree_sub(a, b):
    return tmap(lambda x, y: x - y, a, b)


def tree_add(a, b):
    return tmap(lambda x, y: x + y, a, b)


def tree_scale(a, s):
    return tmap(lambda x: x * s, a)


def _inexact(x) -> bool:
    """Communication rules act on floating-point leaves only: integer/bool
    variable state (Keras SeedGenerator counters, step counters, ...) has no
    meaningful average/sum and must keep its dtype and worker-local value
    across window edges.  Works on jnp and np leaves alike — the async PS
    (``ps.servers`` / ``ps.workers``) shares this predicate."""
    return jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact)


def adopt_float_leaves(source: Tree, local: Tree) -> Tree:
    """``local`` with its floating leaves replaced by ``source``'s; integer/
    bool leaves keep the local value (see ``_inexact``).  The single merge
    rule for every window-edge/pull site (sync algorithms, async workers)."""
    return tmap(lambda s, l: s if _inexact(l) else l, source, local)


def _squeeze0(tree):
    return tmap(lambda x: x[0], tree)


def _expand0(tree):
    return tmap(lambda x: x[None], tree)


# ---------------------------------------------------------------------------
# the local minibatch step (shared by sync engine and async PS workers)
# ---------------------------------------------------------------------------

def aux_losses(state: Tree) -> list:
    """Collect every ``aux_loss`` leaf from a variables-state tree (each
    ``MoEDense`` writes its router load-balance scalar there)."""
    return state_leaves(state, "aux_loss")


#: parameter keys whose leaves (and everything under them) a mixed-precision
#: step hands to the forward uncast, as the float32 master weights: a
#: routed layer scores its experts in float32 (``ops.moe.route_top_k``:
#: ``router``), and a state-space mixer's decay rates, step bias and skip
#: are float32 in the published layer (``ops.ssm.Mamba2Mixer``); an exit
#: gate's values become chances that are multiplied pass over pass
#: (``models.layers.ExitHeads``: ``exit_gate``)
FLOAT32_KEYS = frozenset({"router", "A_log", "dt_bias", "D", "exit_gate"})


def make_local_step(model, loss_fn: Callable,
                    optimizer: optax.GradientTransformation,
                    compute_dtype=None, remat: bool = False,
                    aux_weight: float = 0.0):
    """One minibatch of local optimization as a pure scan-able function:
    ``step((variables, opt_state, rng), (x, y)) -> (carry', loss)``.

    This is the reference's ``model.train_on_batch`` (reference
    ``distkeras/workers.py``) as a jit-compiled value_and_grad + optax
    update — the MXU hot loop.

    ``remat=True``: the step may recompute activations in the backward
    pass to fit the device — the FLOPs-for-memory trade for models whose
    activation footprint, not weights, is what OOMs.  How much is the
    step's ``models.remat.Plan`` (``step.remat_plan``), decided at trace
    time from the shapes and the device's memory limit: a ``Sequential``
    model is checkpointed application by application (a child that runs
    once is one, a ``Looped`` child one a pass and child of its body),
    the attention and scan kernels' outputs are kept, the last
    application is not wrapped, and whole applications are kept from the
    end backward while the backward's estimated peak (residuals and
    gradients) fits the limit less the step's arguments and cast copies
    (none where the device reports no limit); any other layer is wrapped
    whole under the same policy.  ``remat=False``: the forward is called
    as it is.  ``loss_fn`` gets the model's output as the model gave it,
    an array or a structure of them.

    ``aux_weight > 0`` folds ``aux_weight * Σ state['aux_loss']`` (the
    MoE router load-balance losses) into the objective — the opt-in
    mitigation for router/expert collapse in long MoE runs (ADVICE r3);
    the default keeps the reference-parity task-loss-only behavior.
    """

    plan = remat_plans.Plan() if remat else None

    def forward(params, state, x, rng):
        apply = functools.partial(model.layer.apply, train=True)
        if plan is None:
            return apply(params, state, x, rng=rng)
        if isinstance(model.layer, Sequential):
            return apply(params, state, x, rng=rng, remat=plan)
        plan.whole_forward()
        return remat_plans.checkpoint(apply)(params, state, x, rng=rng)

    def cast_floats(tree):
        # leaves under a ``FLOAT32_KEYS`` key stay as they are
        def cast(path, a):
            if not jnp.issubdtype(a.dtype, jnp.floating) or any(
                    getattr(p, "key", None) in FLOAT32_KEYS for p in path):
                return a
            return a.astype(compute_dtype)

        return jax.tree_util.tree_map_with_path(cast, tree)

    def step(carry, batch):
        variables, opt_state, rng = carry
        x, y = batch
        if compute_dtype is not None and \
                jnp.issubdtype(x.dtype, jnp.floating):
            x = x.astype(compute_dtype)
        rng, sub = jax.random.split(rng)

        def loss_of(params):
            # mixed precision: master params stay f32 in the optimizer;
            # the forward sees compute_dtype copies (covers token-input
            # models too, where no float x exists to derive dtype from —
            # layers cast their weights to the activation dtype)
            fwd_params = params
            if compute_dtype is not None:
                with jax.named_scope("cast_params"):
                    fwd_params = cast_floats(params)
            if plan is not None:
                # what the step holds from end to end: its arguments
                # and the cast copies
                copies = [c for c, p in zip(
                    jax.tree_util.tree_leaves(fwd_params),
                    jax.tree_util.tree_leaves(params)) if c is not p]
                plan.fit(remat_plans.tree_bytes((carry, batch, copies)),
                         remat_plans.device_limit())
            out, new_state = forward(fwd_params, variables["state"], x, sub)
            with jax.named_scope("loss"):
                loss_val = loss_fn(out, y)
            if aux_weight:
                aux = aux_losses(new_state)
                if aux:
                    loss_val = loss_val + aux_weight * sum(aux)
            return loss_val, new_state

        (loss_val, new_state), grads = jax.value_and_grad(
            loss_of, has_aux=True)(variables["params"])
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, opt_state, variables["params"])
            params = optax.apply_updates(variables["params"], updates)
        return ({"params": params, "state": new_state}, opt_state, rng), loss_val

    step.remat_plan = plan
    return step


def make_window_fn(model, loss_fn, optimizer, compute_dtype=None,
                   remat: bool = False, aux_weight: float = 0.0):
    """jit-compiled window scan: ``(variables, opt_state, rng, xs, ys) ->
    (variables, opt_state, rng, losses)`` over the leading (steps) axis —
    the unit of work between two parameter-server interactions.

    Carry buffers are donated: params/opt-state update in place in HBM
    (callers all rebind to the outputs, measured ~4% on ResNet-20).
    """
    step = make_local_step(model, loss_fn, optimizer, compute_dtype, remat,
                           aux_weight)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def run(variables, opt_state, rng, xs, ys):
        (variables, opt_state, rng), losses = lax.scan(
            step, (variables, opt_state, rng), (xs, ys))
        return variables, opt_state, rng, losses

    run.remat_plan = step.remat_plan
    return run


# ---------------------------------------------------------------------------
# communication rules (one per reference algorithm)
# ---------------------------------------------------------------------------

class SyncAlgorithm:
    """Window-edge communication rule.

    ``communicate(center, local, axis)`` runs inside ``shard_map`` (per
    device, collectives available) and returns ``(new_center, new_local)``.
    """

    #: whether workers restart each window from the (new) center variable
    name = "base"

    def communicate(self, center: Tree, local: Tree, axis: str):
        raise NotImplementedError


class NoCommSync(SyncAlgorithm):
    """No inter-worker communication (AveragingTrainer / EnsembleTrainer):
    workers train fully independently; any averaging happens after training
    (reference ``distkeras/trainers.py:AveragingTrainer.average_models``)."""

    name = "none"

    def communicate(self, center, local, axis):
        return center, local


class AdagSync(SyncAlgorithm):
    """ADAG (reference ``ADAGWorker`` + ``ADAGParameterServer``): workers
    accumulate a window of updates, commit the accumulated delta normalized
    by the worker count.  Synchronous limit: center ← center +
    mean_k(local_k − center) ≡ pmean of worker models; workers re-pull the
    new center.  This is allreduce-mean windowed SGD — the flagship mapping
    onto the MXU/ICI."""

    name = "adag"

    def communicate(self, center, local, axis):
        new_center = tmap(
            lambda c, l: lax.pmean(l, axis) if _inexact(l) else c,
            center, local)
        return new_center, adopt_float_leaves(new_center, local)


class DownpourSync(SyncAlgorithm):
    """DOWNPOUR (reference ``DOWNPOURWorker`` + ``DeltaParameterServer``):
    each worker commits Δ_k = local_k − center and the server adds every
    commit in full (no normalization).  Synchronous limit: center ← center +
    Σ_k Δ_k; workers re-pull."""

    name = "downpour"

    def communicate(self, center, local, axis):
        new_center = tmap(
            lambda c, l: c + lax.psum(l - c, axis) if _inexact(l) else c,
            center, local)
        return new_center, adopt_float_leaves(new_center, local)


class DynSgdSync(SyncAlgorithm):
    """DynSGD (reference ``DynSGDParameterServer``): commit scaled by
    1/(staleness+1).  Every window edge is a barrier here, so staleness ≡ 0
    and the scale is 1 — documented explicitly rather than silently; the
    staleness-sensitive behavior is exercised by the async PS path."""

    name = "dynsgd"
    staleness = 0

    def communicate(self, center, local, axis):
        scale = 1.0 / (self.staleness + 1)
        new_center = tmap(
            lambda c, l: c + lax.psum((l - c) * scale, axis)
            if _inexact(l) else c,
            center, local)
        return new_center, adopt_float_leaves(new_center, local)


class EasgdSync(SyncAlgorithm):
    """EASGD elastic averaging (reference ``AEASGDWorker`` /
    ``EAMSGDWorker``; Zhang, Choromanska, LeCun 2015): every τ steps the
    elastic force E_k = α(local_k − center) pulls the worker toward the
    center and the center toward the workers:
        local_k ← local_k − E_k ;  center ← center + Σ_k E_k.
    Workers KEEP their local model across windows (exploration) — this is
    the one family where local ≠ center by design.  EAMSGD differs only in
    the local optimizer (Nesterov momentum), not in this rule."""

    name = "easgd"

    def __init__(self, alpha: float):
        self.alpha = float(alpha)

    def communicate(self, center, local, axis):
        new_center = tmap(
            lambda c, l: c + lax.psum(self.alpha * (l - c), axis)
            if _inexact(l) else c,
            center, local)
        new_local = tmap(
            lambda c, l: l - self.alpha * (l - c) if _inexact(l) else l,
            center, local)
        return new_center, new_local


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

class EpochResult(NamedTuple):
    center: Tree      # variables pytree (replicated)
    local: Tree       # variables pytree, leading axis = workers
    opt_state: Tree   # leading axis = workers
    rngs: jnp.ndarray
    losses: jnp.ndarray  # (workers, n_windows, window)


class SyncEngine:
    """Builds jit-compiled epoch programs for a (model, loss, optimizer,
    algorithm) tuple over a worker mesh."""

    def __init__(self, model, loss_fn: Callable, optimizer: optax.GradientTransformation,
                 algo: SyncAlgorithm, num_workers: int, window: int,
                 mesh: Optional[Mesh] = None, axis: str = "workers",
                 compute_dtype=None, remat: bool = False,
                 aux_weight: float = 0.0):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.algo = algo
        self.num_workers = int(num_workers)
        self.window = int(window)
        self.axis = axis
        self.mesh = mesh if mesh is not None else make_mesh(num_workers, (axis,))
        self.compute_dtype = compute_dtype
        self._local_step = make_local_step(model, loss_fn, optimizer,
                                           compute_dtype, remat, aux_weight)

    # -- distributed epoch --------------------------------------------------
    def epoch_fn(self):
        """jit-compiled: (center, local, opt_state, rngs, xs, ys) -> EpochResult.

        Global shapes: center replicated; local/opt_state leading axis =
        workers; rngs (workers, 2); xs/ys (workers, n_windows, window,
        batch, ...).
        """
        axis = self.axis

        def per_device(center, local, opt_state, rng, xs, ys):
            local, opt_state, rng = (_squeeze0(local), _squeeze0(opt_state),
                                     rng[0])
            xs, ys = xs[0], ys[0]

            def window_step(carry, batch_window):
                center, local, opt_state, rng = carry
                wx, wy = batch_window
                (local, opt_state, rng), losses = lax.scan(
                    self._local_step, (local, opt_state, rng), (wx, wy))
                center, local = self.algo.communicate(center, local, axis)
                return (center, local, opt_state, rng), losses

            (center, local, opt_state, rng), losses = lax.scan(
                window_step, (center, local, opt_state, rng), (xs, ys))
            return (center, _expand0(local), _expand0(opt_state),
                    rng[None], losses[None])

        mapped = shard_map(
            per_device, mesh=self.mesh,
            in_specs=(P(), P(axis), P(axis), P(axis), P(axis), P(axis)),
            out_specs=(P(), P(axis), P(axis), P(axis), P(axis)),
            check_vma=False)

        @jax.jit
        def run(center, local, opt_state, rngs, xs, ys):
            return EpochResult(*mapped(center, local, opt_state, rngs, xs, ys))

        run.remat_plan = self._local_step.remat_plan
        return run

    # -- streaming window ---------------------------------------------------
    def window_fn(self):
        """jit-compiled SINGLE window: (center, local, opt_state, rngs, wx,
        wy) -> EpochResult with losses (workers, window).

        The disk-streaming trainers drive this once per communication
        window (the host assembles window w+1 while the devices train
        window w); the collective at the window edge is identical to the
        epoch program's.  Model/opt state is donated — it updates in place
        in HBM across the host loop.
        """
        axis = self.axis

        def per_device(center, local, opt_state, rng, wx, wy):
            local, opt_state, rng = (_squeeze0(local), _squeeze0(opt_state),
                                     rng[0])
            (local, opt_state, rng), losses = lax.scan(
                self._local_step, (local, opt_state, rng), (wx[0], wy[0]))
            center, local = self.algo.communicate(center, local, axis)
            return (center, _expand0(local), _expand0(opt_state),
                    rng[None], losses[None])

        mapped = shard_map(
            per_device, mesh=self.mesh,
            in_specs=(P(), P(axis), P(axis), P(axis), P(axis), P(axis)),
            out_specs=(P(), P(axis), P(axis), P(axis), P(axis)),
            check_vma=False)

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def run(center, local, opt_state, rngs, wx, wy):
            return EpochResult(*mapped(center, local, opt_state, rngs, wx, wy))

        run.remat_plan = self._local_step.remat_plan
        return run

