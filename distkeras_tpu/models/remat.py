"""The recompute plan: what a training step's backward keeps and what it
runs again.

``Trainer(remat=True)`` says "this step may recompute to fit"; how much is
decided here, where the step is traced and the shapes are known, from
what the code can observe (shapes, dtypes, the device's memory limit):

1. every ``jax.checkpoint`` the step builds (:func:`checkpoint`) keeps the
   attention and scan kernels' outputs (``ops.pallas_attention`` and
   ``ops.pallas_ssm`` tag them with :func:`name_kernel_outputs`): a recomputed
   child rebuilds the kernels' inputs and reads ``out`` / ``lse`` (``y``
   and the chunks' states) as kept, so the forward kernels run once;
2. the unit that is kept or run again is an APPLICATION
   (:class:`Application`): a child of the model's ``Sequential`` where it
   runs once, a (pass, child) of a ``Looped`` stack, which runs its
   children several times over one set of parameters.  The last
   application is never wrapped: its backward begins where its forward
   ends, a checkpoint there buys no memory;
3. whole applications are kept, from the last one backward, while an
   estimate of the backward's peak fits the budget
   (:func:`keep_from_end`, :func:`peak`).  Late applications' residuals
   are freed first in the backward, while the gradients are still few.
   Where the device reports no limit (the CPU) no application but the
   last is kept, so a program does not depend on the host's memory.

The estimate is coarse and errs on the side of recompute; the compiled
program is the judge (:meth:`Plan.judge`, called by the trainer after the
compile it already waits for).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Callable, NamedTuple, Optional, Sequence

import jax
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.extend.core import Var

from ..obs.registry import default_registry
from ..obs.spans import default_tracer

#: the names the forward kernels' outputs carry out of their custom VJPs'
#: forward rules, by kernel family (``pallas_attention._vjp_fwd``: the
#: output and its row statistics; ``pallas_ssm._ssd_pallas_fwd`` and
#: ``pallas_gdn._gdn_pallas_fwd``: the output and the chunks' incoming
#: states); outside a checkpoint they lower to nothing
KERNEL_OUTPUT_NAMES = {"flash": ("flash_out", "flash_lse"),
                       "ssd": ("ssd_out", "ssd_state"),
                       "gdn": ("gdn_out", "gdn_state")}
KERNEL_OUTPUTS = tuple(n for names in KERNEL_OUTPUT_NAMES.values()
                       for n in names)
#: the share of the device's limit that the estimate may fill
FILL = 0.92
#: what a plan's estimate is the sum of (``Plan.estimate``'s keys; on the
#: record ``remat_<part>_bytes``)
ESTIMATE_PARTS = ("held", "residual", "grads")
#: a compiled program over this share of the limit: one application back
REFUSE = 0.95


class Application(NamedTuple):
    """One run of one layer in a step: ``call(params, state, x, rng=rng)
    -> (y, state)``.  The applications of a model are chained in the
    order they run, each on the one before's output; ``fan_out``: what is
    handed on is a tuple of that many outputs of this shape (the last
    application of a ``Looped`` stack: every pass's output)."""
    call: Callable
    params: Any
    state: Any
    fan_out: Optional[int] = None


_SIZING = threading.local()


@contextlib.contextmanager
def _sizing():
    _SIZING.on = True
    try:
        yield
    finally:
        _SIZING.on = False


def sizing() -> bool:
    """True while this thread traces a child only to size it: what a
    trace counts of the program it builds (``flash.*_tiles_*``) is not
    counted then."""
    return getattr(_SIZING, "on", False)


def name_kernel_outputs(*outputs, kernel: str = "flash"):
    """A forward kernel's outputs, tagged so that :func:`checkpoint`
    keeps them."""
    return tuple(checkpoint_name(o, n) for o, n in
                 zip(outputs, KERNEL_OUTPUT_NAMES[kernel], strict=True))


def checkpoint(fn):
    """``jax.checkpoint`` as the step builds it: everything is recomputed
    but the forward kernels' named outputs."""
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(
            *KERNEL_OUTPUTS))


def device_limit() -> Optional[int]:
    """``bytes_limit`` of the device a step compiles for; None where the
    backend reports none (the CPU)."""
    stats = jax.local_devices()[0].memory_stats()
    return int(stats["bytes_limit"]) if stats and stats.get("bytes_limit") \
        else None


def tree_bytes(tree) -> int:
    return sum(a.size * np.dtype(a.dtype).itemsize
               for a in jax.tree_util.tree_leaves(tree))


def peak_parts(sizes: Sequence[dict], first_kept: int) -> tuple:
    """``(residuals, gradients)`` bytes at the moment their sum is the
    most, in a backward that goes from the last child to the first.  A
    child's ``sizes`` entry: ``saved`` bytes held from its forward where
    it is checkpointed (its input, its named kernel outputs), ``whole``
    (no less) where it is kept and, kept or not, while it is
    differentiated, ``grads`` its parameters' gradients.  While child ``i`` is
    differentiated the earlier children hold what their forward left,
    child ``i`` holds all of its own (the residuals), and the gradients
    of ``i`` and the later ones are there."""
    held = [s["whole"] if i >= first_kept else s["saved"]
            for i, s in enumerate(sizes)]
    return max(((sum(held[:i]) + s["whole"],
                 sum(t["grads"] for t in sizes[i:]))
                for i, s in enumerate(sizes)), key=sum, default=(0, 0))


def peak(sizes: Sequence[dict], first_kept: int) -> int:
    """The most the forward's residuals and the gradients take at one
    time: the sum of :func:`peak_parts`."""
    return sum(peak_parts(sizes, first_kept))


def keep_from_end(sizes: Sequence[dict], budget: Optional[float]) -> int:
    """The planner: the index of the first child kept.  The last child is
    always kept (index ``len(sizes) - 1`` at the most); then children
    from the end backward, until the next would take :func:`peak` past
    ``budget`` (None: unknown, keep no other)."""
    first = max(len(sizes) - 1, 0)
    while budget is not None and first > 0 \
            and peak(sizes, first - 1) <= budget:
        first -= 1
    return first


#: primitives a compiler fuses into their consumers: a residual they
#: produce is held as the values it was computed from
_FUSED = frozenset({
    "add", "sub", "mul", "div", "neg", "max", "min", "exp", "exp2", "log",
    "log1p", "tanh", "logistic", "rsqrt", "sqrt", "square", "integer_pow",
    "pow", "abs", "sign", "select_n", "convert_element_type", "reshape",
    "squeeze", "expand_dims", "erf", "sin", "cos", "stop_gradient", "gt",
    "lt", "ge", "le", "eq", "ne", "and", "or", "not", "copy", "copy_p"})


def _held(jaxpr, n_out: int, inputs: Sequence):
    """``(whole, named)`` bytes of a child from the jaxpr of its
    ``jax.vjp`` (residuals after the first ``n_out`` outputs).  Whole:
    the residuals that are no parameter passed through (the child's own
    ``inputs`` count), each traced back through fused operations to the
    values a compiler would hold, counted once.  Named: the part of them
    that carries a kernel-output name (a checkpointed child holds that
    too)."""
    producer = {v: eqn for eqn in jaxpr.eqns for v in eqn.outvars}
    passed = (set(jaxpr.invars) | set(jaxpr.constvars)) - set(inputs)
    roots, named = {}, {}
    todo = [v for v in jaxpr.outvars[n_out:] if isinstance(v, Var)]
    seen = set()
    while todo:
        v = todo.pop()
        if v in seen or v in passed:
            continue
        seen.add(v)
        eqn = producer.get(v)
        size = tree_bytes(v.aval)
        operands = [i for i in getattr(eqn, "invars", ())
                    if isinstance(i, Var)]
        if eqn is not None and eqn.primitive.name == "name" \
                and eqn.params["name"] in KERNEL_OUTPUTS:
            named[v] = size
        elif eqn is not None and eqn.primitive.name in _FUSED \
                and any(tree_bytes(i.aval) >= size for i in operands):
            # held as its operands (unless they are all smaller, a
            # broadcast: then the result itself is what is held)
            todo.extend(operands)
        else:
            roots[v] = size
    return sum(roots.values()) + sum(named.values()), sum(named.values())


class Plan:
    """The recompute plan of one step program.

    ``budget``: the bytes the forward's residuals and the gradients may
    take at one time (None: unknown, keep no application but the last).
    ``make_local_step`` sets it from the device's limit less what the
    step holds throughout; a test passes its own.  After a trace
    ``first_kept`` / ``children`` / ``bytes_estimated`` say what was
    decided (``children`` counts applications: a child that runs four
    times is four), ``sizes`` the applications' estimates, ``estimate``
    the three parts ``bytes_estimated`` is the sum of (all 0 where
    nothing was estimated); ``stepped_back`` counts the applications a
    judge took back."""

    def __init__(self, budget: Optional[float] = None):
        self.budget = budget
        self.limit = None      # the device's, where ``fit`` was told one
        self.held = 0          # what the step holds throughout
        self.stepped_back = 0
        self.children = 0
        self.first_kept = 0
        self.sizes: list = []
        self.estimate = dict.fromkeys(ESTIMATE_PARTS, 0)
        self.bytes_compiled = 0
        self.tracer = None     # the trainer's, for ``train.remat_plan``

    # -- the budget ---------------------------------------------------------
    def fit(self, held: int, limit: Optional[int]) -> None:
        """``held``: the step's arguments and the cast copies; ``limit``:
        :func:`device_limit`."""
        self.held, self.limit = int(held), limit
        self.budget = None if limit is None else FILL * limit - held

    # -- the decision, at trace time ------------------------------------------
    def first_kept_of(self, applications, x, rng) -> int:
        """Index of the first of a model's ``applications`` (in the order
        they run, the first on ``x``) left unwrapped; counts the decision
        into the registry."""
        n = len(applications)
        span = (self.tracer or default_tracer()).span
        with span("train.remat_plan", children=n) as record:
            first, parts = n - 1, (0, 0, 0)
            if self.budget is not None and n > 1:
                self.sizes = _children_sizes(applications, x, rng)
                first = min(keep_from_end(self.sizes, self.budget)
                            + self.stepped_back, n - 1)
                parts = (self.held, *peak_parts(self.sizes, first))
            self._decided(n, first, parts)
            record.update(self.record(), by_kind=self.by_kind())
        return first

    def whole_forward(self) -> None:
        """A model that is no ``Sequential``: one checkpoint around it."""
        self._decided(1, 1)

    def _decided(self, children: int, first_kept: int,
                 parts=(0, 0, 0)) -> None:
        """``parts``: the estimate's, in ``ESTIMATE_PARTS``' order; zeros
        where nothing was estimated."""
        self.children, self.first_kept = children, first_kept
        self.estimate = dict(zip(ESTIMATE_PARTS, map(int, parts),
                                 strict=True))
        registry = default_registry()
        registry.counter("remat.children_kept").inc(children - first_kept)
        registry.counter("remat.children_recomputed").inc(first_kept)

    @property
    def bytes_estimated(self) -> int:
        return sum(self.estimate.values())

    def by_kind(self) -> list:
        """The applications by kind, in the order a kind first runs: for
        each its name (the classes of the layer and of what it holds, as
        the step's scopes spell them), the index of its first
        application, how many there are and how many of them are kept,
        and an application's ``whole`` and ``saved`` bytes.  Read off
        ``sizes``: empty where nothing was sized."""
        rows = {}
        for i, size in enumerate(self.sizes):
            row = rows.setdefault(
                (size["kind"], size["whole"], size["saved"]),
                {"kind": size["kind"], "first": i, "count": 0, "kept": 0,
                 "whole": size["whole"], "saved": size["saved"]})
            row["count"] += 1
            row["kept"] += i >= self.first_kept
        return list(rows.values())

    # -- the judge, after the compile -------------------------------------------
    def judge(self, compiled_bytes: int) -> bool:
        """Records the compiled program's size (``obs.profile
        .program_memory``'s ``program_bytes``: arguments + outputs −
        aliased + temporaries, as the compiler counts them); True where
        it passes ``REFUSE`` of the limit and an application can still be
        taken back: the caller compiles again."""
        self.bytes_compiled = int(compiled_bytes)
        return (self.limit is not None
                and compiled_bytes > REFUSE * self.limit
                and self.step_back())

    def step_back(self) -> bool:
        """One more application recomputed at the next trace; False where
        none is left to take back."""
        if self.first_kept >= self.children - 1:
            return False
        self.stepped_back += 1
        self.first_kept += 1
        return True

    def record(self) -> dict:
        """What was decided and what it came to, as the ``jit_compile``
        record carries it: the counts, the estimate and the three parts
        it is the sum of (what the step holds throughout; at the
        application where :func:`peak` is reached the residuals and the
        gradients), and the compiled program's size."""
        return {"remat_children_kept": self.children - self.first_kept,
                "remat_children_recomputed": self.first_kept,
                "remat_bytes_estimated": self.bytes_estimated,
                **{f"remat_{part}_bytes": n
                   for part, n in self.estimate.items()},
                "remat_bytes_compiled": self.bytes_compiled}


def _children_sizes(applications, x, rng) -> list:
    """For each application the bytes :func:`peak` reads: ``saved`` (its
    input and its named kernel outputs: what a checkpoint holds),
    ``whole`` (what its backward holds where it is kept) and ``grads`` (a
    float32 gradient a parameter; parameters applied several times count
    at their last application, where the backward first meets them).
    Applications of equal call, shapes and dtypes are traced once."""
    shaped = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    x, rng = shaped(x), shaped(rng)
    known, sizes = {}, []
    for call, params, state, fan_out in applications:
        args = (shaped(params), shaped(state), x, rng)
        key = (_call_key(call), str(jax.tree_util.tree_structure(args)),
               tuple((a.shape, str(a.dtype))
                     for a in jax.tree_util.tree_leaves(args)))
        if key not in known:
            with _sizing():
                known[key] = _trace_child(call, *args)
        out, whole, named = known[key]
        saved = tree_bytes(x) + named
        sizes.append({"saved": saved, "whole": max(whole, saved),
                      "kind": _kind(call)})
        x = out if fan_out is None else (out,) * fan_out
    met = set()
    for size, application in zip(reversed(sizes), reversed(applications)):
        leaves = [a for a in jax.tree_util.tree_leaves(application.params)
                  if id(a) not in met]
        met.update(id(a) for a in leaves)
        size["grads"] = 4 * sum(a.size for a in leaves)
    return sizes


def _layer_of(call):
    """The layer whose bound ``apply`` ``call`` is a partial of, or None."""
    return getattr(getattr(call, "func", None), "__self__", None)


def _call_key(call):
    """A child's identity for the cache: its layer's configuration where
    ``call`` is a partial of a bound ``apply``, else the call itself."""
    layer = _layer_of(call)
    if layer is None:
        return id(call)
    return (type(layer).__name__, repr(layer.config()),
            repr(sorted(getattr(call, "keywords", {}).items())))


def _kind(call) -> str:
    """A child's name in ``Plan.by_kind``: its layer's class and those of
    the layers inside it, each once, as the step's scopes spell them
    (``residual/sequential/rmsnorm/multiheadattention``)."""
    layer = _layer_of(call)
    if layer is None:
        return getattr(call, "__name__", type(call).__name__)
    return "/".join(dict.fromkeys(type(sub).__name__.lower()
                                  for sub in layer.iter_layers()))


def _trace_child(call, p, s, x, rng):
    """``(output's shape, whole, named)`` of one child."""
    def residuals(p, s, x, rng):
        y, vjp, _ = jax.vjp(lambda p, x: call(p, s, x, rng=rng), p, x,
                            has_aux=True)
        return y, jax.tree_util.tree_leaves(vjp)

    closed, (out, _) = jax.make_jaxpr(residuals, return_shape=True)(
        p, s, x, rng)
    before = len(jax.tree_util.tree_leaves((p, s)))
    inputs = closed.jaxpr.invars[
        before:before + len(jax.tree_util.tree_leaves(x))]
    return (out,) + _held(closed.jaxpr, len(jax.tree_util.tree_leaves(out)),
                          inputs)
