"""TPU-native layer/model API.

This is the replacement for the reference's reliance on Keras model objects
(dist-keras ships Keras models to Spark executors and calls
``model.train_on_batch``; see reference ``distkeras/workers.py`` and
``distkeras/utils.py:serialize_keras_model``).  Here a model is a pure
function pair:

    variables = model.init(rng)                     # {'params': ..., 'state': ...}
    y, new_state = model.apply(variables, x, train=True, rng=rng)

``params`` are trainable pytrees (differentiated through), ``state`` holds
non-trainable mutables (BatchNorm running statistics).  Everything lowers to
jit-friendly JAX: static shapes, ``lax.scan`` recurrence, no Python control
flow on traced values — so the whole train step compiles onto the TPU MXU.

Layer configs are JSON-serializable (``get_config``/``from_config``) which
gives us the reference's architecture-JSON + weight-list serialization
contract (reference ``distkeras/utils.py:serialize_keras_model``).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.registry import default_registry

LAYER_REGISTRY: dict[str, type] = {}


def register(cls):
    """Register a layer class for config-based (de)serialization."""
    LAYER_REGISTRY[cls.__name__] = cls
    return cls


def layer_from_config(cfg: dict) -> "Layer":
    cls = LAYER_REGISTRY[cfg["class"]]
    return cls.from_config(cfg["config"])


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def glorot_uniform(rng, shape, dtype=jnp.float32, fan_in=None, fan_out=None):
    if fan_in is None or fan_out is None:
        receptive = math.prod(shape[:-2]) if len(shape) > 2 else 1
        fan_in = shape[-2] * receptive if len(shape) >= 2 else shape[-1]
        fan_out = shape[-1] * receptive if len(shape) >= 2 else shape[-1]
    limit = math.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(rng, shape, dtype, -limit, limit)


def he_normal(rng, shape, dtype=jnp.float32):
    receptive = math.prod(shape[:-2]) if len(shape) > 2 else 1
    fan_in = (shape[-2] * receptive) if len(shape) >= 2 else shape[-1]
    std = math.sqrt(2.0 / fan_in)
    return jax.random.normal(rng, shape, dtype) * std


def uniform_scale(rng, shape, scale=0.05, dtype=jnp.float32):
    return jax.random.uniform(rng, shape, dtype, -scale, scale)


def relu2(x):
    """Squared ReLU (So et al. 2021, Primer)."""
    return jnp.square(jax.nn.relu(x))


ACTIVATIONS: dict[str, Callable] = {
    "linear": lambda x: x,
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
    "softmax": lambda x: jax.nn.softmax(x, axis=-1),
    "log_softmax": lambda x: jax.nn.log_softmax(x, axis=-1),
    "elu": jax.nn.elu,
    "silu": jax.nn.silu,
    "leaky_relu": jax.nn.leaky_relu,
    "relu2": relu2,
}


def get_activation(name_or_fn):
    if name_or_fn is None:
        return ACTIVATIONS["linear"]
    if callable(name_or_fn):
        return name_or_fn
    return ACTIVATIONS[name_or_fn]


def activation_config(name_or_fn):
    """Serializable form of an activation spec; refuses silent loss."""
    if name_or_fn is None or isinstance(name_or_fn, str):
        return name_or_fn
    for name, fn in ACTIVATIONS.items():
        if fn is name_or_fn:
            return name
    raise ValueError(
        f"cannot serialize custom activation {name_or_fn!r}; use a registered "
        f"name ({', '.join(ACTIVATIONS)}) or an Activation layer subclass")


# ---------------------------------------------------------------------------
# base
# ---------------------------------------------------------------------------

def _scope(layer):
    """``jax.named_scope`` a container holds while it applies ``layer``:
    the child's class name in lower case, so a profiler trace and the
    compiled program's ``op_name``s say which layer an operation belongs
    to.  Metadata only, and never a layer INDEX: twelve blocks share one
    path, so a trace still sums each kernel and fusion kind in one row."""
    return jax.named_scope(type(layer).__name__.lower())


class Layer:
    """Base layer: pure-functional init/apply with explicit shapes.

    ``init(rng, in_shape) -> (params, state, out_shape)`` where shapes
    exclude the leading batch dimension.  ``apply(params, state, x, ...)``
    returns ``(y, new_state)``.  Shapes are static so XLA traces once.
    """

    def init(self, rng, in_shape: tuple) -> tuple[Any, Any, tuple]:
        return {}, {}, self.out_shape(in_shape)

    def out_shape(self, in_shape: tuple) -> tuple:
        return in_shape

    def apply(self, params, state, x, *, train: bool = False, rng=None):
        raise NotImplementedError

    # -- cached autoregressive decode (causal LMs) --------------------------

    #: layers that mix information ACROSS the time axis set this True;
    #: the decode protocol refuses stacks containing a time-mixing layer
    #: without its own apply_decode override (the pointwise default would
    #: silently compute the wrong thing on per-token input)
    time_mixing = False

    #: layers whose training forward consumes randomness (Dropout) set
    #: this True; contexts that cannot thread per-layer rng (the GPipe
    #: stage schedule) refuse them instead of silently running eval-mode
    rng_in_train = False

    def init_cache(self, batch: int, in_shape: tuple):
        """Decode-cache pytree for one-position-at-a-time generation
        (``models.generation``), or None for cache-free layers.
        ``in_shape`` is the layer's input shape INCLUDING the time axis
        (same walk as ``init``); the time extent bounds the cache."""
        return None

    def apply_decode(self, params, state, x, cache, pos):
        """One-token decode step: ``x`` is (B, ...) for position ``pos``
        (no time axis) → ``(y, cache)``.  Default covers time-pointwise
        layers (Dense, LayerNorm, Embedding, activations, MoE FF — their
        ``apply`` treats the time axis elementwise, so per-token input is
        just a batch); time-MIXING layers must override (see
        ``MultiHeadAttention``) — ``models.generation`` enforces this via
        ``time_mixing`` and falls back to full-context recompute."""
        y, _ = self.apply(params, state, x, train=False)
        return y, cache

    def apply_prefill(self, params, state, x, cache):
        """Batched prefill: run the FULL-sequence forward (x has its time
        axis) while filling the decode cache → ``(y, cache)``.  Default
        (cache-free layers) is the ordinary inference apply; caching
        layers override to also record K/V (one batched forward instead
        of per-token prefill steps)."""
        y, _ = self.apply(params, state, x, train=False)
        return y, cache

    # -- what a recompute plan keeps or runs again ---------------------------

    def applications(self, params, state, *, train: bool = False) -> list:
        """The layer as the applications a step's recompute plan decides
        on (``models.remat.Application``), in the order they run: one for
        a plain layer, one a pass and child for a ``Looped``."""
        from .remat import Application
        return [Application(functools.partial(self.apply, train=train),
                            params, state)]

    def apply_calls(self, calls, params, state, x, *, rng=None):
        """``apply`` through the calls of its :meth:`applications`, as the
        plan left them (under ``remat.checkpoint`` or as they were)."""
        (call,) = calls
        return call(params, state, x, rng=rng)

    def decode_logits(self, out):
        """The logits a user decodes from, of what ``apply`` gave."""
        return out

    def iter_layers(self):
        """Yield this layer and every nested layer (depth-first through
        the composition attributes: ``layers``, ``body``, ``inner``,
        ``shortcut``, ``closing``).  The public way to find/configure
        layers inside a built model — e.g. attaching a mesh to every
        ``MoEDense``."""
        yield self
        for attr in ("layers", "body"):
            for sub in getattr(self, attr, None) or []:
                yield from sub.iter_layers()
        for attr in ("inner", "shortcut", "closing"):
            sub = getattr(self, attr, None)
            if isinstance(sub, Layer):
                yield from sub.iter_layers()

    # -- config serde -------------------------------------------------------
    def get_config(self) -> dict:
        return {}

    @classmethod
    def from_config(cls, cfg: dict) -> "Layer":
        return cls(**cfg)

    def config(self) -> dict:
        return {"class": type(self).__name__, "config": self.get_config()}

    def __repr__(self):
        args = ", ".join(f"{k}={v!r}" for k, v in self.get_config().items())
        return f"{type(self).__name__}({args})"


# ---------------------------------------------------------------------------
# core layers
# ---------------------------------------------------------------------------

@register
class Dense(Layer):
    def __init__(self, units: int, activation=None, use_bias: bool = True):
        self.units = int(units)
        self.activation = activation
        self.use_bias = use_bias
        self._act = get_activation(activation)

    def init(self, rng, in_shape):
        (d,) = in_shape[-1:]
        kr, _ = jax.random.split(rng)
        params = {"kernel": glorot_uniform(kr, (d, self.units))}
        if self.use_bias:
            params["bias"] = jnp.zeros((self.units,))
        return params, {}, self.out_shape(in_shape)

    def out_shape(self, in_shape):
        return (*in_shape[:-1], self.units)

    def apply(self, params, state, x, *, train=False, rng=None):
        y = x @ params["kernel"].astype(x.dtype)
        if self.use_bias:
            y = y + params["bias"].astype(x.dtype)
        return self._act(y), state

    def get_config(self):
        return {
            "units": self.units,
            "activation": activation_config(self.activation),
            "use_bias": self.use_bias,
        }


@register
class RMSNorm(Layer):
    """Root-mean-square norm (Zhang & Sennrich 2019): ``x / sqrt(mean(x²)
    + epsilon) * scale``, no mean subtracted and no bias; the statistics
    are taken in float32 whatever the activations' dtype."""

    def __init__(self, epsilon: float = 1e-6):
        self.epsilon = float(epsilon)

    def init(self, rng, in_shape):
        return {"scale": jnp.ones((in_shape[-1],))}, {}, in_shape

    def apply(self, params, state, x, *, train=False, rng=None):
        xf = x.astype(jnp.float32)
        y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                           + self.epsilon)
        return (y * params["scale"].astype(jnp.float32)).astype(x.dtype), \
            state

    def get_config(self):
        return {"epsilon": self.epsilon}


def swiglu(x, gate_up, down):
    """``(silu(x W_gate) * (x W_up)) W_down`` with W_gate and W_up side
    by side in ``gate_up`` (D, 2F): one matmul, split in the middle."""
    h = x @ gate_up.astype(x.dtype)
    f = h.shape[-1] // 2
    return (jax.nn.silu(h[..., :f]) * h[..., f:]) @ down.astype(x.dtype)


def relu2_mlp(x, up, down):
    """``relu(x W_up)^2 W_down``: the ungated squared-ReLU feed-forward."""
    return relu2(x @ up.astype(x.dtype)) @ down.astype(x.dtype)


@register
class SwiGLU(Layer):
    """Gated feed-forward (Shazeer 2020): D -> ``units`` twice (gate and
    up, one fused (D, 2·units) matrix), silu(gate) * up, back to D.  No
    bias."""

    def __init__(self, units: int):
        self.units = int(units)

    def init(self, rng, in_shape):
        d = in_shape[-1]
        k1, k2 = jax.random.split(rng)
        return {"gate_up": glorot_uniform(k1, (d, 2 * self.units),
                                          fan_in=d, fan_out=self.units),
                "down": glorot_uniform(k2, (self.units, d))}, {}, in_shape

    def apply(self, params, state, x, *, train=False, rng=None):
        return swiglu(x, params["gate_up"], params["down"]), state

    def get_config(self):
        return {"units": self.units}


@register
class Activation(Layer):
    def __init__(self, activation: str):
        self.activation = activation
        self._act = get_activation(activation)

    def apply(self, params, state, x, *, train=False, rng=None):
        return self._act(x), state

    def get_config(self):
        return {"activation": self.activation}


@register
class Flatten(Layer):
    def out_shape(self, in_shape):
        return (math.prod(in_shape),)

    def apply(self, params, state, x, *, train=False, rng=None):
        return x.reshape(x.shape[0], -1), state


@register
class Reshape(Layer):
    def __init__(self, target_shape: Sequence[int]):
        self.target_shape = tuple(int(s) for s in target_shape)

    def out_shape(self, in_shape):
        return self.target_shape

    def apply(self, params, state, x, *, train=False, rng=None):
        return x.reshape(x.shape[0], *self.target_shape), state

    def get_config(self):
        return {"target_shape": list(self.target_shape)}


@register
class Dropout(Layer):
    rng_in_train = True

    def __init__(self, rate: float):
        self.rate = float(rate)

    def apply(self, params, state, x, *, train=False, rng=None):
        if not train or self.rate == 0.0:
            return x, state
        if rng is None:
            raise ValueError("Dropout needs an rng when train=True")
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype), state

    def get_config(self):
        return {"rate": self.rate}


@register
class Conv2D(Layer):
    """NHWC conv lowering to ``lax.conv_general_dilated`` (MXU-tiled by XLA)."""
    time_mixing = True

    def __init__(self, filters: int, kernel_size, strides=1, padding="SAME",
                 activation=None, use_bias: bool = True):
        self.filters = int(filters)
        self.kernel_size = (kernel_size, kernel_size) if isinstance(kernel_size, int) else tuple(kernel_size)
        self.strides = (strides, strides) if isinstance(strides, int) else tuple(strides)
        self.padding = padding
        self.activation = activation
        self.use_bias = use_bias
        self._act = get_activation(activation)

    def init(self, rng, in_shape):
        h, w, c = in_shape
        kh, kw = self.kernel_size
        params = {"kernel": he_normal(rng, (kh, kw, c, self.filters))}
        if self.use_bias:
            params["bias"] = jnp.zeros((self.filters,))
        return params, {}, self.out_shape(in_shape)

    def out_shape(self, in_shape):
        h, w, c = in_shape
        sh, sw = self.strides
        if self.padding == "SAME":
            oh, ow = -(-h // sh), -(-w // sw)
        else:
            kh, kw = self.kernel_size
            oh, ow = (h - kh) // sh + 1, (w - kw) // sw + 1
        return (oh, ow, self.filters)

    def apply(self, params, state, x, *, train=False, rng=None):
        y = lax.conv_general_dilated(
            x, params["kernel"].astype(x.dtype),
            window_strides=self.strides, padding=self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        if self.use_bias:
            y = y + params["bias"].astype(x.dtype)
        return self._act(y), state

    def get_config(self):
        return {
            "filters": self.filters, "kernel_size": list(self.kernel_size),
            "strides": list(self.strides), "padding": self.padding,
            "activation": activation_config(self.activation),
            "use_bias": self.use_bias,
        }


class _Pool2D(Layer):
    """Pooling via stacked strided slices instead of ``lax.reduce_window``.

    One static slice per (i, j) window offset (p² slices, e.g. 9 for 3×3),
    reduced with max/mean.  Equivalent math, but differentiable everywhere
    reverse-mode runs — ``reduce_window`` fails to linearize inside
    ``shard_map`` (jax 0.9), which the distributed conv trainers hit —
    and XLA fuses the slices back into one windowed reduction.
    """
    time_mixing = True

    def __init__(self, pool_size=2, strides=None, padding="VALID"):
        self.pool_size = (pool_size, pool_size) if isinstance(pool_size, int) else tuple(pool_size)
        self.strides = self.pool_size if strides is None else (
            (strides, strides) if isinstance(strides, int) else tuple(strides))
        self.padding = padding

    def out_shape(self, in_shape):
        h, w, c = in_shape
        ph, pw = self.pool_size
        sh, sw = self.strides
        if self.padding == "SAME":
            return (-(-h // sh), -(-w // sw), c)
        return ((h - ph) // sh + 1, (w - pw) // sw + 1, c)

    def _pads(self, h, w):
        if self.padding != "SAME":
            return (0, 0), (0, 0)
        ph, pw = self.pool_size
        sh, sw = self.strides
        oh, ow = -(-h // sh), -(-w // sw)
        dh = max(0, (oh - 1) * sh + ph - h)
        dw = max(0, (ow - 1) * sw + pw - w)
        return (dh // 2, dh - dh // 2), (dw // 2, dw - dw // 2)

    def _patches(self, x):
        """(p²,) list of (B, OH, OW, C) strided slices of padded input."""
        _, h, w, _ = x.shape
        ph, pw = self.pool_size
        sh, sw = self.strides
        oh = (h - ph) // sh + 1
        ow = (w - pw) // sw + 1
        return [x[:, i: i + (oh - 1) * sh + 1: sh,
                  j: j + (ow - 1) * sw + 1: sw, :]
                for i in range(ph) for j in range(pw)]

    def get_config(self):
        return {"pool_size": list(self.pool_size), "strides": list(self.strides),
                "padding": self.padding}


@register
class SpaceToDepth(Layer):
    """(H, W, C) → (H/b, W/b, C·b²): each b×b spatial patch becomes one
    pixel's channel stack.  The standard TPU stem transform: a conv on
    tiny-channel inputs (RGB C=3) underfills the MXU's 128 lanes, so the
    stem patchifies first and feeds a stride-1 conv at C·b² channels —
    same downsampling, MXU-shaped contraction (``zoo.resnet50(stem=
    "s2d")``; SURVEY.md §6 perf north star, VERDICT r3 weak #2)."""

    def __init__(self, block_size: int):
        self.block_size = int(block_size)

    def out_shape(self, in_shape):
        h, w, c = in_shape
        b = self.block_size
        if h % b or w % b:
            raise ValueError(f"spatial extent ({h}, {w}) not divisible by "
                             f"block_size {b}")
        return (h // b, w // b, c * b * b)

    def apply(self, params, state, x, *, train=False, rng=None):
        n, h, w, c = x.shape
        b = self.block_size
        x = x.reshape(n, h // b, b, w // b, b, c)
        x = x.transpose(0, 1, 3, 2, 4, 5)  # (N, H/b, W/b, b, b, C)
        return x.reshape(n, h // b, w // b, b * b * c), state

    def get_config(self):
        return {"block_size": self.block_size}


@register
class MaxPool2D(_Pool2D):
    def apply(self, params, state, x, *, train=False, rng=None):
        (pt, pb), (pl, pr) = self._pads(x.shape[1], x.shape[2])
        if pt or pb or pl or pr:
            neg = jnp.asarray(jnp.finfo(x.dtype).min, x.dtype)
            x = jnp.pad(x, ((0, 0), (pt, pb), (pl, pr), (0, 0)),
                        constant_values=neg)
        patches = self._patches(x)
        out = patches[0]
        for p in patches[1:]:
            out = jnp.maximum(out, p)
        return out, state


@register
class AvgPool2D(_Pool2D):
    def apply(self, params, state, x, *, train=False, rng=None):
        (pt, pb), (pl, pr) = self._pads(x.shape[1], x.shape[2])
        if pt or pb or pl or pr:
            # average over valid (unpadded) elements only, like Keras:
            # zero-pad the values, divide by the per-window valid count
            mask = jnp.ones((1, x.shape[1], x.shape[2], 1), x.dtype)
            x = jnp.pad(x, ((0, 0), (pt, pb), (pl, pr), (0, 0)))
            mask = jnp.pad(mask, ((0, 0), (pt, pb), (pl, pr), (0, 0)))
            total = sum(self._patches(x))
            counts = sum(self._patches(mask))
            return total / counts, state
        return sum(self._patches(x)) / math.prod(self.pool_size), state


@register
class GlobalAvgPool2D(Layer):
    time_mixing = True
    def out_shape(self, in_shape):
        return (in_shape[-1],)

    def apply(self, params, state, x, *, train=False, rng=None):
        return jnp.mean(x, axis=(1, 2)), state


@register
class BatchNorm(Layer):
    """Batch normalization with running statistics kept in ``state``.

    During distributed (SPMD) training the batch statistics are per-shard;
    trainers that need cross-replica stats psum them via ``axis_name`` — we
    follow the simpler per-shard convention (matches the reference, where
    each Spark worker batch-norms its own minibatch independently).
    """

    def __init__(self, momentum: float = 0.9, epsilon: float = 1e-5,
                 axis_name: Optional[str] = None):
        self.momentum = float(momentum)
        self.epsilon = float(epsilon)
        self.axis_name = axis_name

    def init(self, rng, in_shape):
        c = in_shape[-1]
        params = {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}
        state = {"mean": jnp.zeros((c,)), "var": jnp.ones((c,))}
        return params, state, in_shape

    def apply(self, params, state, x, *, train=False, rng=None):
        reduce_axes = tuple(range(x.ndim - 1))
        if train:
            # SIBLING reduces (mean and mean-of-squares over the same
            # read) fuse into ONE pass over the activations, where
            # jnp.var's (x − mean)² formulation needs a second,
            # dependent pass — one full HBM read saved per BN per step
            # on the conv families (r5 MFU work).  Accumulation is f32
            # even for bf16 activations.
            mean = jnp.mean(x, axis=reduce_axes, dtype=jnp.float32)
            mean2 = jnp.mean(lax.square(x), axis=reduce_axes,
                             dtype=jnp.float32)
            if self.axis_name is not None:
                mean = lax.pmean(mean, self.axis_name)
                mean2 = lax.pmean(mean2, self.axis_name)
            var = jnp.maximum(mean2 - lax.square(mean), 0.0)
            m = self.momentum
            new_state = {"mean": m * state["mean"] + (1 - m) * mean,
                         "var": m * state["var"] + (1 - m) * var}
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        # per-CHANNEL affine precompute: the (B, H, W, C)-wide loop is
        # y = x·a + b (one fused multiply-add) instead of the 4-op
        # subtract/scale/shift chain
        inv = lax.rsqrt(var.astype(jnp.float32) + self.epsilon)
        a = (inv * params["scale"].astype(jnp.float32)).astype(x.dtype)
        b = (params["bias"].astype(jnp.float32)
             - mean.astype(jnp.float32) * inv
             * params["scale"].astype(jnp.float32)).astype(x.dtype)
        return x * a + b, new_state

    def get_config(self):
        return {"momentum": self.momentum, "epsilon": self.epsilon,
                "axis_name": self.axis_name}


@register
class Embedding(Layer):
    def __init__(self, vocab_size: int, dim: int):
        self.vocab_size = int(vocab_size)
        self.dim = int(dim)

    def init(self, rng, in_shape):
        params = {"table": uniform_scale(rng, (self.vocab_size, self.dim))}
        return params, {}, (*in_shape, self.dim)

    def out_shape(self, in_shape):
        return (*in_shape, self.dim)

    def apply(self, params, state, x, *, train=False, rng=None):
        return jnp.take(params["table"], x.astype(jnp.int32), axis=0), state

    def get_config(self):
        return {"vocab_size": self.vocab_size, "dim": self.dim}


@register
class LSTM(Layer):
    """LSTM over the time axis via ``lax.scan`` (static-shape recurrence).

    Replaces the reference's Keras LSTM layers (IMDB sentiment config in
    BASELINE.json).  Gates are fused into one (in+h, 4h) matmul so each scan
    step is a single MXU-shaped GEMM.
    """
    time_mixing = True

    def __init__(self, units: int, return_sequences: bool = False):
        self.units = int(units)
        self.return_sequences = bool(return_sequences)

    def init(self, rng, in_shape):
        t, d = in_shape
        k1, k2 = jax.random.split(rng)
        h = self.units
        params = {
            "kernel": glorot_uniform(k1, (d, 4 * h)),
            "recurrent": glorot_uniform(k2, (h, 4 * h)),
            "bias": jnp.zeros((4 * h,)).at[h:2 * h].set(1.0),  # forget-gate bias 1
        }
        return params, {}, self.out_shape(in_shape)

    def out_shape(self, in_shape):
        t, d = in_shape
        return (t, self.units) if self.return_sequences else (self.units,)

    def apply(self, params, state, x, *, train=False, rng=None):
        b, t, d = x.shape
        h = self.units
        wk = params["kernel"].astype(x.dtype)
        wr = params["recurrent"].astype(x.dtype)
        bias = params["bias"].astype(x.dtype)
        x_proj = x @ wk + bias  # (b, t, 4h): hoist input projection out of scan

        def step(carry, xp):
            hprev, cprev = carry
            z = xp + hprev @ wr
            i, f, g, o = jnp.split(z, 4, axis=-1)
            c = jax.nn.sigmoid(f) * cprev + jax.nn.sigmoid(i) * jnp.tanh(g)
            hnew = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (hnew, c), hnew

        h0 = jnp.zeros((b, h), x.dtype)
        (hT, _), hs = lax.scan(step, (h0, h0), jnp.swapaxes(x_proj, 0, 1))
        if self.return_sequences:
            return jnp.swapaxes(hs, 0, 1), state
        return hT, state

    def get_config(self):
        return {"units": self.units, "return_sequences": self.return_sequences}


# ---------------------------------------------------------------------------
# composition
# ---------------------------------------------------------------------------

@register
class Residual(Layer):
    """Residual block: ``y = act(inner(x) + shortcut(x))``.

    The combinator the reference never needed (its era's models were plain
    Sequential stacks) but ResNet-20/50 (BASELINE.json configs) require.
    ``shortcut`` defaults to identity; pass a layer (e.g. a 1×1 strided
    Conv2D) when shapes change.  XLA fuses the add into the adjacent convs.
    """

    def __init__(self, inner: "Layer", shortcut: Optional["Layer"] = None,
                 activation=None):
        self.inner = inner
        self.shortcut = shortcut
        self.activation = activation
        self._act = get_activation(activation)

    def init(self, rng, in_shape):
        r1, r2 = jax.random.split(rng)
        p_in, s_in, out_shape = self.inner.init(r1, in_shape)
        params = {"inner": p_in}
        state = {"inner": s_in}
        if self.shortcut is not None:
            p_sc, s_sc, sc_shape = self.shortcut.init(r2, in_shape)
            if tuple(sc_shape) != tuple(out_shape):
                raise ValueError(
                    f"shortcut shape {sc_shape} != inner shape {out_shape}")
            params["shortcut"] = p_sc
            state["shortcut"] = s_sc
        elif tuple(out_shape) != tuple(in_shape):
            raise ValueError(
                f"identity shortcut needs matching shapes, got {in_shape} -> "
                f"{out_shape}; pass a projection shortcut")
        return params, state, out_shape

    def out_shape(self, in_shape):
        return self.inner.out_shape(in_shape)

    def apply(self, params, state, x, *, train=False, rng=None):
        r1 = r2 = None
        if rng is not None:
            r1, r2 = jax.random.split(rng)
        with _scope(self.inner):
            y, new_inner = self.inner.apply(params["inner"], state["inner"],
                                            x, train=train, rng=r1)
        new_state = {"inner": new_inner}
        if self.shortcut is not None:
            with _scope(self.shortcut):
                sc, new_sc = self.shortcut.apply(params["shortcut"],
                                                 state["shortcut"], x,
                                                 train=train, rng=r2)
            new_state["shortcut"] = new_sc
        else:
            sc = x
        return self._act(y + sc), new_state

    def init_cache(self, batch, in_shape):
        cache = {"inner": self.inner.init_cache(batch, in_shape)}
        if self.shortcut is not None:
            cache["shortcut"] = self.shortcut.init_cache(batch, in_shape)
        return cache

    def apply_decode(self, params, state, x, cache, pos):
        y, ci = self.inner.apply_decode(params["inner"], state["inner"],
                                        x, cache["inner"], pos)
        new_cache = {"inner": ci}
        if self.shortcut is not None:
            sc, cs = self.shortcut.apply_decode(
                params["shortcut"], state["shortcut"], x,
                cache["shortcut"], pos)
            new_cache["shortcut"] = cs
        else:
            sc = x
        return self._act(y + sc), new_cache

    def apply_prefill(self, params, state, x, cache):
        y, ci = self.inner.apply_prefill(params["inner"], state["inner"],
                                         x, cache["inner"])
        new_cache = {"inner": ci}
        if self.shortcut is not None:
            sc, cs = self.shortcut.apply_prefill(
                params["shortcut"], state["shortcut"], x,
                cache["shortcut"])
            new_cache["shortcut"] = cs
        else:
            sc = x
        return self._act(y + sc), new_cache

    def get_config(self):
        return {"inner": self.inner.config(),
                "shortcut": self.shortcut.config() if self.shortcut else None,
                "activation": activation_config(self.activation)}

    @classmethod
    def from_config(cls, cfg):
        return cls(layer_from_config(cfg["inner"]),
                   layer_from_config(cfg["shortcut"]) if cfg["shortcut"] else None,
                   activation=cfg.get("activation"))


@register
class Sequential(Layer):
    """Keras-Sequential-style composition; the standard model container.

    Parity surface for the reference's use of ``keras.models.Sequential`` in
    its examples (``examples/mnist.ipynb``): same mental model, but lowering
    to one pure jit-able function.
    """

    def __init__(self, layers: Sequence[Layer], input_shape: Optional[Sequence[int]] = None):
        self.layers = list(layers)
        self.input_shape = tuple(input_shape) if input_shape is not None else None

    def init(self, rng, in_shape=None):
        in_shape = tuple(in_shape) if in_shape is not None else self.input_shape
        if in_shape is None:
            raise ValueError("Sequential needs input_shape (constructor or init arg)")
        params, state = [], []
        shape = in_shape
        for lyr in self.layers:
            rng, sub = jax.random.split(rng)
            p, s, shape = lyr.init(sub, shape)
            params.append(p)
            state.append(s)
        return params, state, shape

    def out_shape(self, in_shape):
        shape = tuple(in_shape)
        for lyr in self.layers:
            shape = lyr.out_shape(shape)
        return shape

    def apply(self, params, state, x, *, train=False, rng=None,
              remat=False):
        """``remat``: the backward may recompute to fit.  True, or the
        step's ``remat.Plan``: the children are laid out as their
        applications in the order they run (a plain child is one, a
        ``Looped`` one a pass and child), and each application before the
        plan's first kept one runs under ``remat.checkpoint`` (its input
        and its attention kernels' outputs are held, the rest is run
        again while it is differentiated); the last never is, and a plan
        with a budget keeps whole applications from the end backward
        while their estimate fits (none where the budget is unknown: True
        alone, or a device that reports no limit)."""
        own = [lyr.applications(params[i], state[i], train=train)
               for i, lyr in enumerate(self.layers)]
        calls = [[a.call for a in of_child] for of_child in own]
        if remat:
            from . import remat as plans
            plan = remat if isinstance(remat, plans.Plan) else plans.Plan()
            flat = [a for of_child in own for a in of_child]
            # every application's key has ``rng``'s shape: it stands in
            first_kept = plan.first_kept_of(flat, x, rng)
            wrapped = iter([plans.checkpoint(a.call) if i < first_kept
                            else a.call for i, a in enumerate(flat)])
            calls = [[next(wrapped) for _ in of_child] for of_child in own]
        new_state = []
        for i, lyr in enumerate(self.layers):
            sub = None
            if rng is not None:
                rng, sub = jax.random.split(rng)
            with _scope(lyr):
                x, s = lyr.apply_calls(calls[i], params[i], state[i], x,
                                       rng=sub)
            new_state.append(s)
        return x, new_state

    def decode_logits(self, out):
        return self.layers[-1].decode_logits(out)

    def init_cache(self, batch, in_shape):
        caches, shape = [], tuple(in_shape)
        for lyr in self.layers:
            caches.append(lyr.init_cache(batch, shape))
            shape = lyr.out_shape(shape)
        return caches

    def apply_decode(self, params, state, x, cache, pos):
        new_cache = []
        for i, lyr in enumerate(self.layers):
            x, c = lyr.apply_decode(params[i], state[i], x, cache[i], pos)
            new_cache.append(c)
        return x, new_cache

    def apply_prefill(self, params, state, x, cache):
        new_cache = []
        for i, lyr in enumerate(self.layers):
            x, c = lyr.apply_prefill(params[i], state[i], x, cache[i])
            new_cache.append(c)
        return x, new_cache

    def get_config(self):
        return {"layers": [l.config() for l in self.layers],
                "input_shape": list(self.input_shape) if self.input_shape else None}

    @classmethod
    def from_config(cls, cfg):
        return cls([layer_from_config(c) for c in cfg["layers"]],
                   input_shape=cfg.get("input_shape"))


@jax.custom_vjp
def _handed_on(x):
    """A pass's output as the next pass's input: the same values under a
    variable of their own.  The output is read twice (by what follows the
    loop and by the next pass), and a backward adds a variable's
    cotangents in the order it meets them; with this the next pass's are
    added up on their own, whether its first application is differentiated
    under a checkpoint or in the open, so a recompute plan moves no bit of
    a gradient."""
    return x


_handed_on.defvjp(lambda x: (x, None), lambda _, ct: (ct,))


@register
class Looped(Layer):
    """A stack of layers run ``steps`` times over ONE set of parameters
    (Dehghani et al. 2019, Universal Transformers; the looped language
    models of arXiv:2510.25741): pass t applies ``body``'s children in
    order and then ``closing`` to pass t - 1's output (the first pass to
    the layer's input), so input and output shapes are equal.

    ``params = {"body": [...], "closing": ...}``, held once whatever
    ``steps`` is; a parameter's gradient is the sum over the passes.
    ``apply`` returns a TUPLE of the ``steps`` passes' outputs, the last
    pass's last (a tuple and not a stacked array: what follows reads each
    pass where it lies).  The passes are unrolled, each under
    ``jax.named_scope("pass_<t>")``, so a recompute plan decides on every
    (pass, child) application on its own (:meth:`applications`).  A
    child's state is threaded through the passes."""

    #: no decode rule yet (a K/V cache a (pass, layer): ROADMAP R7), so
    #: generation recomputes the whole context
    time_mixing = True

    def __init__(self, body: Sequence[Layer], steps: int, closing: Layer):
        if int(steps) < 1:
            raise ValueError(f"steps must be >= 1, got {steps}")
        self.body = list(body)
        self.steps = int(steps)
        self.closing = closing

    @property
    def _children(self):
        return [*self.body, self.closing]

    @staticmethod
    def _flat(tree):
        """``{"body": [...], "closing": c}`` in ``_children``'s order."""
        return [*tree["body"], tree["closing"]]

    def init(self, rng, in_shape):
        # a key a child, split as ``Sequential`` splits them
        params, state, shape = [], [], tuple(in_shape)
        for lyr in self._children:
            rng, sub = jax.random.split(rng)
            p, s, shape = lyr.init(sub, shape)
            params.append(p)
            state.append(s)
        if self.steps > 1 and tuple(shape) != tuple(in_shape):
            raise ValueError(f"a pass maps {tuple(in_shape)} to "
                             f"{tuple(shape)}: it cannot be run again")
        return ({"body": params[:-1], "closing": params[-1]},
                {"body": state[:-1], "closing": state[-1]},
                (tuple(shape),) * self.steps)

    def out_shape(self, in_shape):
        shape = tuple(in_shape)
        for lyr in self._children:
            shape = lyr.out_shape(shape)
        return (shape,) * self.steps

    def applications(self, params, state, *, train=False):
        from .remat import Application
        one_pass = [Application(functools.partial(lyr.apply, train=train),
                                p, s)
                    for lyr, p, s in zip(self._children, self._flat(params),
                                         self._flat(state))]
        return one_pass * (self.steps - 1) + one_pass[:-1] + [
            one_pass[-1]._replace(fan_out=self.steps)]

    def apply_calls(self, calls, params, state, x, *, rng=None):
        # counted where the loop is unrolled into a program, as the
        # kernels count their tiles
        registry = default_registry()
        registry.counter("loop.passes").inc(self.steps)
        registry.counter("loop.applications").inc(
            self.steps * len(self.body))
        n = len(self.body) + 1
        params, state, outs = self._flat(params), self._flat(state), []
        for t in range(self.steps):
            with jax.named_scope(f"pass_{t}"):
                if t:
                    x = _handed_on(x)
                for i, lyr in enumerate(self._children):
                    sub = None
                    if rng is not None:
                        rng, sub = jax.random.split(rng)
                    with _scope(lyr):
                        x, state[i] = calls[t * n + i](params[i], state[i],
                                                       x, rng=sub)
            outs.append(x)
        return tuple(outs), {"body": state[:-1], "closing": state[-1]}

    def apply(self, params, state, x, *, train=False, rng=None):
        calls = [a.call for a in self.applications(params, state,
                                                   train=train)]
        return self.apply_calls(calls, params, state, x, rng=rng)

    def get_config(self):
        return {"body": [l.config() for l in self.body],
                "steps": self.steps, "closing": self.closing.config()}

    @classmethod
    def from_config(cls, cfg):
        return cls([layer_from_config(c) for c in cfg["body"]],
                   cfg["steps"], layer_from_config(cfg["closing"]))


@register
class ExitHeads(Layer):
    """What follows a ``Looped`` stack in a language model that may answer
    after any pass: ONE head (``units`` logits, no bias) and ONE exit gate
    (a ``Dense(1)`` with bias, in float32: ``parallel.sync.FLOAT32_KEYS``
    names its key) applied to every pass's output.

    ``apply`` takes the tuple of passes and returns ``{"logits": a tuple
    of (B, T, units), one a pass, "exit_gate": (B, T, steps) float32}``,
    in training and in prediction alike; ``ops.losses``'s
    ``exit_weighted_crossentropy`` reads it whole.  A pass's gate value g
    gives the chance sigmoid(g) of answering there if no earlier pass
    did; the last pass takes what is left (``ops.losses.exit_log_probs``).
    :meth:`decode_logits` gives each token the logits of the first pass
    at which the exit chances add up to ``threshold`` (at 1.0, the
    last).  In training the state's ``exit_share`` holds the step's mean
    chance of each pass (``loop.exit_share.<t>``, set by the trainer when
    the variables are back on the host)."""

    def __init__(self, units: int, threshold: float = 1.0):
        self.units = int(units)
        self.threshold = float(threshold)

    def init(self, rng, in_shape):
        steps, (*_, d) = len(in_shape), in_shape[0]
        k1, k2 = jax.random.split(rng)
        params = {"head": {"kernel": glorot_uniform(k1, (d, self.units))},
                  "exit_gate": {"kernel": glorot_uniform(k2, (d, 1)),
                                "bias": jnp.zeros((1,))}}
        return params, {"exit_share": jnp.zeros((steps,))}, \
            self.out_shape(in_shape)

    def out_shape(self, in_shape):
        return {"logits": tuple((*s[:-1], self.units) for s in in_shape),
                "exit_gate": (*in_shape[0][:-1], len(in_shape))}

    def apply(self, params, state, x, *, train=False, rng=None):
        from ..ops.losses import exit_log_probs
        head, gate = params["head"]["kernel"], params["exit_gate"]
        logits, values = [], []
        for t, h in enumerate(x):
            with jax.named_scope(f"pass_{t}"):
                logits.append(h @ head.astype(h.dtype))
                with jax.named_scope("exit_gate"):
                    # float32 from the pass's output on: the last pass's
                    # chance is a product of the earlier ones' complements
                    values.append(jnp.dot(
                        h.astype(jnp.float32),
                        gate["kernel"].astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
                        + gate["bias"].astype(jnp.float32))
        values = jnp.concatenate(values, axis=-1)
        if train:
            share = jnp.exp(exit_log_probs(lax.stop_gradient(values)))
            state = {"exit_share": jnp.mean(
                share.reshape(-1, share.shape[-1]), axis=0)}
        return {"logits": tuple(logits), "exit_gate": values}, state

    def decode_logits(self, out):
        from ..ops.losses import exit_log_probs
        logits = out["logits"]
        mass = jnp.cumsum(jnp.exp(exit_log_probs(out["exit_gate"])),
                          axis=-1)[..., :-1] >= self.threshold
        # the first pass that reaches the threshold; none: the last
        first = jnp.where(jnp.any(mass, axis=-1), jnp.argmax(mass, axis=-1),
                          len(logits) - 1)[..., None]
        picked = logits[-1]
        for t in reversed(range(len(logits) - 1)):
            picked = jnp.where(first == t, logits[t], picked)
        return picked

    def get_config(self):
        return {"units": self.units, "threshold": self.threshold}


def state_leaves(state, key: str) -> list:
    """Every leaf of a variables-state tree that sits under the dict key
    ``key`` (a routed layer's ``aux_loss``, an ``ExitHeads``'
    ``exit_share``), in the tree's order."""
    from jax.tree_util import DictKey, tree_flatten_with_path
    return [leaf for path, leaf in tree_flatten_with_path(state)[0]
            if path and isinstance(path[-1], DictKey)
            and path[-1].key == key]
