"""Loss functions.

The reference passes Keras loss *names* into trainers
(``distkeras/trainers.py`` — e.g. ``loss='categorical_crossentropy'``).  We
keep the same string surface, resolving to pure JAX functions
``loss(logits_or_probs, targets) -> scalar`` that differentiate and fuse
cleanly under jit.

Convention: the named crossentropy losses here treat model outputs as
*logits* (numerically stable log-softmax inside the loss).  The reference's
Keras models end in a softmax layer, so trainers detect a trailing softmax
and swap in the ``*_from_probs`` variants below (clipped-log, exactly the
Keras semantics) — the model surface stays identical to the reference and
nothing is stripped.
"""

from __future__ import annotations

from typing import Callable, Union

import jax
import jax.numpy as jnp


def categorical_crossentropy(logits, targets):
    """targets: one-hot (batch, classes); logits: (batch, classes)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.sum(targets * logp, axis=-1))


def sparse_categorical_crossentropy(logits, targets):
    """targets: int class ids, any shape matching logits' leading dims —
    (batch,) for classifiers, (batch, seq) for per-token LM loss."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, targets.astype(jnp.int32)[..., None], axis=-1))


def binary_crossentropy(logits, targets):
    """targets in {0,1}, logits: raw scores (any shape)."""
    logits = logits.reshape(targets.shape)
    return jnp.mean(jnp.clip(logits, 0) - logits * targets
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def mean_squared_error(preds, targets):
    return jnp.mean((preds - targets) ** 2)


def mean_absolute_error(preds, targets):
    return jnp.mean(jnp.abs(preds - targets))


def exit_log_probs(gate):
    """``log p`` (..., steps), float32, of the pass a token answers from,
    from its exit gate's values ``g`` (..., steps): with ``λ_t =
    sigmoid(g_t)`` the chance of answering at pass t if no earlier pass
    did, ``p_t = λ_t Π_{j<t} (1 - λ_j)`` and the last pass takes what is
    left, ``p_steps = Π_{j<steps} (1 - λ_j)``, so the chances add up to
    one.  Sums of ``log_sigmoid``, never the log of a product."""
    gate = gate.astype(jnp.float32)
    stay = jax.nn.log_sigmoid(-gate)                  # log (1 - λ_t)
    before = jnp.cumsum(stay, axis=-1) - stay         # Σ_{j<t} log (1 - λ_j)
    return jnp.concatenate(
        [(before + jax.nn.log_sigmoid(gate))[..., :-1], before[..., -1:]],
        axis=-1)


def exit_weighted_crossentropy(out, targets, beta: float = 0.1):
    """The first-stage loss of a looped language model that may answer
    after any pass (arXiv:2510.25741, uniform prior): the mean over
    tokens of ``Σ_t p_t nll_t - beta H(p)``, with ``nll_t`` the
    cross-entropy of pass t's logits, ``p`` the exit distribution of
    :func:`exit_log_probs` and ``H`` its entropy.  ``out`` is what
    ``models.layers.ExitHeads`` gives: ``{"logits": one (B, T, V) a
    pass, "exit_gate": (B, T, steps)}``; ``targets`` int ids (B, T).
    Float32 throughout, whatever the logits' dtype."""
    log_p = exit_log_probs(out["exit_gate"])
    p = jnp.exp(log_p)
    ids = targets.astype(jnp.int32)[..., None]
    nll = []
    for t, logits in enumerate(out["logits"]):
        with jax.named_scope(f"pass_{t}"):
            logits = logits.astype(jnp.float32)
            nll.append(jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
                logits, ids, axis=-1)[..., 0])
    expected = jnp.sum(p * jnp.stack(nll, axis=-1), axis=-1)
    entropy = -jnp.sum(p * log_p, axis=-1)
    return jnp.mean(expected - beta * entropy)


LOSSES: dict[str, Callable] = {
    "categorical_crossentropy": categorical_crossentropy,
    "sparse_categorical_crossentropy": sparse_categorical_crossentropy,
    "binary_crossentropy": binary_crossentropy,
    "mean_squared_error": mean_squared_error,
    "mse": mean_squared_error,
    "mean_absolute_error": mean_absolute_error,
    "mae": mean_absolute_error,
    "exit_weighted_crossentropy": exit_weighted_crossentropy,
}


def get_loss(name_or_fn: Union[str, Callable]) -> Callable:
    if callable(name_or_fn):
        return name_or_fn
    return LOSSES[name_or_fn]


# -- on-probabilities variants (Keras semantics) ----------------------------
# The reference's models end in a softmax layer and its losses therefore see
# probabilities, not logits (Keras ``categorical_crossentropy``).  Trainers
# that detect a trailing softmax swap in these clipped-log variants so the
# model surface can stay identical to the reference.

_EPS = 1e-7


def categorical_crossentropy_from_probs(probs, targets):
    p = jnp.clip(probs, _EPS, 1.0)
    return -jnp.mean(jnp.sum(targets * jnp.log(p), axis=-1))


def sparse_categorical_crossentropy_from_probs(probs, targets):
    p = jnp.clip(probs, _EPS, 1.0)
    logp = jnp.log(p)
    return -jnp.mean(jnp.take_along_axis(
        logp, targets.astype(jnp.int32)[..., None], axis=-1))


def binary_crossentropy_from_probs(probs, targets):
    p = jnp.clip(probs.reshape(targets.shape), _EPS, 1.0 - _EPS)
    return -jnp.mean(targets * jnp.log(p) + (1 - targets) * jnp.log1p(-p))


_PROBS_VARIANTS: dict[str, Callable] = {
    "categorical_crossentropy": categorical_crossentropy_from_probs,
    "sparse_categorical_crossentropy": sparse_categorical_crossentropy_from_probs,
    "binary_crossentropy": binary_crossentropy_from_probs,
}


def probs_loss_variant(name: str):
    """On-probs variant of a named loss, or None if not a crossentropy."""
    return _PROBS_VARIANTS.get(name)
