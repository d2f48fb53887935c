"""Runner for training cells whose model runs its layers several times
and may answer after any pass (``zoo.decoder_lm(total_ut_steps > 1)``):
``runners/train.py``'s run, timed in the same way, with a comparison
against the reference that looks at every pass.

``train.py``'s own comparison holds one array of logits to the
reference's.  This model's output is a structure: one array of logits a
pass and the exit gate's value a pass and token.  A fault in the loop
(a pass too few, the final norm left out between passes, parameters not
shared) can leave the last pass's logits plausible, and a fault in the
gate leaves every logit right.  So EACH pass's logits are held to the
reference's within ``atol + rtol * |want|`` (``rtol``, ``atol``: the
accepted cells' limit), and the exit distribution ``p`` the gate's values
give (``ops.losses.exit_log_probs``) to the reference's ``p`` within
``p_atol``, on the trained variables of the timed call and two rows of
its data.  The largest difference of each is written to standard error,
passing or not.
"""

import importlib
import importlib.util
import json
import os
import sys

import numpy as np


def compare(out, want_logits, want_p, tol: dict) -> tuple:
    """(reasons it is not correct, the readings): ``out`` as the model
    gives it, ``want_logits`` (passes, B, T, V) and ``want_p`` (B, T,
    passes) as the reference does.  Compared on the device: the logits
    are 0.4 GB a pass and side.  In the not-form: a NaN misses."""
    import jax.numpy as jnp

    from distkeras_tpu.ops.losses import exit_log_probs
    reasons, readings = [], {"logits": [], "p": None}
    if len(out["logits"]) != len(want_logits) or any(
            got.shape != want.shape
            for got, want in zip(out["logits"], want_logits)):
        return [f"logits {[a.shape for a in out['logits']]} against the "
                f"reference's {want_logits.shape}"], readings
    for t, (got, want) in enumerate(zip(out["logits"], want_logits)):
        diff = jnp.abs(got - want)
        within = diff <= tol["atol"] + tol["rtol"] * jnp.abs(want)
        readings["logits"].append(float(jnp.max(diff)))
        if not bool(jnp.all(within)):
            reasons.append(
                f"pass {t}'s logits differ from the reference's by "
                f"{readings['logits'][-1]:.3g} (rtol = {tol['rtol']}, atol "
                f"= {tol['atol']}) on {int(jnp.sum(~jnp.all(within, -1)))} "
                f"token(s)")
    got_p = jnp.exp(exit_log_probs(out["exit_gate"]))
    readings["p"] = float(jnp.max(jnp.abs(got_p - want_p)))
    if not readings["p"] <= tol["p_atol"]:
        reasons.append(f"the exit distribution differs from the "
                       f"reference's by {readings['p']:.3g} (p_atol = "
                       f"{tol['p_atol']})")
    return reasons, readings


def check_reference(config: dict, model, ds, not_correct: list) -> None:
    """``predict_fn`` over the trained variables against the plain
    reference, pass by pass and ``p``, outside every window."""
    import jax
    name = config["reference"]
    reference = importlib.import_module(f"reference.{name}")
    x = np.asarray(ds["features"][:2])
    with jax.default_matmul_precision("highest"):
        out = jax.jit(model.predict_fn())(model.variables, x)
    reasons, readings = compare(
        out, *reference.forward(model.variables, x, config["sizes"]),
        config["reference_tolerance"])
    sys.stderr.write(
        f"runners/train_looped.py: largest difference from reference/"
        f"{name}.py, logits by pass and p: {json.dumps(readings)}\n")
    not_correct.extend(reasons)


def run(ctx: dict) -> dict:
    """``runners/train.py:run``, from a copy of that module of this one's
    own with the comparison above in the place of its own (the cells that
    load ``train.py`` get theirs untouched)."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_runners_train_of_train_looped", os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "train.py"))
    plain = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(plain)
    plain.check_reference = check_reference
    return plain.run(ctx)
