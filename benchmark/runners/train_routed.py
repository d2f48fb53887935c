"""Runner for training cells whose model sends each token to the k best of
its experts: ``runners/train.py``'s run, timed in the same way, with a
comparison against the reference that knows where float32 cannot decide
the choice.

Why ``train.py``'s own comparison does not do here.  It holds every logit
to the reference's within ``atol + rtol * |want|``.  A top-k choice is
not continuous: where a token's k-th and (k + 1)-th probabilities lie
nearer than float32 rounding, the program and the reference, both correct,
may take different experts, and that token's logits then differ by a
whole expert's output (0.1 to 0.5 at Laguna-XS.2's sizes, on one token of
16,384, every other token within 1.4e-5; my chip runs, PR 29: seen in 1
of 41 checks of trained variables and in 5 of 27 of untrained ones; the
router's logits are dot products of 2,048 float32 terms and come out
EQUAL for the two experts about once in 20 layers of 16,384 tokens).

What this comparison does.  The limit on every logit stays as it is
(``rtol``, ``atol``: no token is let off and no limit is wider).  The
reference says, for every routing, how far apart the last chosen and the
first unchosen probability are, and whether either expert is held on this
chip.  Routings nearer than ``reference_tolerance["tie"]`` (relative) that
bear on this chip are *ties*: the published function has two values
there that float32 cannot tell apart.  The program is correct if ALL its
logits agree with the reference under ONE way of settling the ties.  A
choice taken the other way moves its own token by a whole expert's
output, so the ties tried are those of the tokens that differ: each
subset of them, the reference run again whole with those choices taken
the other way, so that what a choice changes further up (later layers,
later tokens through attention) is carried along, and every logit of
every token compared anew.  The reference honours a swap only where the
gap it then meets is still under ``tie``, so a pass never holds an expert
that float32 can tell is not among the k best.  More than
``reference_tolerance["max_ties"]`` such ties are not tried (2 ** n
passes): the run is then not correct, and says why.

A wrong mask, scale or gate, a dropped or misplaced assignment, or a lower
precision (which moves every token) agrees with no such pass and fails as
before.
"""

import importlib
import importlib.util
import itertools
import json
import os
import sys

import numpy as np


def check_reference(config: dict, model, ds, not_correct: list) -> None:
    """``predict_fn`` over the trained variables against the plain
    reference, outside every window; ties settled as the module says."""
    import jax
    import jax.numpy as jnp
    name = config["reference"]
    reference = importlib.import_module(f"reference.{name}")
    tol = config["reference_tolerance"]
    x = np.asarray(ds["features"][:2])
    with jax.default_matmul_precision("highest"):
        got = jax.jit(model.predict_fn())(model.variables, x)

    def compare(want) -> tuple:
        """(the tokens (B, T) with a logit beyond the limit, the largest
        difference), compared on the device: the logits are 0.4 GB a
        side.  In the not-form: a NaN misses."""
        diff = jnp.abs(got - want)
        within = diff <= tol["atol"] + tol["rtol"] * jnp.abs(want)
        return (np.asarray(jnp.any(~within, axis=-1)),
                float(jnp.max(diff)))

    reference_pass = reference.passes(config["sizes"])
    want, gaps, bears = reference_pass(model.variables, x)
    if got.shape != want.shape:
        not_correct.append(f"logits {got.shape} against the reference's "
                           f"{want.shape}")
        return
    gaps, bears = np.asarray(gaps), np.asarray(bears)
    missed, least = compare(want)
    del want
    near = (gaps < tol["tie"]) & bears
    sys.stderr.write(
        f"runners/train_routed.py: smallest relative gap between a token's "
        f"last chosen and first unchosen expert, by sparse layer: "
        f"{json.dumps([float(g.min()) for g in gaps])}; {int(near.sum())} "
        f"routings are ties (gap < {tol['tie']}, one of the two experts "
        f"held here); {int(missed.sum())} of {missed.size} tokens differ "
        f"from the reference by more than the limit\n")
    if not missed.any():
        return
    said = (f"logits differ from reference/{name}.py by {least:.3g} (rtol "
            f"= {tol['rtol']}, atol = {tol['atol']}) on {int(missed.sum())} "
            f"token(s)")
    # a choice taken the other way moves its own token by a whole expert's
    # output, so only the ties of tokens that differ are tried
    ties = [tuple(int(i) for i in at) for at in np.argwhere(near & missed)]
    if len(ties) > tol["max_ties"]:
        not_correct.append(f"{said}; their {len(ties)} ties are more than "
                           f"the {tol['max_ties']} that are tried")
        return
    for n in range(1, len(ties) + 1):
        for taken in itertools.combinations(ties, n):
            swap = np.zeros(gaps.shape, bool)
            swap[tuple(np.array(taken).T)] = True
            missed, diff = compare(reference_pass(
                model.variables, x, swap=swap, tie=tol["tie"])[0])
            if not missed.any():
                sys.stderr.write(
                    f"runners/train_routed.py: {said} as the reference "
                    f"chose, and by {diff:.3g} with the tie(s) at (sparse "
                    f"layer, row, token) {json.dumps(taken)} settled the "
                    f"other way: correct\n")
                return
            least = min(least, diff)
    not_correct.append(f"{said}, and by {least:.3g} at the least under "
                       f"every way to settle their {len(ties)} tie(s)")


def run(ctx: dict) -> dict:
    """``runners/train.py:run``, from a copy of that module of this one's
    own with the comparison above in the place of its own (the cells that
    load ``train.py`` get theirs untouched)."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_runners_train_of_train_routed", os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "train.py"))
    plain = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(plain)
    plain.check_reference = check_reference
    return plain.run(ctx)
