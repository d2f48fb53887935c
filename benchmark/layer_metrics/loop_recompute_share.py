"""Applications of the step program that its backward runs again, over
all its applications (%): what the recompute plan (``models/remat.py``)
decided for the program the window ran, from the ``remat_children_*``
fields of the cold call's ``jit_compile`` record (the last one: a judge
that stepped back compiled again inside the same record).  An application
is one run of one child of the model: in a looped model one a pass and
child.  A program without a plan, or from before the record carried the
fields, has nothing to read and the metric is left out."""

NAME, UNIT, LAYER, MOVES = ("loop_recompute_share", "%", "step program",
                            "train_samples_per_s")
SOURCE = "program_counter"


def read(sources):
    spans = [s for s in sources.get("setup_compile_spans") or ()
             if "remat_children_recomputed" in s]
    if not spans:
        return None
    kept, recomputed = (spans[-1][f"remat_children_{which}"]
                        for which in ("kept", "recomputed"))
    if not kept + recomputed:
        return None
    return 100.0 * recomputed / (kept + recomputed)
