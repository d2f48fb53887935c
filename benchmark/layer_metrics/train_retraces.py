"""Programs traced or compiled inside the window, as the trainer's
``RetraceSentinel`` counts them (``jit.retraces`` plus any new
``jit.compiles``).  Has to be 0."""

NAME, UNIT, LAYER, MOVES = ("train_retraces", "count", "trainers",
                            "train_samples_per_s")
SOURCE = "program_counter"


def read(sources):
    counts = sources.get("in_window")
    if counts is None:
        return None
    return counts["jit.retraces"] + counts["jit.compiles"]
