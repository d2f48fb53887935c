"""Seconds in the trainer's ``jit_compile`` spans before the window: what
compiling (or loading from the cache) adds to set-up."""

NAME, UNIT, LAYER, MOVES = "train_compile_s", "s", "trainers", "setup_s"
SOURCE = "program_span"


def read(sources):
    spans = sources.get("setup_compile_spans")
    if spans is None:
        return None
    return sum(s["seconds"] for s in spans)
