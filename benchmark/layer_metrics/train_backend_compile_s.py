"""Seconds of the set-up's ``jit_compile`` spans inside the backend (the
records' ``backend_s``): XLA's compile at a cache miss, the entry's read
and the executable's load at a hit."""

from tracing_fields import compile_field_sum

NAME, UNIT, LAYER, MOVES = ("train_backend_compile_s", "s", "trainers",
                            "setup_s")
SOURCE = "program_span"


def read(sources):
    return compile_field_sum(sources, "backend_s")
