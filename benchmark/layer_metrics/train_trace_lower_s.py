"""Seconds of the set-up's ``jit_compile`` spans that JAX spent tracing the
program in Python and lowering it to MLIR (the records' ``trace_s`` and
``lower_s``): paid again by every process, whatever the compile cache
holds."""

from tracing_fields import compile_field_sum

NAME, UNIT, LAYER, MOVES = "train_trace_lower_s", "s", "trainers", "setup_s"
SOURCE = "program_span"


def read(sources):
    return compile_field_sum(sources, "trace_s", "lower_s")
