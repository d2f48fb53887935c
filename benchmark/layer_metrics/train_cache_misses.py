"""Programs the set-up compiled and wrote to the persistent cache (the
``jit_compile`` records' ``cache_misses``): 0 on a warm run, so a cold
``setup_s`` reading names itself."""

from tracing_fields import compile_field_sum

NAME, UNIT, LAYER, MOVES = ("train_cache_misses", "count", "trainers",
                            "setup_s")
SOURCE = "program_span"


def read(sources):
    return compile_field_sum(sources, "cache_misses")
