"""What the epoch program takes on one chip (GB, 10^9 bytes): arguments +
outputs − aliased + temporaries as the compiler counts them, the
``program_bytes`` of the memory account that the cold call's
``jit_compile`` record carries (``obs/profile.py:program_memory``, taken
where the executable is born; of a program over a mesh one device's
share).  The last record that has the field: a judge that stepped back
compiled again inside the same record, a second cold call's program is
the one the window runs.  A program from before the account has nothing
to read and the metric is left out."""

NAME, UNIT, LAYER, MOVES = ("program_memory_gb", "GB", "step program",
                            "train_samples_per_s")
SOURCE = "program_counter"


def read(sources):
    spans = [s for s in sources.get("setup_compile_spans") or ()
             if "program_bytes" in s]
    if not spans:
        return None
    return spans[-1]["program_bytes"] / 1e9
