"""The share (%) of the epoch program's bytes that are its temporaries:
``program_temp_bytes`` ÷ ``program_bytes`` of the memory account on the
cold call's last ``jit_compile`` record that carries one
(``obs/profile.py:program_memory``).  Temporaries are what the program
takes while it runs, beside its arguments and outputs: activations kept
for the backward, partial gradients, the logits.  A program from before
the account has nothing to read and the metric is left out."""

NAME, UNIT, LAYER, MOVES = ("program_temp_share", "%", "step program",
                            "train_samples_per_s")
SOURCE = "program_counter"


def read(sources):
    spans = [s for s in sources.get("setup_compile_spans") or ()
             if "program_temp_bytes" in s and s.get("program_bytes")]
    if not spans:
        return None
    return 100.0 * spans[-1]["program_temp_bytes"] / spans[-1]["program_bytes"]
