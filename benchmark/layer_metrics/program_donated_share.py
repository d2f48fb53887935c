"""The share (%) of the epoch program's argument bytes that it writes its
outputs over: ``program_alias_bytes`` ÷ ``program_argument_bytes`` of the
memory account on the cold call's last ``jit_compile`` record that
carries one (``obs/profile.py:program_memory``).  A program that donates
its carry (weights, optimizer state) reads near 100 less the staged data;
one that donates nothing reads 0 and holds its carry twice.  A program
from before the account has nothing to read and the metric is left out."""

NAME, UNIT, LAYER, MOVES = ("program_donated_share", "%", "step program",
                            "train_samples_per_s")
SOURCE = "program_counter"


def read(sources):
    spans = [s for s in sources.get("setup_compile_spans") or ()
             if "program_alias_bytes" in s
             and s.get("program_argument_bytes")]
    if not spans:
        return None
    return 100.0 * spans[-1]["program_alias_bytes"] \
        / spans[-1]["program_argument_bytes"]
