"""How far the recompute plan's estimate of the step's bytes lies from
what the compiler made of the program (%): 100 × |``remat_bytes_estimated``
− ``remat_bytes_compiled``| ÷ ``remat_bytes_compiled`` of the cold call's
last ``jit_compile`` record that carries both (``models/remat.py``; the
compiled size is the memory account's ``program_bytes``).  The plan keeps
applications while the ESTIMATE fits, so an estimate that is too high
recomputes what would have fitted.  Where nothing was estimated (no
device limit: off the chip) it reads 100; a program without a plan has
nothing to read and the metric is left out."""

NAME, UNIT, LAYER, MOVES = ("remat_estimate_error", "%", "step program",
                            "train_samples_per_s")
SOURCE = "program_counter"


def read(sources):
    spans = [s for s in sources.get("setup_compile_spans") or ()
             if "remat_bytes_estimated" in s
             and s.get("remat_bytes_compiled")]
    if not spans:
        return None
    estimated, compiled = (spans[-1][f"remat_bytes_{which}"]
                           for which in ("estimated", "compiled"))
    return 100.0 * abs(estimated - compiled) / compiled
