"""What one ``train()`` call costs beside its epochs: the fenced clock of
the window's call less its epochs at the window's pace.  It holds
``model.init``, staging the data, and bringing the variables back to the
host.  The set-up call pays it too."""

NAME, UNIT, LAYER, MOVES = ("train_call_overhead_s", "s", "trainers",
                            "setup_s")
SOURCE = "host_clock"


def read(sources):
    window = sources.get("window")
    return None if window is None else window["call_overhead_s"]
