"""Share of the traced window in which no operation ran on device 0 (see
``reduce_trace.py`` for the window)."""

NAME, UNIT, LAYER, MOVES = ("device_idle_share.train", "%", "device",
                            "train_samples_per_s")
SOURCE = "device_trace"


def read(sources):
    trace = sources.get("trace")
    return None if trace is None else 100.0 * trace["idle_share"]
