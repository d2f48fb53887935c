"""Model FLOP/s utilisation: the operations forward and backward need for
a row (``flops.py``, by the function the configuration names; causal
attention at its executed half, nothing recomputed) times the window's
rows per second per chip, over the chip's published peak."""

from common import resolve

NAME, UNIT, LAYER, MOVES = ("model_flops_util", "%", "step program",
                            "train_samples_per_s")
SOURCE = "host_clock"


def read(sources):
    window, peak = sources.get("window"), sources.get("peak")
    name = sources["config"].get("flops")
    if window is None or peak is None or not name:
        return None
    per_row = resolve(name)(sources["config"]["sizes"])
    return 100.0 * per_row * window["rate_per_chip"] / peak["flops_per_s"]
