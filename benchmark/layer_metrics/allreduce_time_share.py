"""Share of the traced window that device 0 spends in all-reduce operations
(own time of the trace's ``all-reduce`` rows over the window).  None where
the program has no such operation on the device's "XLA Ops" line."""

NAME, UNIT, LAYER, MOVES = ("allreduce_time_share", "%", "collectives",
                            "train_samples_per_s")
SOURCE = "device_trace"


def read(sources):
    trace = sources.get("trace")
    if trace is None:
        return None
    seconds = sum(s for name, s in trace["device_ops"]
                  if "all-reduce" in name)
    return 100.0 * seconds / trace["window_s"] if seconds else None
