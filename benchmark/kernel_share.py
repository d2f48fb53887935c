"""Shared by the per-layer readers of kernels: a configuration's ``kernels``
block names each kernel family, the trace names that belong to it and the
function that gives the FLOPs and bytes one step of it needs."""

from common import resolve


def kernel_seconds(sources, family: str):
    """Device seconds of the family's kernels inside the traced window, or
    None where the configuration has no such kernel or none ran."""
    trace = sources.get("trace")
    spec = sources["config"].get("kernels", {}).get(family)
    if trace is None or spec is None:
        return None
    seconds = sum(s for name, s in trace["device_ops"]
                  if any(m in name for m in spec["match"]))
    return seconds or None


def least_seconds(sources, family: str):
    """The least time the chip could take for the family's work in the
    traced window: the larger of operations over peak FLOP/s and bytes
    over peak bytes/s, for every step the window ran."""
    spec = sources["config"]["kernels"][family]
    flops, bytes_ = resolve(spec["work"])(sources["config"]["sizes"],
                                          sources["batch"])
    steps = sources["trace"]["module_runs"] * sources["steps_per_epoch"]
    peak = sources["peak"]
    return steps * max(flops / peak["flops_per_s"],
                       bytes_ / peak["hbm_bytes_per_s"])
