"""Shared by the readers of what the program's tracing names (PR 26): the
compile split on the trainers' ``jit_compile`` records, and the device
time of one named Pallas kernel."""

MOSAIC_ROW = "tpu_custom_call:"


def compile_field_sum(sources, *fields):
    """The sum of ``fields`` over the ``jit_compile`` records before the
    window, or None where there is no such record or one lacks a field (a
    program from before the split put none on its records)."""
    spans = sources.get("setup_compile_spans")
    if not spans or any(f not in s for s in spans for f in fields):
        return None
    return sum(s[f] for s in spans for f in fields)


def kernel_time_share(sources, kernel: str):
    """Share (%) of device 0's busy time inside the traced window that the
    Mosaic calls named ``kernel`` take, or None where no such row is in
    the trace (a program whose kernels carry no name, a model without the
    kernel).  XLA names a Mosaic call by the scope that holds it, so the
    row reads ``tpu_custom_call:<kernel>``, and would still hold the name
    were the scopes around it ever folded in."""
    trace = sources.get("trace")
    if trace is None:
        return None
    seconds = sum(s for name, s in trace["device_ops"]
                  if name.startswith(MOSAIC_ROW) and kernel in name)
    return 100.0 * seconds / trace["busy_s"] if seconds else None
