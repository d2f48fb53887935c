"""Mean of the engine's ``serve.step_seconds`` over the window: one decode
step of the whole batch, dispatch to readback.  Sum / count of the
histogram: its buckets are too coarse for a tail."""

NAME, UNIT, LAYER, MOVES = ("serve_step_ms", "ms", "serving scheduler",
                            "tpot_p95_ms")
SOURCE = "program_span"


def read(sources):
    mean = (sources.get("histograms") or {}).get("serve.step_seconds")
    return None if mean is None else 1e3 * mean
