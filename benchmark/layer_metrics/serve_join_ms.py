"""Mean of the engine's ``serve.join_seconds`` over the window: admitting
one request into a slot, prefill included (sum / count of the histogram)."""

NAME, UNIT, LAYER, MOVES = ("serve_join_ms", "ms", "serving scheduler",
                            "ttft_p95_ms")
SOURCE = "program_span"


def read(sources):
    mean = (sources.get("histograms") or {}).get("serve.join_seconds")
    return None if mean is None else 1e3 * mean
