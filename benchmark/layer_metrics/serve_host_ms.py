"""Mean of the engine's ``serve.host_seconds`` over the window: the host's
work between two decode steps (sum / count of the histogram)."""

NAME, UNIT, LAYER, MOVES = ("serve_host_ms", "ms", "serving scheduler",
                            "tpot_p95_ms")
SOURCE = "program_span"


def read(sources):
    mean = (sources.get("histograms") or {}).get("serve.host_seconds")
    return None if mean is None else 1e3 * mean
