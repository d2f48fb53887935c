"""95th percentile over the window's replies of ``queue_wait_s``: submit to
admission into a slot, on the server's clock."""

import numpy as np

NAME, UNIT, LAYER, MOVES = ("queue_wait_p95_ms", "ms", "serving scheduler",
                            "ttft_p95_ms")
SOURCE = "program_span"


def read(sources):
    waits = sources.get("queue_wait_ms")
    return float(np.percentile(waits, 95)) if waits else None
