"""Of the window's replies to requests sent with a shared prefix, the share
that the engine marked ``warm`` (joined over cached KV)."""

NAME, UNIT, LAYER, MOVES = ("prefix_hit_share", "%", "KV / prefix cache",
                            "ttft_p95_ms")
SOURCE = "program_counter"


def read(sources):
    share = sources.get("prefix_hit_share")
    return None if share is None else 100.0 * share
