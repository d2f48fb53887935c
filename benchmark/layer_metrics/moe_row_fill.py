"""Rows the routed experts' grouped matmuls needed over rows they ran
(%): the assignments that landed on the experts held here, over the rows
of the tiles their stretches of the row buffer take (every expert's
stretch is whole 128-row tiles, one at least).  Read from the default
registry's ``moe.rows_needed`` / ``moe.rows_run``, which a trainer adds
to from the routed layers' state (their last step) whenever it brings
the variables back to the host.  A program without such layers, or from
before they counted, has neither counter and the metric is left out."""

NAME, UNIT, LAYER, MOVES = ("moe_row_fill", "%", "experts",
                            "train_samples_per_s")
SOURCE = "program_counter"


def read(sources):
    if sources.get("window") is None:
        return None
    from distkeras_tpu.obs.registry import default_registry
    needed, run = (default_registry().get(name)
                   for name in ("moe.rows_needed", "moe.rows_run"))
    if needed is None or run is None or not run.value:
        return None
    return 100.0 * needed.value / run.value
