"""Device: percent of the traced window in which no operation ran on
the chip (one minus the union of its operation intervals).  Moves
``output_tok_s``: the chip waits on the host between steps."""


def read(ctx):
    if ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
