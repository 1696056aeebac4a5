"""Model step: mean device milliseconds per execution of the greedy
decode program (``decode_greedy``), from the device's module line in the
trace.  Moves ``output_tok_s``."""

PROGRAM = "decode_greedy"


def read(ctx):
    n, secs = ctx.trace.programs().get(PROGRAM, (0, 0.0))
    return 1e3 * secs / n if n else None
