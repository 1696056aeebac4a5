"""Model step: mean device milliseconds per execution of the paged
prefill program (``prefill_paged``), from the device's module line in
the trace.  Moves ``output_tok_s``: a step that admits requests runs
their prefills before its decode."""

PROGRAM = "prefill_paged"


def read(ctx):
    n, secs = ctx.trace.programs().get(PROGRAM, (0, 0.0))
    return 1e3 * secs / n if n else None
