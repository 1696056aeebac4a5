"""Scheduler and engine: host milliseconds per ``InferenceServer.step()``,
from the harness's span around each step of the traced window, over all
its steps.  Moves ``output_tok_s``: a decode step yields one token per
busy slot."""


def read(ctx):
    if not any(s.decode_ctx for s in ctx.steps):
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in ctx.steps) / len(ctx.steps)
