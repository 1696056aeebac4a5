"""Scheduler and engine: mean host milliseconds per
``InferenceServer.step()`` in which the host did not wait on the device
-- each ``serve.step`` span of the traced window less the
``serve.device_wait`` spans inside it (the step's blocking readbacks).
In a synchronous loop the chip waits for this work.  Moves
``output_tok_s``."""

STEP = "serve.step"
WAIT = "serve.device_wait"


def read(ctx):
    steps = [e for e in ctx.trace.host if e.name == STEP]
    if not steps:
        return None
    waits = [e for e in ctx.trace.host if e.name == WAIT]
    host_ns = 0.0
    for s in steps:
        waited = sum(w.dur for w in waits
                     if (w.plane, w.line) == (s.plane, s.line)
                     and s.start <= w.start and w.end <= s.end)
        host_ns += s.dur - waited
    return 1e-6 * host_ns / len(steps)
