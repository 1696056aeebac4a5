"""Quantized linear (``PackedLinear`` -> ``quant_matmul``): share of its
roofline.  The least time is counted from the plan for every projection
call of the traced steps, on the real rows of each call (decoded rows of
a decode step, the prompt of a prefill); the time is that of the
``quant_matmul`` kernel's device events.  Moves ``output_tok_s``."""

# the quant_matmul kernel: an f32 result from int8 operands
KERNEL = r"= f32\[\d+,\d+\]\{[^}]*\} custom-call\(s8\["


def read(ctx):
    if ctx.bits is None:
        return None
    _, secs = ctx.trace.kernel(KERNEL)
    if secs <= 0:
        return None
    rows = []
    for s in ctx.steps:
        rows.extend(s.prefill)
        if s.decode_ctx:
            rows.append(len(s.decode_ctx))
    least = ctx.work.qlinear_roofline_s(ctx.cfg, ctx.bits, rows, ctx.peaks)
    return 100.0 * least / secs
