"""Mamba-2 decode-state kernel (``ssm_decode_step``): share of its
roofline.  The least time is the state read and written, with each
row's x, y, B, C and dt, of every decoded row in every Mamba-2 layer
(the work module's ``ssm_decode_bytes``) over the memory peak; the time
is that of the kernel's device events, matched by its name.  Moves
``output_tok_s``."""

KERNEL = r"^%?ssm_decode_step(\.\d+)? = "


def read(ctx):
    count = getattr(ctx.work, "ssm_decode_bytes", None)
    if count is None:
        return None
    _, secs = ctx.trace.kernel(KERNEL)
    byts = sum(count(ctx.cfg, len(s.decode_ctx))
               for s in ctx.steps if s.decode_ctx)
    if secs <= 0 or byts == 0:
        return None
    return 100.0 * byts / ctx.peaks["hbm_bytes_s"] / secs
