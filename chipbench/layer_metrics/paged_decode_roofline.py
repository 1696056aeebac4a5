"""Paged decode attention kernel: share of its roofline.  The least time
is the live K and V of every decoded row's context, with its query and
output, over the memory peak; the time is that of the decode kernel's
device events.  Moves ``output_tok_s``."""

# the paged decode kernel: block tables (B, P), positions (B,), queries
# (B, H, D) -- the prefill kernel's queries are (1, S, H, D)
KERNEL = (r"custom-call\(s32\[\d+,\d+\]\{[^}]*\} %[\w.\-]+, "
          r"s32\[\d+\]\{[^}]*\} %[\w.\-]+, bf16\[\d+,\d+,\d+\]\{")


def read(ctx):
    _, secs = ctx.trace.kernel(KERNEL)
    byts = sum(ctx.work.paged_decode_bytes(ctx.cfg, s.decode_ctx)
               for s in ctx.steps if s.decode_ctx)
    if secs <= 0 or byts == 0:
        return None
    return 100.0 * byts / ctx.peaks["hbm_bytes_s"] / secs
