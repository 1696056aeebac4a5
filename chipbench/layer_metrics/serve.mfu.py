"""Whole step: the model operations that the traced window served --
every prompt token and every decoded token, with the channels the plan
prunes left out and attention over each token's own context -- as a
percent of the chip's bf16 peak over the window.  Moves
``output_tok_s``, and bounds every kernel's share from above."""


def read(ctx):
    flops = sum(ctx.work.model_flops(ctx.cfg, ctx.bits, s.prefill,
                                     s.decode_ctx)
                for s in ctx.steps)
    window_s = ctx.trace.window_s
    if flops <= 0 or window_s <= 0:
        return None
    return 100.0 * flops / (window_s * ctx.peaks["bf16_flops"])
