"""Share of the chips' bf16 peak: the operations of the evaluations
answered in the window (the family's FLOP count, 3 forward passes each)
over window seconds x chips x peak."""


def read(ctx):
    if ctx.served is None or not ctx.work_flops:
        return None
    return 100.0 * ctx.work_flops / (ctx.window_s * ctx.chips
                                     * ctx.peak["flops_bf16"])
