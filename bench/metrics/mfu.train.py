"""Share of the chips' bf16 peak: the operations of the structures the
window's steps consumed (the family's FLOP count, 9 forward passes each)
over window seconds x chips x peak."""


def read(ctx):
    if not ctx.train_steps or not ctx.work_flops:
        return None
    return 100.0 * ctx.work_flops / (ctx.window_s * ctx.chips
                                     * ctx.peak["flops_bf16"])
