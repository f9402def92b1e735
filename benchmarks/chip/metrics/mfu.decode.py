"""The decode step's share of the chips' peak: the operations the model
needs for each decode token delivered in the traced slice, at its
context, over the slice's seconds times the chips times their peak bf16
FLOP/s, counted by the configuration's block (``decode_token_flops``).
It bounds the decode kernels' rooflines from the whole step, so a kernel
taken off the path still shows here."""


def read(ctx):
    if not ctx.decode_contexts:
        return None
    flops = sum(ctx.block.decode_token_flops(ctx.spec, c)
                for c in ctx.decode_contexts)
    return 100.0 * flops / (ctx.slice_s * ctx.chips * ctx.peaks.flops_bf16)
