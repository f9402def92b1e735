"""The whole serving step's share of the chips' peak: the operations the
model needs for the tokens of the slice (each prompt served in it, and
each decode token delivered in it, at its context) over the slice's
seconds times the chips times their peak bf16 FLOP/s.  The operations
are counted by the configuration's block (``prompt_flops``,
``decode_token_flops``)."""


def read(ctx):
    if not ctx.prompt_lens and not ctx.decode_contexts:
        return None
    flops = sum(ctx.block.prompt_flops(ctx.spec, p) for p in ctx.prompt_lens)
    flops += sum(ctx.block.decode_token_flops(ctx.spec, c)
                 for c in ctx.decode_contexts)
    return 100.0 * flops / (ctx.slice_s * ctx.chips * ctx.peaks.flops_bf16)
