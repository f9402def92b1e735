"""Device time of the prefill programs per thousand prompt tokens: the
programs' time in the trace (mean over chips) over the prompt tokens of
the requests whose first token was delivered in the slice."""

PROGRAM = r"prefill"


def read(ctx):
    tokens = sum(ctx.prompt_lens)
    ns = ctx.per_chip_ns(PROGRAM)
    if tokens == 0 or ns == 0:
        return None
    return ns / 1e6 / (tokens / 1e3)
