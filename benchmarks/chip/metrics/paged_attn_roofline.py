"""Share of its roofline that the paged decode-attention kernel reaches.

The least time the chip could take is the larger of the bytes the kernel
needs over peak HBM bandwidth and its operations over peak FLOP/s.  The
bytes needed are, for every decode token delivered in the slice, the K
and V of its whole context in every layer, as one chip of the mesh holds
them.  The kernel's time is the sum of its events in the trace (mean over
chips)."""
from benchmarks.chip import model_math

# The kernel's own events: an operation's name is its HLO text, which
# names its operands too.
KERNEL = r"^%?paged_attn"


def read(ctx):
    ns = ctx.per_chip_ns(KERNEL, line="ops")
    if not ctx.decode_contexts or ns == 0:
        return None
    nbytes, flops = model_math.paged_attn_need(ctx.spec, ctx.decode_contexts,
                                               ctx.tp)
    bound = max(nbytes / ctx.peaks.hbm_bytes_per_s,
                flops / ctx.peaks.flops_bf16)
    return 100.0 * bound / (ns / 1e9)
