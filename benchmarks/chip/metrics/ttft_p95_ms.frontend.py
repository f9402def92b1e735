"""The 95th percentile (nearest rank) of the time to first token over
every request due in the window, from when it was due, host clock.  In a
50 s window below the knee only some 120 requests are due, so 6 lie
beyond it: too few for a bound (the end-to-end metric is the median,
``ttft_p50_ms``).  The tail is mostly the wait in ``submit``."""
import math


def read(ctx):
    if not ctx.ttft_ms:
        return None
    v = sorted(ctx.ttft_ms)
    return v[max(0, math.ceil(0.95 * len(v)) - 1)]
