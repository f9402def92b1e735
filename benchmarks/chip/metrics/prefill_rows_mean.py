"""Requests per prefill call over the traced slice: the frontend's
coalescing of same-bucket arrivals into one batched prefill (engine
counters ``prefill_batches``, ``prefill_batched_reqs`` and the bucket
hit/miss/fallback count of every prefill call)."""


def read(ctx):
    e0, e1 = ctx.stats0["engine"], ctx.stats1["engine"]

    def delta(*keys):
        return sum(e1[k] - e0[k] for k in keys)

    calls = delta("prefill_bucket_hits", "prefill_bucket_misses",
                  "prefill_bucket_fallbacks")
    if calls <= 0:
        return None
    batches = delta("prefill_batches")
    rows = delta("prefill_batched_reqs") + calls - batches
    return rows / calls
