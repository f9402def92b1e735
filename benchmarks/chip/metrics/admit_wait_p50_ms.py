"""The median, over the requests whose first token came in the traced
slice, of the time from when ``ServeFrontend.submit`` handed a request
to the scheduler (``queued_at``) until a prefill call took it
(``admitted_at``): the admission queue (frontend counter
``admit_wait_s``, one entry per request, in
``stats["engine"]["frontend"]``)."""
import statistics

KEY = "admit_wait_s"


def read(ctx):
    lists = [s.get("engine", {}).get("frontend", {}).get(KEY)
             for s in (ctx.stats0, ctx.stats1)]
    if None in lists:
        return None
    waits = lists[1][len(lists[0]):]
    if not waits:
        return None
    return 1e3 * statistics.median(waits)
