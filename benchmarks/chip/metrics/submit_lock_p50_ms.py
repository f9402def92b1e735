"""The median, over the requests submitted in the traced slice, of the
time ``ServeFrontend.submit`` waited for the frontend's bookkeeping lock
(``_books``), measured inside ``submit`` (frontend counter
``submit_lock_s``, one entry per request, in
``stats["engine"]["frontend"]``).  No engine work holds that lock: the
scheduler takes it only to read cancels and hand over results, never
across a prefill or a decode window.  Unlike ``submit_wait_p50_ms`` it
leaves out the benchmark's sender pool and the interpreter lock."""
import statistics

KEY = "submit_lock_s"


def read(ctx):
    lists = [s.get("engine", {}).get("frontend", {}).get(KEY)
             for s in (ctx.stats0, ctx.stats1)]
    if None in lists:
        return None
    waits = lists[1][len(lists[0]):]
    if not waits:
        return None
    return 1e3 * statistics.median(waits)
