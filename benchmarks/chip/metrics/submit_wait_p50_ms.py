"""The median, over requests due in the window, of the time from when a
request was due until ``ServeFrontend.submit`` returned its handle, host
clock: the wait for the frontend's lock, which the scheduler holds
through each engine step.  It is part of every time to first token."""
import statistics


def read(ctx):
    if not ctx.submit_wait_ms:
        return None
    return statistics.median(ctx.submit_wait_ms)
