"""Peak device memory in use over the run, on the fullest chip
(``memory_stats()["peak_bytes_in_use"]``)."""


def read(ctx):
    if not ctx.memory_peak_bytes:
        return None
    return ctx.memory_peak_bytes / 2**30
