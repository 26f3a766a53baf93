"""Peak device memory in use over the run (the runtime's
peak_bytes_in_use after the window), in GB."""


def read(ctx):
    peak = ctx["memory_peak_bytes"]
    return peak / 1e9 if peak else None
