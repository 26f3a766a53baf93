"""Device-busy time per period: the union of op intervals inside each
execution of the period program, averaged. From the device trace."""
import devtrace as tr


def read(ctx):
    lo, hi = ctx["window"]
    busy = tr.segment_busy_ns(ctx["events"], lo, hi)
    return sum(busy) / len(busy) / 1e6 if busy else None
