"""Mean device-idle time between one period program's end and the next
one's start: the host's work between periods (publish, history,
checkpoint, dispatch). From the device trace."""
import devtrace as tr


def read(ctx):
    lo, hi = ctx["window"]
    gaps = tr.host_gaps_ns(ctx["events"], lo, hi)
    return sum(gaps) / len(gaps) / 1e6 if gaps else None
