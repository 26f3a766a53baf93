"""Share of its roofline the fused exchange kernel reaches: the least
time for the bytes of the (M, N, R, C) neighbor web, own logits,
labels and outputs, or for 10 operations per web element, whichever
bounds it, per call, over the measured time of its calls, in percent."""
import devtrace as tr
import work

KERNEL = "fused_exchange"


def read(ctx):
    lo, hi = ctx["window"]
    ns, calls = tr.kernel_ns(ctx["events"], KERNEL, lo, hi)
    if not calls or not ns:
        return None
    least, _bound = work.min_seconds(*ctx["exchange"], ctx["peaks"])
    if least <= 0:
        return None
    return 100.0 * least * calls / (ns / 1e9)
