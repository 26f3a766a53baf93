"""Share of its roofline the LSH projection kernel reaches: the least
time the chip needs for 2*M*P*bits operations or M*P*4 + M*bits/8
bytes, whichever bounds it, per call, over the measured time of each
call of the kernel in the trace, in percent."""
import devtrace as tr
import work

KERNEL = "lsh_project_sums_batched"


def read(ctx):
    lo, hi = ctx["window"]
    ns, calls = tr.kernel_ns(ctx["events"], KERNEL, lo, hi)
    if not calls or not ns:
        return None
    least, _bound = work.min_seconds(*ctx["lsh"], ctx["peaks"])
    if least <= 0:
        return None
    return 100.0 * least * calls / (ns / 1e9)
