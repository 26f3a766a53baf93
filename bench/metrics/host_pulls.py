"""Device-to-host transfers per period in the host loop (each history
scalar, each array the publisher or transport pulls, each membership
mask and checkpointed leaf): the program's `host_pulls` counter, over
the traced periods."""
import progspans


def read(ctx):
    snap = progspans.snapshot()
    if snap is None:
        return None
    return progspans.per_period_count(snap, progspans.n_periods(ctx),
                                      "host_pulls")
