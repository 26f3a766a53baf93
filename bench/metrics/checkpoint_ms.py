"""Host time per period in `period.checkpoint` (the service's snapshot
of its whole state and the chain head), in ms. From the program's span
record, over the traced periods."""
import progspans


def read(ctx):
    snap = progspans.snapshot()
    if snap is None:
        return None
    return progspans.per_period_ms(
        snap, progspans.n_periods(ctx),
        lambda name: name == "period.checkpoint")
