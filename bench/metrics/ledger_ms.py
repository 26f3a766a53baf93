"""Host time per period in the ledger spans (`ledger.publish` of the
chain publisher; `ledger.collect`, `ledger.publish`, `ledger.fetch` of
the service's transport), in ms. From the program's span record, over
the traced periods."""
import progspans


def read(ctx):
    snap = progspans.snapshot()
    if snap is None:
        return None
    return progspans.per_period_ms(
        snap, progspans.n_periods(ctx),
        lambda name: name.startswith(progspans.LEDGER))
