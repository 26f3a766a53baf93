"""Host time per period in `period.history` (the loop's pull of each
round's scalar metrics, `extract_history`), less any span under it, in
ms. From the program's span record, over the traced periods."""
import progspans


def read(ctx):
    snap = progspans.snapshot()
    if snap is None:
        return None
    return progspans.per_period_ms(snap, progspans.n_periods(ctx),
                                   lambda name: name == "period.history",
                                   self_time=True)
