"""Seconds the period loop spent in calls that traced and compiled its
period program (`period.compile` spans), over the whole run. From the
program's span record."""
import progspans


def read(ctx):
    snap = progspans.snapshot()
    total = (snap or {}).get("totals", {}).get("period.compile")
    return total["ns"] / 1e9 if total else None
