"""Device-idle time between period programs that no span of the host
loop accounts for: `host_gap_ms`'s reading less the time per gap of
the loop's between-segment spans (dispatch or compile, history,
on_reselect, events, ledger, checkpoint, log: one period's after its
wait, the next one's before it), floored at 0, in ms."""
import progspans


def read(ctx):
    snap = progspans.snapshot()
    if snap is None or not ctx.get("window"):
        return None
    return progspans.untraced_gap_ms(ctx["events"], snap,
                                     progspans.n_periods(ctx),
                                     *ctx["window"])
