"""Rate of the configuration's own dense work: the FLOPs that its
`layer_work` counts under "dense" for one period, over the traced
periods' mean wall time, in TFLOP/s. Nothing where the configuration
counts no dense work."""


def read(ctx):
    work = ctx["layer_work"].get("dense")
    periods = ctx["periods_s"]
    if work is None or not periods:
        return None
    return work[0] * len(periods) / sum(periods) / 1e12
