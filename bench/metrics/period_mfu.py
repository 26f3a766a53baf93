"""Client-model FLOPs a period needs (forward and backward of the
local update, the exchange's forwards, the evaluation; counted from
shapes, no recompute) over the traced periods' wall time and the
chip's bf16 peak, in percent."""


def read(ctx):
    periods = ctx["periods_s"]
    if not periods or not ctx["period_flops"]:
        return None
    rate = ctx["period_flops"] * len(periods) / sum(periods)
    return 100.0 * rate / ctx["peaks"]["bf16_flops_per_s"]
