"""Device-busy time per period program of the ops in the program's
`exchange` scope, the reference-set exchange (neighbor forwards, Eq. 3
losses, the fused exchange kernel), in ms. Ops are mapped to phases by
the program's `repro.spans.op_scopes()`; times come from the device
trace."""
import progspans


def read(ctx):
    return progspans.read_phase(ctx, "exchange")
