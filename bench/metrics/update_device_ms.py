"""Device-busy time per period program of the ops in the program's
`update` scope, the local updates on the combined objective (Alg. 1
l.19), in ms. Ops are mapped to phases by the program's
`repro.spans.op_scopes()`; times come from the device trace."""
import progspans


def read(ctx):
    return progspans.read_phase(ctx, "update")
