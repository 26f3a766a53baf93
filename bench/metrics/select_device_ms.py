"""Device-busy time per period program of the ops in the program's
`select` scope, reveal verification and partner selection (Eq. 6-8:
the code distances and the selection kernel), in ms. Ops are mapped to
phases by the program's `repro.spans.op_scopes()`; times come from the
device trace."""
import progspans


def read(ctx):
    return progspans.read_phase(ctx, "select")
