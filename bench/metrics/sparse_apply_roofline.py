"""The Eq. 4 masked-update kernel's share of its roofline, in %: one call
per sparse round, over every client's real shared rows."""
import costs

OP = "sparse_apply_pallas"


def read(ctx):
    tr, work = ctx["trace"], ctx["work"]
    if not tr.op_calls(OP):
        return None
    flops, nbytes = costs.sparse_apply_cost(sum(ctx["shapes"]["shared"]),
                                            ctx["cfg"]["dim"])
    n = work["sparse_rounds"]
    return costs.roofline_pct(n * flops, n * nbytes, tr.op_s(OP), ctx["peak"])
