"""The distance-family eval kernel's share of its roofline, in %.  Per eval
call and leg (tail, head), each client's real queries are scored against
its real entities: the queries and candidate rows read once and the score
block written once."""
import costs

OP = "dist_cand_score_pallas"


def read(ctx):
    cfg, sh, tr = ctx["cfg"], ctx["shapes"], ctx["trace"]
    if not tr.op_calls(OP):
        return None
    flops = nbytes = 0.0
    for q, e in zip(sh["queries"], sh["entities"]):
        f, b = costs.cand_score_cost(cfg["method"], q, e, cfg["dim"])
        flops, nbytes = flops + 2 * f, nbytes + 2 * b
    n = ctx["work"]["calls"]
    return costs.roofline_pct(n * flops, n * nbytes, tr.op_s(OP), ctx["peak"])
