"""Scoring FLOP/s of the traced eval calls, as a share of the chip's peak,
in %: the real test triples, both legs, against each client's entities."""
import costs


def read(ctx):
    cfg, sh, tr = ctx["cfg"], ctx["shapes"], ctx["trace"]
    pairs = sum(q * e for q, e in zip(sh["queries"], sh["entities"]))
    flops = ctx["work"]["calls"] * costs.eval_flops(cfg["method"], cfg["dim"], pairs)
    return 100.0 * flops / tr.window_s / ctx["peak"]["flops_per_s"]
