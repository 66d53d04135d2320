"""The distance-family eval kernel's share of its roofline, in %
(``costs.cand_kernel_roofline``)."""
import costs


def read(ctx):
    return costs.cand_kernel_roofline(ctx, "dist_cand_score_pallas")
