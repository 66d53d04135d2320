"""The bilinear-family eval kernel's share of its roofline, in %
(``costs.cand_kernel_roofline``)."""
import costs


def read(ctx):
    return costs.cand_kernel_roofline(ctx, "bilinear_cand_score_pallas")
