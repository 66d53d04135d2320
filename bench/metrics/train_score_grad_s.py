"""Own device seconds per round of the scoring loss's forward and backward
pass (scope ``feds/train/score_grad``)."""
import scopes


def read(ctx):
    return scopes.seconds_per("feds/train/score_grad", ctx["work"]["rounds"])
