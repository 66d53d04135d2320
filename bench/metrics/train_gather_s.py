"""Own device seconds per round of the training step's entity and relation row
gathers (scope ``feds/train/gather``)."""
import scopes


def read(ctx):
    return scopes.seconds_per("feds/train/gather", ctx["work"]["rounds"])
