"""Own device seconds per round of the Adam updates (scope
``feds/train/adam``)."""
import scopes


def read(ctx):
    return scopes.seconds_per("feds/train/adam", ctx["work"]["rounds"])
