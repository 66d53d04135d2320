"""Own device seconds per eval call of the candidate score kernel (scope
``feds/eval/score``)."""
import scopes


def read(ctx):
    return scopes.seconds_per("feds/eval/score", ctx["work"]["calls"])
