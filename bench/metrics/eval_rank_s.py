"""Own device seconds per eval call of the rank counts and the metric block
(scope ``feds/eval/rank``)."""
import scopes


def read(ctx):
    return scopes.seconds_per("feds/eval/rank", ctx["work"]["calls"])
