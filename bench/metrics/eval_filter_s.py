"""Own device seconds per eval call of the filter-word unpacking and pads
(scope ``feds/eval/filter``)."""
import scopes


def read(ctx):
    return scopes.seconds_per("feds/eval/filter", ctx["work"]["calls"])
