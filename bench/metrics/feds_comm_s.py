"""Own device seconds per round of the FedS communication round: upload,
aggregate, download, sync and the shared-row gather and scatter-back
between them (scope ``feds/comm`` and every scope inside it)."""
import scopes


def read(ctx):
    return scopes.seconds_per("feds/comm", ctx["work"]["rounds"], under=True)
