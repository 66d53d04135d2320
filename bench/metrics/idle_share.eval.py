"""Share of the traced eval calls in which no operation ran on the device, in %."""


def read(ctx):
    tr = ctx["trace"]
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
