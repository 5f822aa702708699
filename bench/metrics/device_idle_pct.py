"""Device: share of the traced slice in which no operation ran on the
chip (one minus the union of the device-op intervals over the slice)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share
