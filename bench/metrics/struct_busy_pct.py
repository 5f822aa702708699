"""Struct stage: share of the window the ``struct`` span was open (chunk
dispatch, the kernel, the device-to-host copy and the host combine)."""


def read(ctx):
    if "struct" not in ctx.spans:
        return None
    return 100.0 * ctx.spans["struct"] / ctx.window_s
