"""Executor: share of the window in which the commit path was blocked,
waiting on the host stage (``stall.host``) or for a write-queue slot
(``stall.write``)."""


def read(ctx):
    stalled = ctx.spans.get("stall.host", 0.0) + ctx.spans.get(
        "stall.write", 0.0)
    return 100.0 * stalled / ctx.window_s
