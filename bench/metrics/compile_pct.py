"""Struct stage: share of the window that JAX spent compiling, from the
program's ``compile.trace``, ``compile.lower`` and ``compile.backend``
spans (jaxpr tracing, lowering to MLIR, and the XLA compile or a load
from the compilation cache).  The program books a phase that another
holds inside the outer one, so on one thread the three add up to the
union of the compile time.  A program that watches its compiles has the
three spans from the start, at zero until something compiles; one that
does not has none, and this reads nothing."""

PHASES = ("compile.trace", "compile.lower", "compile.backend")


def read(ctx):
    if not any(p in ctx.spans for p in PHASES):
        return None
    return 100.0 * sum(ctx.spans.get(p, 0.0) for p in PHASES) / ctx.window_s
