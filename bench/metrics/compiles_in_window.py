"""Programs compiled, or loaded from the compilation cache, inside the
window (JAX's ``backend_compile_duration`` events).  Set-up runs the
warm-up shard through the same path, so this reads 0 unless the program
compiles something per call or meets a shape the warm-up shard did not
have."""


def read(ctx):
    return float(ctx.compiles_in_window)
