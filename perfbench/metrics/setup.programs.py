"""XLA executables made ready during set-up, compiled or read back from
JAX's persistent cache, counted by JAX's monitoring events."""


def read(ctx):
    return float(ctx.setup_programs)
