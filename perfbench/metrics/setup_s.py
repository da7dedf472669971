"""Process start to the opening of the window: weights, engine, program
loads and compiles, the prefix fill, the warm-up and the traffic's ramp."""


def read(ctx):
    return ctx.setup_s
