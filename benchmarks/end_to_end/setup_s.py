"""Process start to the first measured request: build, start, /health,
warm-up of every shape, ramp. Host clock."""


def read(ctx):
    return ctx.set_up_s
