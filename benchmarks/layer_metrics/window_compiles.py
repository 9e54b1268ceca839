"""Compile events of the server's ledger whose timestamp falls inside the
measured window. Should read 0: every shape is warmed during set-up."""


def read(ctx):
    e0, e1 = ctx.window_epoch
    return sum(1 for e in ctx.compile_events if e0 <= e["ts"] <= e1)
