"""(padded - real) / padded token positions, summed over the window's step
profiler samples."""


def read(ctx):
    padded = sum(int(s.get("padded_tokens") or 0) for s in ctx.steps or ())
    if padded <= 0:
        return None
    real = sum(int(s.get("tokens") or 0) for s in ctx.steps)
    return 100.0 * (padded - real) / padded
