"""Milliseconds per forward pass the engine thread WANTED to run and did not:
its wall outside the time it was blocked on the chip and its idle wait — the
window's samples' `total_ms` + `loop_admit_ms` + `loop_other_ms` -
`collect_ms` — minus the CPU seconds it used between the window's two scrapes
(engine_cpu_ms_per_step), over the samples' passes. The GIL held by the
server's thread and the scheduler are what is left; it can read a little
under zero where the blocking read spins before it sleeps. None where the
program exports no thread CPU clocks (older than PR 37)."""
from benchmarks.layer_metrics import _dry


def read(ctx):
    cpu = _dry.cpu_ms(ctx, "engine")
    if cpu is None or not ctx.steps or not all(
            f in s for s in ctx.steps
            for f in _dry.ENGINE_WALL + ("collect_ms",)):
        return None
    wall = sum(sum(float(s[f]) for f in _dry.ENGINE_WALL)
               - float(s["collect_ms"]) for s in ctx.steps)
    return _dry.per_pass(wall - cpu, ctx)
