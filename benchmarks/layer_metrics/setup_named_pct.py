"""What of the harness's `setup_s` the program's own spans name: 100 x
(`ready_s` + `warm_compile_s`) over `ctx.set_up_s`. The rest is the
harness's `make`, the spawn and the poll of /health, the warm-up requests'
own generation (their steps after each first call) and the ramp. From a
program older than PR 67 `ready_s` reads 0.0, and this what follows from its
`wall_ms` alone. (The last of the seven: it also prints the `start_up` note,
`_setup.say`.)"""
from benchmarks.layer_metrics import _setup


def read(ctx):
    _setup.say(ctx)
    return 100.0 * (_setup.ready_s(ctx) + _setup.warm_compile_s(ctx)) \
        / ctx.set_up_s
