"""Share of device-busy time in the WINDOW layers' attention launches: self
time of the Mosaic custom calls `_swa.ATTEND` names over busy_s. 0 where
the trace holds no such launch (the jnp path: a rehearsal on the CPU); None
for a configuration without window layers."""
from benchmarks.layer_metrics import _swa
from benchmarks.lib import arch_window


def read(ctx):
    if not ctx.trace or not arch_window.window_layers(ctx.cell.config):
        return None
    seconds, launches = _swa.time_and_launches(ctx.trace)
    if not launches:
        return 0.0
    ctx.say("swa_attn_share", launches_in_trace=launches, measured_s=seconds,
            ms_a_launch=1e3 * seconds / launches)
    return 100.0 * seconds / ctx.trace["busy_s"]
