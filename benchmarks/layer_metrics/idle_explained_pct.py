"""A metric of the measurement: the share of the device's idle time in the
capture that the program's own host spans account for — host_prep, dispatch
and detok of every step sample taken during the capture plus the engine
thread's loop phases (admit, other, wait), over window_s - busy_s of the
trace. It may pass 100: the capture's samples are aligned to a fraction of
a second, and a decode scan's `dispatch` overlaps the device. Since the
engine thread keeps one step in flight (it composes and emits while the chip
runs another step), its spans overlap busy time as a rule, and in a cell the
host bounds the share passes 100 by far (181 on the sparse cell; PERF.md
section 6, PR 31): read it as the host's busy seconds over the device's idle
seconds. Well under 100 means the rest of the idle time lies between ops
inside the device programs, where no overlap of host work can reach it. None
without a trace, without samples of the capture, or where the samples lack
the loop fields (a program older than PR 24)."""
from benchmarks.lib import steps

LOOP = ("loop_admit_ms", "loop_other_ms", "loop_wait_ms")


def read(ctx):
    taken = ctx.trace_steps
    if not ctx.trace or not taken \
            or not all(f in s for s in taken for f in LOOP):
        return None
    idle_s = ctx.trace["window_s"] - ctx.trace["busy_s"]
    if idle_s <= 0:
        return None
    host_ms = sum(steps.host_ms(s) + sum(float(s[f]) for f in LOOP)
                  for s in taken)
    return 100.0 * host_ms / 1e3 / idle_s
