"""What the program says of its own start-up, for the seven metric files
beside this one (layer `start-up`, all moving `setup_s`).

Since PR 67 the program keeps a start-up ledger (telemetry/stepprof.py): it
sets `ollamamq_ready_seconds` and `ollamamq_startup_seconds{phase}` once,
when its HTTP server starts to listen; it counts every program jax's backend
built or fetched in `ollamamq_compile_programs_total{cache}`; and a compile
event of its ledger — a step program's FIRST CALL, `wall_ms` from its start
to its return — also says what that wall was: `trace_ms`, `lower_ms`,
`backend_ms` (the compile on a miss of the persistent cache, the retrieval
on a hit) and the rest, `first_run_ms`. `ctx.compile_events` are those
events as /debug/stepprof lists them after the window, `ctx.prom1` the
exposition at the window's end.

A program older than PR 67 has none of the series and its events carry
`site / key / wall_ms / ts` alone. The readers then give a NUMBER all the
same (lib/result.py fails a traced line that lacks a listed metric, and a
PR's parent runs under that PR's benchmark files): a gauge, a counter or a
field that is not there reads 0.0, as each reader's docstring says."""
from benchmarks.lib import stats

READY = "ollamamq_ready_seconds"
PHASE = "ollamamq_startup_seconds"
PROGRAMS = "ollamamq_compile_programs_total"


def series(ctx, name: str, **labels) -> float:
    """The series' value at the window's end; 0.0 where the exposition
    (or the series) is not there."""
    if not ctx.prom1:
        return 0.0
    return stats.prom_value(ctx.prom1, name, **labels) or 0.0


def warm_events(ctx) -> list:
    """The ledger's events that ENDED before the window began: the warm-up's
    first calls. One that ends inside the window is `window_compiles`'s."""
    return [e for e in ctx.compile_events or ()
            if e["ts"] < ctx.window_epoch[0]]


def warm_s(ctx, *fields) -> float:
    """Seconds of `fields` summed over those events; a field an event lacks
    counts 0."""
    return sum(float(e.get(f, 0.0)) for e in warm_events(ctx)
               for f in fields) / 1e3


def ready_s(ctx) -> float:
    return series(ctx, READY)


def warm_compile_s(ctx) -> float:
    return warm_s(ctx, "wall_ms")


def say(ctx) -> None:
    """One earlier line of a traced run, for whoever reads it by hand
    (PERF.md section 5's tables): the start by phase, the programs by the
    cache's word, and the warm-up's first calls rung by rung —
    [site, key, wall, trace, lower, backend, first run (ms), cache]."""
    if getattr(ctx, "say", None) is None:
        return
    split = ("wall_ms", "trace_ms", "lower_ms", "backend_ms", "first_run_ms")
    ctx.say("start_up", ready_s=ready_s(ctx), set_up_s=ctx.set_up_s,
            phases={ph: series(ctx, PHASE, phase=ph) for ph in (
                "import", "backend", "weights", "place", "alloc", "serve")},
            programs={c: series(ctx, PROGRAMS, cache=c)
                      for c in ("hit", "miss", "off")},
            rungs=[[e["site"], e["key"]] + [e.get(f) for f in split]
                   + [e.get("cache")] for e in warm_events(ctx)])
