"""Of the programs jax's backend was asked for since the process started
and the persistent cache had a word on (`ollamamq_compile_programs_total`,
cache="hit" and cache="miss", at the window's end), the percentage it
fetched: whether the run was warm. Programs under the cache's thresholds
(cache="off": under a second of compile) are in neither term. 0.0 where jax
reported neither — no cache directory, or a program older than PR 67, which
has no such counter."""
from benchmarks.layer_metrics import _setup


def read(ctx):
    hit = _setup.series(ctx, _setup.PROGRAMS, cache="hit")
    miss = _setup.series(ctx, _setup.PROGRAMS, cache="miss")
    return 100.0 * hit / (hit + miss) if hit + miss else 0.0
