"""Test config: force JAX onto CPU with 8 virtual devices BEFORE jax import,
so mesh/sharding logic is exercised without a TPU (SURVEY.md §4)."""

from ollamamq_tpu.platform_force import force_cpu

force_cpu(8)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _compile_cache_stays_where_it_was():
    """`cli.main` run in-process past its flag checks (tests/test_fleet.py)
    leaves `jax_compilation_cache_dir` at `<checkout>/.jax_cache` for every
    file its worker runs afterwards, and a program read back from there is
    not the program compiled here (tests/test_weight_formats.py's layouts
    lost, two engines' ids differing): cases failed under `-n 6` and passed
    alone (ROADMAP C7)."""
    import jax

    before = jax.config.jax_compilation_cache_dir
    yield
    if jax.config.jax_compilation_cache_dir != before:
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


# Memory mappings a worker may hold when a test file ends before its jit
# caches are dropped. Every program XLA compiles for the CPU maps its code:
# a model's test file leaves 5-9 thousand mappings behind (`/proc/self/maps`;
# tests/test_k_exaone.py 8,352, tests/test_mimo_v2_flash.py 9,353), a worker
# under `--dist loadfile` runs ~14 files, and the kernel's
# `vm.max_map_count` is 65,530: past it the next compile's `mmap` fails and
# the worker dies inside `backend_compile_and_load` (a segmentation fault or
# an abort in whatever test compiles next, and a run that can hang on the
# replacement worker: PR 65's two whole runs, before this). `jax.clear_caches`
# gives all but ~700 back; what a later file needs again it compiles again.
MAPS_HIGH = 40_000


@pytest.fixture(autouse=True, scope="module")
def _jit_code_stays_under_the_map_limit():
    yield
    try:
        with open("/proc/self/maps") as f:
            held = sum(1 for _ in f)
    except OSError:  # (no procfs: nothing to count, nothing to do)
        return
    if held > MAPS_HIGH:
        import jax

        jax.clear_caches()


FILE_BUDGET_S = 400  # summed case time a file may hold (SKILL.md, Tier-1)


def pytest_terminal_summary(terminalreporter):
    """One line in the run's log (the driver keeps it): the suite's worker
    seconds, its largest file, and every file over the budget — `--dist
    loadfile` gives a file to ONE worker, so a large file started late is
    what the wall waits for, and xdist starts files by their case COUNT."""
    by_file = {}
    for reports in terminalreporter.stats.values():
        for rep in reports:
            if hasattr(rep, "duration") and getattr(rep, "nodeid", None):
                name = rep.nodeid.split("::")[0]
                by_file[name] = by_file.get(name, 0.0) + rep.duration
    if by_file:
        worst = max(by_file, key=by_file.get)
        over = sorted(f"{f} {s:.0f} s" for f, s in by_file.items()
                      if s > FILE_BUDGET_S)
        terminalreporter.write_line(
            f"worker seconds: {sum(by_file.values()):.0f} in {len(by_file)} "
            f"files, largest {worst} {by_file[worst]:.0f} s; over "
            f"{FILE_BUDGET_S} s a file: {', '.join(over) or 'none'}")


@pytest.fixture(scope="session")
def tiny_cfg():
    from ollamamq_tpu.config import MODEL_CONFIGS

    return MODEL_CONFIGS["test-tiny"]


@pytest.fixture(scope="session")
def tiny_params(tiny_cfg):
    import jax
    import jax.numpy as jnp
    from ollamamq_tpu.models import llama

    return llama.init_params(tiny_cfg, jax.random.PRNGKey(0), dtype=jnp.float32)


@pytest.fixture(scope="session")
def poison_trash_page():
    """`poison(pool, page_size, layer)`: the pool with its trash page (page
    0) set, in every layer, to a large FINITE value chosen by the layer
    under test — 1e30, -1e30, the dtype's largest. The attention kernels
    read that page where a block runs past a sequence's last page, masked
    to a weight of 0 (ops/pallas/kv_contract.py), so a kernel fed the
    poisoned pool must return every bit it returns on the clean one."""
    import jax.numpy as jnp

    def poison(pool, page_size, layer):
        value = (1e30, -1e30, float(jnp.finfo(pool.dtype).max))[layer % 3]
        return pool.at[:, :page_size].set(value)

    return poison


@pytest.fixture(scope="module")
def v5e():
    """The described 2x2 v5e host, with the persistent compile cache off (a
    compile for a described device is written to it but cannot be read back
    without a chip). Skipped where the topology cannot be described."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, no description
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
