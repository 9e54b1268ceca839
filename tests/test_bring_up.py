"""Bring-up contracts: nothing on the serving or measurement path hides
which device it ran on. The chip itself is out of reach of tier-1;
these pin what can be checked from a sandbox — the platform gates of
the CLI, the compile-cache placement, the peaks table, and per-member
device placement of an in-process fleet. (chip_smoke.py's own control
flow is rehearsed in tests/test_smoke_rehearsal.py.)"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

from ollamamq_tpu import platform_force
from ollamamq_tpu.telemetry import mfu as mfu_model
from testutil import free_port

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cli_real_engine_without_tpu_or_cpu_request_refuses_to_start():
    """No TPU and no explicit CPU request (--cpu N / JAX_PLATFORMS=cpu):
    the server exits non-zero before loading weights."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["TPU_LOG_DIR"] = "disabled"
    proc = subprocess.run(
        [sys.executable, "-m", "ollamamq_tpu.cli", "--models", "test-tiny",
         "--no-tui", "--port", "1"],
        capture_output=True, text=True, timeout=120, cwd=ROOT, env=env)
    assert proc.returncode == 3
    assert "no TPU" in proc.stdout
    assert "loaded model" not in proc.stdout


def test_compile_cache_honours_the_environment(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: nothing is set in code (jax reads
    the variable itself) and the helper names that directory."""
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x/placed-outside")
    before = jax.config.jax_compilation_cache_dir
    assert platform_force.place_compile_cache() == "/x/placed-outside"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    """No variable: `<checkout>/.jax_cache`, the same path every time —
    never built from a temp name, a pid or the time."""
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = platform_force.place_compile_cache()
        assert first == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        assert platform_force.place_compile_cache() == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("kind,peak", [
    ("TPU v5 lite", 197e12),  # v5e bf16; 394e12 is its int8 figure
    ("TPU v5 ultra", None),   # an unlisted "v5 ..." is unknown,
    ("TPU v5", None),         # never a neighbour's rate
    ("cpu", None),
])
def test_peak_flops_matches_device_kind_exactly(kind, peak, monkeypatch):
    monkeypatch.delenv("OLLAMAMQ_PEAK_FLOPS", raising=False)
    assert mfu_model.peak_flops_per_chip(kind) == peak


def test_fleet_members_get_their_own_devices():
    """`--replicas 2 --cpu 2`: each in-process member's weights sit on a
    device of its own (before: every replica landed on device 0)."""
    port = free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "ollamamq_tpu.cli", "--models", "test-tiny",
         "--no-tui", "--host", "127.0.0.1", "--port", str(port),
         "--replicas", "2", "--cpu", "2", "--max-slots", "4",
         "--num-pages", "64", "--page-size", "8", "--max-pages-per-seq", "8"],
        cwd=ROOT, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        stats, deadline = None, time.monotonic() + 120
        while stats is None:
            assert proc.poll() is None, "server exited"
            assert time.monotonic() < deadline, "server never answered"
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics.json",
                        timeout=5) as r:
                    stats = json.load(r)
            except OSError:
                time.sleep(0.5)
    finally:
        proc.kill()
        proc.wait(timeout=30)
    placed = {r["replica"]: r["devices"] for r in stats["runtimes"]}
    assert sorted(placed) == ["r0", "r1"]
    assert all(len(d) == 1 for d in placed.values())
    assert placed["r0"] != placed["r1"], placed
    assert stats["device_count"] >= 2 and stats["platform"] == "cpu"


def test_member_mesh_wraps_when_the_fleet_outgrows_the_devices():
    import jax

    from ollamamq_tpu.cli import _member_mesh
    from ollamamq_tpu.config import EngineConfig

    n = len(jax.devices())
    cfg = EngineConfig(model="test-tiny", tp=2)
    first = [_member_mesh(cfg, i).devices.flatten().tolist()
             for i in range(n // 2)]
    assert len({d for m in first for d in m}) == n  # disjoint slices
    assert _member_mesh(cfg, n // 2).devices.flatten().tolist() == first[0]
    with pytest.raises(ValueError):
        _member_mesh(EngineConfig(model="test-tiny", tp=2 * n), 0)
