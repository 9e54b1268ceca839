"""chip_smoke.py, rehearsed on the CPU: the same control flow the driver
runs on the chip — build, the real CLI server, three API surfaces, a
burst, status, a restart on the compile cache, the kernel child — at
test-tiny. With no TPU it must FAIL, at the platform check and nowhere
else. The longest single test of tier-1 (over a minute)."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_rehearsal_fails_at_the_platform_check():
    """`chip_smoke.py --rehearse-cpu` drives the real CLI server at
    test-tiny on the CPU: every request phase passes, the status phase
    fails because the platform is not a TPU, the exit code is non-zero
    and the last line says ok: false."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"),
         "--rehearse-cpu"],
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()]
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert proc.returncode == 1
    last = lines[-1]  # (count: conftest's XLA_FLAGS reach the children)
    assert set(last) == {"ok", "device"} and last["ok"] is False
    assert (last["device"]["platform"], last["device"]["kind"]) == (
        "cpu", "cpu") and last["device"]["count"] >= 1
    for name in ("build", "serve_start", "requests", "burst", "serve_stop",
                 "warm_start", "warm_request", "warm_stop", "kernels"):
        assert phases[name]["ok"], phases[name]
    assert phases["summary"]["failed"] == ["status"]
    status = phases["status"]
    assert "platform is 'cpu', not 'tpu'" in status["error"]
    # The server's own account rode the failing line: a clean run on the
    # reference attention, both step programs compiled.
    assert status["attn_impl"] == ["jnp"] and status["retries"] == 0
    assert status["compile_total"]["ragged"] >= 1
    assert status["compile_total"]["decode"] >= 1
    assert phases["kernels"]["ragged"]["ok"] and phases["kernels"]["decode"]["ok"]
