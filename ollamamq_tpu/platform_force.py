"""Process-level jax set-up, done before the first backend touch: pin
jax to the CPU platform with N virtual devices, and place the persistent
compilation cache.

jax picks the TPU when one is attached and the CPU otherwise. Tests, the
multi-device dry run and `--cpu N` servers must get the CPU on purpose
(with as many virtual devices as the mesh under test needs) whatever the
machine holds; nothing here ever selects an accelerator.

This module must stay importable before jax is initialized — it imports
jax itself only inside its functions.
"""

from __future__ import annotations

import os
import re

_FLAG = "xla_force_host_platform_device_count"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_cache_dir() -> str:
    """Where jax's persistent compilation cache lives (no jax import):
    JAX_COMPILATION_CACHE_DIR when set, else `.jax_cache` in the checkout
    — a fixed path, never built from a temp name, pid or time: a cache
    whose directory moves from run to run never hits."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def place_compile_cache() -> str:
    """Point this process's jax at compile_cache_dir(). The environment
    variable is read by jax itself, so when it is set nothing is set in
    code."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path


def force_cpu(n_devices: int, check: bool = True) -> None:
    """Pin JAX to CPU with at least ``n_devices`` virtual devices.

    Call before any jax device/backend touch. Sets the env vars (honoring a
    pre-existing --xla_force_host_platform_device_count only if it is already
    large enough — a stale smaller value is replaced) and jax.config, so
    the pin holds even where jax was imported before this call.

    ``check=False`` skips the verifying jax.devices() call — required when
    jax.distributed.initialize() must still run before the first backend
    touch (multi-process CPU deployments).
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"--{_FLAG}=(\d+)", flags)
    if m is None:
        flags = (flags + f" --{_FLAG}={n_devices}").strip()
    elif int(m.group(1)) < n_devices:
        flags = re.sub(rf"--{_FLAG}=\d+", f"--{_FLAG}={n_devices}", flags)
    os.environ["XLA_FLAGS"] = flags

    import jax

    jax.config.update("jax_platforms", "cpu")
    if not check:
        return
    # jax caches backends on first touch; if something initialized the real
    # TPU platform before us, the env/config changes above are silently
    # ignored — fail loudly instead of running "multi-chip CPU" work on it.
    plat = jax.devices()[0].platform
    if plat != "cpu":
        raise RuntimeError(
            f"force_cpu() called after jax initialized platform {plat!r}; "
            "call it before any jax device/backend touch"
        )
    n = len(jax.devices())
    if n < n_devices:
        raise RuntimeError(
            f"force_cpu({n_devices}) got only {n} CPU devices; XLA_FLAGS "
            f"({os.environ['XLA_FLAGS']!r}) was read before this call?"
        )
