"""Multi-host control plane: jax.distributed bring-up.

The reference's distribution story is N independent HTTP backends glued by
a proxy; here a deployment is one SPMD program across hosts: every host
runs the same engine binary, `jax.distributed.initialize` wires the
control plane, the mesh spans all hosts' devices (ICI within a slice, DCN
across slices), and XLA's collectives do the data movement that reqwest
did in the reference. Host 0 additionally runs the HTTP front + scheduler;
the other hosts participate in the jitted steps via SPMD.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

import jax

log = logging.getLogger("ollamamq.distributed")


def multiprocess_configured() -> bool:
    """True when the env opts into a multi-process runtime — the SAME
    condition initialize() uses to decide whether to bring one up (callers
    that must defer backend-touching work until after initialize() share
    this instead of re-deriving it)."""
    env_np = os.environ.get("JAX_NUM_PROCESSES")
    num_processes = int(env_np) if env_np else None
    return bool(os.environ.get("JAX_COORDINATOR_ADDRESS")) or (
        num_processes not in (None, 1)
    )


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Initialize the multi-host control plane. No-ops for single-process.

    Args fall back to the standard env vars (JAX_COORDINATOR_ADDRESS,
    JAX_NUM_PROCESSES, JAX_PROCESS_ID). Multi-host is strictly OPT-IN via
    those vars (or explicit args): a bare jax.distributed.initialize()
    auto-detect is NOT attempted, because on a plain single host it can
    hang waiting for a coordinator. Returns True if a multi-process
    runtime was initialized.
    """
    coordinator_address = coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS")
    env_np = os.environ.get("JAX_NUM_PROCESSES")
    env_pid = os.environ.get("JAX_PROCESS_ID")
    num_processes = num_processes if num_processes is not None else (
        int(env_np) if env_np else None
    )
    process_id = process_id if process_id is not None else (
        int(env_pid) if env_pid else None
    )
    if not coordinator_address and num_processes in (None, 1):
        return False
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    log.info(
        "distributed runtime up: process %d/%d, %d local / %d global devices",
        jax.process_index(), jax.process_count(),
        jax.local_device_count(), jax.device_count(),
    )
    return True


def is_primary() -> bool:
    """The host that runs the HTTP front + scheduler (process 0)."""
    return jax.process_index() == 0


def barrier(name: str = "ollamamq") -> None:
    """Cross-host sync point (e.g. after weight loading, before serving)."""
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)
