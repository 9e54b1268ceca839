"""Device mesh construction.

The reference's only parallelism is request-level load balancing across
HTTP backends (/root/reference/src/dispatcher.rs:434-482). Here parallelism
is a jax.sharding.Mesh over TPU chips with named axes:

  - "data":   replica/data parallelism (independent batches / model replicas)
  - "tensor": tensor parallelism within a replica — attention heads and MLP
              hidden dim sharded; XLA emits allgather/reduce-scatter over ICI
  - "expert": expert parallelism — an MoE layer's experts sharded

Multi-host: `jax.distributed.initialize` is handled in
ollamamq_tpu.parallel.distributed; this module only arranges whatever
`jax.devices()` reports into a mesh.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_DATA = "data"
AXIS_TENSOR = "tensor"
AXIS_EXPERT = "expert"


def make_mesh(
    dp: int = 1,
    tp: int = -1,
    ep: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a (data, expert, tensor) mesh.

    `tp=-1` means "all devices not consumed by dp*ep". The tensor
    axis is innermost so TP collectives ride the fastest ICI links
    (adjacent chips).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if tp == -1:
        if n % (dp * ep) != 0:
            raise ValueError(
                f"{n} devices not divisible by dp*ep={dp * ep}")
        tp = n // (dp * ep)
    k = dp * ep * tp
    if k > n:
        raise ValueError(f"dp*ep*tp={k} > {n} available devices")
    nproc = jax.process_count()
    if dp > 1 and nproc > 1:
        # Multi-host dp replica serving slices the mesh along the data axis
        # (one submesh per replica). jax.devices() is process-major, so the
        # default dp-outermost layout would give each replica the chips of
        # ONE host — a submesh the other processes can't participate in
        # (multi-controller jit requires every process to own addressable
        # shards). Give each dp slice (devices_per_process / dp) chips from
        # EVERY process instead; that requires dp to divide the per-process
        # chip count — fail loudly otherwise (a replica smaller than one
        # chip per process cannot span every process at all).
        if k % nproc != 0:
            raise ValueError(
                f"{k} mesh devices not divisible by {nproc} processes")
        per_proc = k // nproc
        if per_proc % dp != 0:
            raise ValueError(
                f"multi-host dp={dp} needs dp to divide the per-process "
                f"device count ({per_proc}): each replica must own chips "
                "on every process for its jit to be a valid "
                "multi-controller computation")
        arr = (np.asarray(_pick_per_process(devices, k, nproc, per_proc))
               .reshape(nproc, dp, per_proc // dp)
               .transpose(1, 0, 2)
               .reshape(dp, ep, tp))
    else:
        arr = np.asarray(devices[:k]).reshape(dp, ep, tp)
    return Mesh(arr, (AXIS_DATA, AXIS_EXPERT, AXIS_TENSOR))


def _pick_per_process(devices, k: int, nproc: int, per_proc: int):
    """The k devices for a multi-host dp mesh, process-major with exactly
    per_proc devices FROM EACH PROCESS. `devices[:k]` alone is wrong when
    k < len(devices): jax.devices() is process-major, so the first k could
    all come from the first host(s) and the (nproc, dp, ...) relabeling
    would silently produce replicas that don't span every process (ADVICE
    r3). Falls back to the positional split only when the device list
    doesn't actually carry nproc distinct process_indexes (single-process
    simulations of a process count, e.g. tests)."""
    by_proc: dict = {}
    for d in devices:
        by_proc.setdefault(getattr(d, "process_index", 0), []).append(d)
    if len(by_proc) != nproc:
        return devices[:k]
    short = {p: len(v) for p, v in by_proc.items() if len(v) < per_proc}
    if short:
        raise ValueError(
            f"multi-host dp mesh needs {per_proc} devices from every "
            f"process; process(es) {sorted(short)} have only "
            f"{sorted(short.values())}")
    return [d for p in sorted(by_proc) for d in by_proc[p][:per_proc]]


def replica_submesh(mesh: Mesh, r: int) -> Mesh:
    """Replica r's slice of the data axis (a [1, ep, tp] submesh) — THE
    derivation, shared by the engine's replica construction and the SPMD
    worker's reload path, which must agree on every host."""
    return Mesh(mesh.devices[r:r + 1], mesh.axis_names)


def validate_tp_for_model(tp: int, num_kv_heads: int, num_heads: int) -> None:
    """TP must divide the head counts so shards stay aligned (MXU tiling).

    tp > num_kv_heads is allowed when tp % num_kv_heads == 0: the runtime
    duplicates each KV head tp/num_kv_heads times at load
    (weights.replicate_kv_heads) so every shard owns one copy — the
    replicated-group sharding, at the cost of that factor in KV-cache
    memory (e.g. qwen2.5's 4 KV heads on tp=8 cost 2x KV HBM)."""
    if num_heads % tp != 0:
        raise ValueError(f"num_heads={num_heads} not divisible by tp={tp}")
    if num_kv_heads % tp != 0 and tp % num_kv_heads != 0:
        raise ValueError(
            f"num_kv_heads={num_kv_heads} incompatible with tp={tp}: "
            "needs kv_heads % tp == 0 (sharded) or tp % kv_heads == 0 "
            "(replicated groups)"
        )
