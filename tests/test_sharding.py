"""Mesh/sharding: TP-sharded forward must match unsharded numerics."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as PS

from ollamamq_tpu.engine import kv_cache as kvc
from ollamamq_tpu.models import llama
from ollamamq_tpu.parallel import (
    make_mesh,
    param_partition_specs,
    kv_cache_spec,
    shard_params,
)

PAGE_SIZE = 8
MAX_PAGES = 8


def test_mesh_shapes():
    mesh = make_mesh(dp=2, tp=-1)
    assert mesh.shape["data"] == 2 and mesh.shape["tensor"] == 4
    mesh = make_mesh(dp=1, ep=2, tp=4)
    assert mesh.axis_names == ("data", "expert", "tensor")
    assert mesh.shape["expert"] == 2


def test_multihost_dp_picks_devices_from_every_process():
    """When k = dp*ep*tp < total devices, the multi-host dp mesh must take
    k/nproc devices FROM EACH process — devices[:k] of a process-major
    list would come entirely from the first host(s) (ADVICE r3)."""
    import pytest

    from ollamamq_tpu.parallel.mesh import _pick_per_process

    class Dev:
        def __init__(self, i, p):
            self.id, self.process_index = i, p

        def __repr__(self):
            return f"d{self.id}p{self.process_index}"

    # 2 processes x 4 devices, but k=4 (per_proc=2): naive [:4] would be
    # all of process 0.
    devs = [Dev(i, i // 4) for i in range(8)]
    picked = _pick_per_process(devs, k=4, nproc=2, per_proc=2)
    assert [d.process_index for d in picked] == [0, 0, 1, 1]
    assert [d.id for d in picked] == [0, 1, 4, 5]
    # A process short of devices fails loudly.
    devs_short = [Dev(i, 0) for i in range(6)] + [Dev(6, 1)]
    with pytest.raises(ValueError, match="every"):
        _pick_per_process(devs_short, k=4, nproc=2, per_proc=2)
    # Single-process simulations (all process_index 0) keep the
    # positional split.
    devs_sim = [Dev(i, 0) for i in range(8)]
    assert _pick_per_process(devs_sim, k=4, nproc=2, per_proc=2) == devs_sim[:4]


def test_partition_specs(tiny_cfg, tiny_params):
    specs = param_partition_specs(tiny_params)
    assert specs["layers"]["wq"] == PS(None, None, "tensor")
    assert specs["layers"]["wo"] == PS(None, "tensor", None)
    assert specs["embed"] == PS("tensor", None)
    assert specs["final_norm"] == PS()


def test_tp_forward_matches_single_device(tiny_cfg, tiny_params):
    cfg, params = tiny_cfg, tiny_params
    tokens = jnp.array([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    seq_lens = jnp.array([8])

    def run(params, kc, vc, pt):
        return llama.forward_prefill(params, cfg, tokens, seq_lens, kc, vc, pt, PAGE_SIZE)

    # Unsharded reference.
    shape = (cfg.num_layers, 32 * PAGE_SIZE, cfg.num_kv_heads * cfg.head_dim)
    kc = jnp.zeros(shape, jnp.float32)
    vc = jnp.zeros(shape, jnp.float32)
    a = kvc.PageAllocator(32, PAGE_SIZE, MAX_PAGES)
    pt = jnp.asarray(np.stack([kvc.make_page_table_row(a.alloc(8), MAX_PAGES)]))
    ref_logits, ref_kc, _ = run(params, kc, vc, pt)

    # TP=2 sharded on the virtual CPU mesh.
    mesh = make_mesh(dp=1, tp=2, devices=jax.devices()[:2])
    sp = shard_params(params, mesh)
    kv_shard = NamedSharding(mesh, kv_cache_spec())
    kc2 = jax.device_put(jnp.zeros(shape, jnp.float32), kv_shard)
    vc2 = jax.device_put(jnp.zeros(shape, jnp.float32), kv_shard)
    with jax.set_mesh(mesh):
        tp_logits, tp_kc, _ = jax.jit(run)(sp, kc2, vc2, pt)

    np.testing.assert_allclose(
        np.asarray(ref_logits), np.asarray(tp_logits), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(ref_kc), np.asarray(tp_kc), rtol=1e-4, atol=1e-4
    )


def test_tp_over_kv_heads_replicated_groups():
    """tp=8 over a 4-KV-head model (qwen2.5 shape): KV heads replicate so
    every shard owns one copy, and generation matches tp=1 exactly
    (duplicated heads are numerically transparent)."""
    import time

    from ollamamq_tpu.config import EngineConfig
    from ollamamq_tpu.engine.engine import TPUEngine
    from ollamamq_tpu.engine.request import Request
    from ollamamq_tpu.ops.sampling import SamplingParams

    def cfg(tp):
        return EngineConfig(model="test-tiny-gqa", max_slots=2, num_pages=64,
                            page_size=8, max_pages_per_seq=16,
                            max_new_tokens=6,
                            decode_steps_per_iter=2, tp=tp)

    def run(eng, user):
        rt = eng.runtimes["test-tiny-gqa"]
        tok = rt.tokenizer
        rid = eng.core.enqueue(user, "", "test-tiny-gqa")
        req = Request(rid, user, "test-tiny-gqa", tok.encode("grouped kv"),
                      SamplingParams(max_tokens=5))
        eng.submit(req)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            item = req.stream.get(timeout=0.2)
            if item and item.kind in ("done", "error"):
                assert item.kind == "done", getattr(item, "error", None)
                return req.generated_ids
        raise TimeoutError

    eng8 = TPUEngine(cfg(8), blocklist_path=None)
    eng1 = TPUEngine(cfg(1), blocklist_path=None)
    eng8.start()
    eng1.start()
    try:
        rt8 = eng8.runtimes["test-tiny-gqa"]
        assert rt8.cfg.num_kv_heads == 8  # 4 heads replicated x2
        # KV cache sharded over all 8 devices, one (duplicated) head each.
        assert len(rt8.cache.kc.sharding.device_set) == 8
        ids8 = run(eng8, "tp8")
        ids1 = run(eng1, "tp1")
        assert ids8 == ids1, f"{ids8} != {ids1}"
    finally:
        eng8.stop()
        eng1.stop()
