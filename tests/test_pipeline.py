"""Pipeline parallelism exactness: the pp forwards are schedule-only
transformations — logits and paged KV caches must match the single-mesh
forwards (models/llama.py) bit-for-bit up to f32 accumulation order.

Runs on the 8-virtual-CPU-device mesh (conftest), covering pp alone,
pp deeper than 2 stages, pp x tp composition, and the microbatch helper.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import MODEL_CONFIGS
from ollamamq_tpu.models import llama
from ollamamq_tpu.parallel import pipeline
from ollamamq_tpu.parallel.mesh import make_mesh

PAGE_SIZE = 8


def _setup(cfg, B=4, T=16, num_pages=64, seed=0):
    params = llama.init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)
    rng = np.random.RandomState(seed)
    tokens = jnp.asarray(rng.randint(1, cfg.vocab_size, size=(B, T)), jnp.int32)
    seq_lens = jnp.asarray(rng.randint(T // 2, T + 1, size=(B,)), jnp.int32)
    S = num_pages * PAGE_SIZE
    kc = jnp.zeros((cfg.num_layers, S, cfg.num_kv_heads * cfg.head_dim),
                   jnp.float32)
    vc = jnp.zeros_like(kc)
    max_pages = T // PAGE_SIZE + 1
    pt = np.zeros((B, max_pages), np.int32)
    pid = 1  # page 0 is the trash page
    for b in range(B):
        for j in range(max_pages):
            pt[b, j] = pid
            pid += 1
    return params, tokens, seq_lens, kc, vc, jnp.asarray(pt)


def _real(c):
    """Cache slots excluding the trash page (bubble steps scribble there)."""
    return c[:, PAGE_SIZE:]


def _run_both(cfg, mesh, B=4, T=16):
    params, tokens, seq_lens, kc, vc, pt = _setup(cfg, B=B, T=T)

    ref_logits, ref_kc, ref_vc = llama.forward_prefill(
        params, cfg, tokens, seq_lens, kc, vc, pt, PAGE_SIZE
    )
    pp_logits, pp_kc, pp_vc = pipeline.pp_forward_prefill(
        params, cfg, tokens, seq_lens, kc, vc, pt, PAGE_SIZE, mesh
    )
    np.testing.assert_allclose(pp_logits, ref_logits, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(_real(pp_kc), _real(ref_kc), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(_real(pp_vc), _real(ref_vc), rtol=1e-5, atol=1e-5)

    # One decode step on top of the prefilled caches.
    next_tok = jnp.argmax(ref_logits, axis=-1).astype(jnp.int32)
    ref_d, ref_kc2, ref_vc2 = llama.forward_decode(
        params, cfg, next_tok, seq_lens, ref_kc, ref_vc, pt, PAGE_SIZE
    )
    pp_d, pp_kc2, pp_vc2 = pipeline.pp_forward_decode(
        params, cfg, next_tok, seq_lens, pp_kc, pp_vc, pt, PAGE_SIZE, mesh
    )
    np.testing.assert_allclose(pp_d, ref_d, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(_real(pp_kc2), _real(ref_kc2), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(_real(pp_vc2), _real(ref_vc2), rtol=1e-5, atol=1e-5)


def test_pp2_matches_single_mesh():
    cfg = MODEL_CONFIGS["test-tiny"]  # 2 layers -> 1 per stage
    _run_both(cfg, make_mesh(dp=1, pp=2, tp=1))


def test_pp4_deeper_pipeline():
    cfg = dataclasses.replace(
        MODEL_CONFIGS["test-tiny"], name="test-tiny-4l", num_layers=4
    )
    _run_both(cfg, make_mesh(dp=1, pp=4, tp=1))


def test_pp2_x_tp2_composition():
    # GQA config with kv_heads=4: tp=2 shards heads AND kv heads cleanly.
    cfg = MODEL_CONFIGS["test-tiny-gqa"]
    _run_both(cfg, make_mesh(dp=1, pp=2, tp=2))


def test_pp2_qwen3_qk_norm():
    # Per-head q/k RMSNorm must match inside the stage body too.
    cfg = MODEL_CONFIGS["test-tiny-qwen3"]
    _run_both(cfg, make_mesh(dp=1, pp=2, tp=2))


def test_pp2_batch_not_multiple_of_stages():
    # B=6 with pp=4 -> n_micro falls back to 3; schedule still exact.
    cfg = dataclasses.replace(
        MODEL_CONFIGS["test-tiny"], name="test-tiny-4l", num_layers=4
    )
    _run_both(cfg, make_mesh(dp=1, pp=4, tp=1), B=6)


def test_pp_engine_serves_generate_and_long_prompt():
    """Full serving path under --pp 2: bucketed prefill, fused decode, and
    the chunked long-prompt path all route through the pipelined forwards
    and produce the same greedy text as a pp=1 engine."""
    from ollamamq_tpu.config import EngineConfig
    from ollamamq_tpu.engine.engine import TPUEngine
    from ollamamq_tpu.engine.request import Request
    from ollamamq_tpu.ops.sampling import SamplingParams
    from testutil import collect

    def mk(pp):
        cfg = EngineConfig(
            model="test-tiny", max_slots=4, num_pages=64, page_size=8,
            max_pages_per_seq=16, prefill_buckets=(16, 32, 64),
            max_new_tokens=16, decode_steps_per_iter=4, pp=pp,
            dtype="float32",
        )
        eng = TPUEngine(cfg, blocklist_path=None)
        eng.start()
        return eng

    ref, pp = mk(1), mk(2)
    try:
        assert pp.runtimes["test-tiny"]._pp == 2
        # A pp runtime serves generate only: embed over pipe-sharded layer
        # stacks would all-gather each stage's weights (OOM on the >HBM
        # models pp targets), so the kind-gate must reject it cleanly.
        assert pp.runtimes["test-tiny"].SERVES == ("generate",)
        assert ref.runtimes["test-tiny"].SERVES == ("generate", "embed")
        # /metrics reports the mesh layout (axis -> size).
        assert pp.stats()["mesh"]["pipe"] == 2
        # Short prompt (bucketed prefill) and a prompt past the largest
        # bucket (chunked prefill), both compared greedy-vs-greedy.
        for prompt in ("hello pipeline world", "long " * 20):
            texts = []
            for eng in (ref, pp):
                tok = eng.runtimes["test-tiny"].tokenizer
                rid = eng.core.enqueue("u", "127.0.0.1", "test-tiny")
                req = Request(rid, "u", "test-tiny", tok.encode(prompt),
                              SamplingParams(max_tokens=8))
                eng.submit(req)
                items = collect(req, timeout=180)
                assert items[-1].kind == "done", items[-1].error
                texts.append("".join(i.text for i in items
                                     if i.kind == "token"))
            assert texts[0] == texts[1], (prompt, texts)
    finally:
        ref.stop()
        pp.stop()


def test_pp2_decode_pallas_interpret_matches_reference():
    """The ragged Pallas kernel inside the shard_map decode stage
    (interpret mode on CPU) matches the jnp pipeline path exactly."""
    cfg = MODEL_CONFIGS["test-tiny"]
    mesh = make_mesh(dp=1, pp=2, tp=1)
    params, tokens, seq_lens, kc, vc, pt = _setup(cfg)
    logits, kc, vc = pipeline.pp_forward_prefill(
        params, cfg, tokens, seq_lens, kc, vc, pt, PAGE_SIZE, mesh
    )
    next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    ref_d, ref_kc, ref_vc = pipeline.pp_forward_decode(
        params, cfg, next_tok, seq_lens, kc, vc, pt, PAGE_SIZE, mesh
    )
    pal_d, pal_kc, pal_vc = pipeline.pp_forward_decode(
        params, cfg, next_tok, seq_lens, kc, vc, pt, PAGE_SIZE, mesh,
        attn_impl="pallas", interpret=True,
    )
    np.testing.assert_allclose(pal_d, ref_d, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(_real(pal_kc), _real(ref_kc), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(_real(pal_vc), _real(ref_vc), rtol=1e-5,
                               atol=1e-5)


def test_dp2_x_pp2_replica_serving():
    """dp=2 with pp=2: each ReplicaSet member owns a [1, 2, 1, 1, tp]
    submesh and runs its own 2-stage pipeline; both replicas serve."""
    from ollamamq_tpu.config import EngineConfig
    from ollamamq_tpu.engine.engine import ReplicaSet, TPUEngine
    from ollamamq_tpu.engine.request import Request
    from ollamamq_tpu.ops.sampling import SamplingParams
    from testutil import collect

    cfg = EngineConfig(
        model="test-tiny", max_slots=2, num_pages=32, page_size=8,
        max_pages_per_seq=8, prefill_buckets=(16,), max_new_tokens=8,
        decode_steps_per_iter=2, dp=2, pp=2, dtype="float32",
    )
    eng = TPUEngine(cfg, blocklist_path=None)
    eng.start()
    try:
        rs = eng.runtimes["test-tiny"]
        assert isinstance(rs, ReplicaSet) and len(rs.replicas) == 2
        assert all(r._pp == 2 for r in rs.replicas)
        tok = rs.replicas[0].tokenizer
        reqs = []
        for i in range(4):  # enough to land work on both replicas
            rid = eng.core.enqueue(f"u{i}", "127.0.0.1", "test-tiny")
            req = Request(rid, f"u{i}", "test-tiny", tok.encode(f"hi {i}"),
                          SamplingParams(max_tokens=4))
            eng.submit(req)
            reqs.append(req)
        for req in reqs:
            items = collect(req, timeout=180)
            assert items[-1].kind == "done", items[-1].error
    finally:
        eng.stop()


def test_n_microbatches_helper():
    assert pipeline.n_microbatches(8, 4) == 4
    assert pipeline.n_microbatches(6, 4) == 3
    assert pipeline.n_microbatches(1, 4) == 1
    assert pipeline.n_microbatches(7, 4) == 1  # prime batch
    assert pipeline.n_microbatches(8, 4, requested=2) == 2
    assert pipeline.n_microbatches(4, 8) == 4  # never exceeds the batch
