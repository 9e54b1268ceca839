"""Long-context serving: a 2048-token prompt (32x the largest bucket)
streams through the engine's chunked prefill + blockwise paged attention
and generates the SAME greedy continuation as a one-shot full-sequence
forward — the long-context story end-to-end, not just per-op."""

import time

import jax
import jax.numpy as jnp
import numpy as np

from ollamamq_tpu.config import MODEL_CONFIGS, EngineConfig
from ollamamq_tpu.engine import kv_cache as kvc
from ollamamq_tpu.engine.engine import TPUEngine
from ollamamq_tpu.engine.request import Request
from ollamamq_tpu.models import llama
from ollamamq_tpu.ops.sampling import SamplingParams
from testutil import collect

T_LONG = 2048
GEN = 8


def test_2k_prompt_chunked_serving_matches_oneshot():
    import dataclasses

    # test-tiny with the context ceiling lifted (max_seq_len gates prompt
    # length at admission); registered temporarily so the engine resolves
    # it by name.
    cfg = dataclasses.replace(MODEL_CONFIGS["test-tiny"],
                              name="test-tiny-long", max_seq_len=4096)
    rng = np.random.RandomState(11)
    prompt = rng.randint(3, cfg.vocab_size, size=T_LONG).tolist()

    # Engine path: largest bucket 64 => the prompt takes the chunked
    # route (blockwise online-softmax over real pages only).
    ps = 16
    ecfg = EngineConfig(
        model="test-tiny-long", max_slots=2, num_pages=192, page_size=ps,
        max_pages_per_seq=160, prefill_buckets=(16, 64), max_new_tokens=GEN,
        decode_steps_per_iter=4, dtype="float32",
    )
    eng = None
    MODEL_CONFIGS["test-tiny-long"] = cfg
    try:
        eng = TPUEngine(ecfg, blocklist_path=None)
        eng.start()
        rid = eng.core.enqueue("u", "127.0.0.1", "test-tiny-long")
        req = Request(rid, "u", "test-tiny-long", list(prompt),
                      SamplingParams(max_tokens=GEN))
        eng.submit(req)
        items = collect(req, timeout=300)
        assert items[-1].kind == "done", items[-1].error
        engine_ids = req.generated_ids
    finally:
        MODEL_CONFIGS.pop("test-tiny-long", None)
        if eng is not None:
            eng.stop()
    assert len(engine_ids) == GEN

    # Reference: one-shot full-sequence prefill + stepwise greedy decode
    # at the model level (no chunking anywhere).
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    # The engine seeds its weights identically (random-init path, seed 0).
    S = 192 * ps
    kc = jnp.zeros((cfg.num_layers, S, cfg.num_kv_heads * cfg.head_dim),
                   jnp.float32)
    vc = jnp.zeros_like(kc)
    alloc = kvc.PageAllocator(192, ps, 160)
    pages = alloc.alloc(T_LONG + GEN + 1)
    pt = jnp.asarray(np.stack([kvc.make_page_table_row(pages, 160)]))
    toks = jnp.asarray([prompt], jnp.int32)
    logits, kc, vc = llama.forward_prefill(
        params, cfg, toks, jnp.array([T_LONG]), kc, vc, pt, ps
    )
    out = []
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    pos = jnp.array([T_LONG], jnp.int32)
    for _ in range(GEN):
        out.append(int(tok[0]))
        logits, kc, vc = llama.forward_decode(
            params, cfg, tok, pos, kc, vc, pt, ps
        )
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        pos = pos + 1
    assert engine_ids == out, (engine_ids, out)
