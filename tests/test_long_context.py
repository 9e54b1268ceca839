"""Long-context serving: a 2048-token prompt streams through the
engine's ragged spans + blockwise paged attention and generates the SAME
greedy continuation as a one-shot full-sequence forward — the
long-context story end-to-end, not just per-op. On a mesh the answer is
the same path: a prompt of any length is admitted as ragged spans,
whatever the KV dtype (there is no other prefill program)."""

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import MODEL_CONFIGS, EngineConfig
from ollamamq_tpu.engine import kv_cache as kvc
from ollamamq_tpu.engine.engine import TPUEngine
from ollamamq_tpu.engine.request import Request
from ollamamq_tpu.models import llama
from ollamamq_tpu.ops.sampling import SamplingParams
from testutil import collect

T_LONG = 2048
T_MESH = 2100  # past the 2048 tokens that once chose another program
GEN = 8
PS = 16


@contextlib.contextmanager
def _long_engine(kv_dtype, **mesh_kw):
    """test-tiny with the context ceiling lifted, registered while the
    engine lives (the engine resolves a model by name)."""
    cfg = dataclasses.replace(MODEL_CONFIGS["test-tiny"],
                              name="test-tiny-long", max_seq_len=4096)
    ecfg = EngineConfig(
        model="test-tiny-long", max_slots=2, num_pages=320,
        page_size=PS, max_pages_per_seq=160, max_new_tokens=GEN,
        decode_steps_per_iter=4, dtype="float32", kv_dtype=kv_dtype,
        **mesh_kw)
    MODEL_CONFIGS["test-tiny-long"] = cfg
    eng = None
    try:
        eng = TPUEngine(ecfg, blocklist_path=None)
        eng.start()
        yield eng
    finally:
        MODEL_CONFIGS.pop("test-tiny-long", None)
        if eng is not None:
            eng.stop()


def _greedy(eng, user, prompt):
    rid = eng.core.enqueue(user, "127.0.0.1", "test-tiny-long")
    req = Request(rid, user, "test-tiny-long", list(prompt),
                  SamplingParams(max_tokens=GEN))
    eng.submit(req)
    items = collect(req, timeout=300)
    assert items[-1].kind == "done", items[-1].error
    assert len(req.generated_ids) == GEN
    return req.generated_ids


def test_2k_prompt_chunked_serving_matches_oneshot():
    cfg = dataclasses.replace(MODEL_CONFIGS["test-tiny"], max_seq_len=4096)
    rng = np.random.RandomState(11)
    prompt = rng.randint(3, cfg.vocab_size, size=T_LONG).tolist()

    # Engine path: the prompt rides ragged spans of max_batch_tokens
    # (blockwise online-softmax over real pages only).
    with _long_engine("bfloat16") as eng:
        engine_ids = _greedy(eng, "u", prompt)

    # Reference: one-shot full-sequence prefill + stepwise greedy decode
    # at the model level (no chunking anywhere).
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    # The engine seeds its weights identically (random-init path, seed 0).
    S = 192 * PS
    kc = jnp.zeros((cfg.num_layers, S, cfg.num_kv_heads * cfg.head_dim),
                   jnp.float32)
    vc = jnp.zeros_like(kc)
    alloc = kvc.PageAllocator(192, PS, 160)
    pages = alloc.alloc(T_LONG + GEN + 1)
    pt = jnp.asarray(np.stack([kvc.make_page_table_row(pages, 160)]))
    toks = jnp.asarray([prompt], jnp.int32)
    logits, kc, vc = llama.forward_prefill(
        params, cfg, toks, jnp.array([T_LONG]), kc, vc, pt, PS
    )
    out = []
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    pos = jnp.array([T_LONG], jnp.int32)
    for _ in range(GEN):
        out.append(int(tok[0]))
        logits, kc, vc = llama.forward_decode(
            params, cfg, tok, pos, kc, vc, pt, PS
        )
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        pos = pos + 1
    assert engine_ids == out, (engine_ids, out)


# ------------------------------------------- a long prompt on a mesh
def _mesh_prompt(seed):
    return np.random.RandomState(seed).randint(3, 512, size=T_MESH).tolist()


@functools.lru_cache(maxsize=None)
def _single_device_ids(kv_dtype):
    with _long_engine(kv_dtype) as eng:
        return tuple(_greedy(eng, "ref", _mesh_prompt(23)))


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_long_prompt_on_a_tp_mesh_is_ragged_spans(kv_dtype):
    """A prompt longer than 2048 tokens on a tp=2 mesh is admitted as
    ragged spans — several steps of the one prefill program the runtime
    has — and decodes to the single-device greedy ids, with an int8 pool
    as with a bfloat16 one."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    with _long_engine(kv_dtype, tp=2) as eng:
        rt = eng.runtimes["test-tiny-long"]
        assert rt.mesh.shape["tensor"] == 2 and rt.kv_dtype == kv_dtype
        ids = _greedy(eng, "mesh", _mesh_prompt(23))
        keys = list(rt._prefill_jits)
        assert keys and all(k[0] == "ragged" for k in keys), keys
        spans = eng.journal.tail(None, kind="chunk")
        assert sum(r["tokens"] for r in spans) == T_MESH
        assert len(spans) >= T_MESH // rt.ecfg.max_batch_tokens
    assert tuple(ids) == _single_device_ids(kv_dtype)


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_decode_continues_after_a_multi_span_prompt_on_a_mesh(kv_dtype):
    """After the spans, decode reads the pages they wrote: two long
    prompts that differ only in their LAST span continue differently, and
    the same prompt again continues the same."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    a = _mesh_prompt(23)
    b = a[:-40] + _mesh_prompt(29)[-40:]
    with _long_engine(kv_dtype, tp=2) as eng:
        rt = eng.runtimes["test-tiny-long"]
        ids_a, ids_b = _greedy(eng, "a", a), _greedy(eng, "b", b)
        assert _greedy(eng, "a2", a) == ids_a
        assert rt._decode_jits, "no fused decode scan ran behind the spans"
    assert ids_a != ids_b, "decode ignored the prefilled context"
