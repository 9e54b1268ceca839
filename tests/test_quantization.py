"""Int8 quantization: weights + KV pages (PR 8 tentpole).

Pinned here:
  - per-channel weight quantize/dequantize roundtrip error bounds (last
    axis for layer matmuls, row axis for embed/lm_head);
  - per-page KV write/gather roundtrip error bounds (per-slot per-head
    scales stored page-aligned alongside the pool);
  - the quality guardrail: greedy token-match-rate + max-logit-error of
    the int8 tree vs its bf16 source on real-shaped weights (GQA,
    head_dim 64), enforced in tier-1 and published as
    `ollamamq_quant_logit_err`;
  - quantized Pallas kernels (ragged + decode) match the jnp quantized
    reference in interpret mode;
  - engine integration: quantized pools shrink kv_bytes ~2x, spec-on
    stays byte-identical to spec-off on an int8 runtime, and a
    randomized preemption/rollback/prefix-sharing fuzz preserves
    free+used+cached == pool with shrunken pages (journal invariants
    clean);
  - the density regression gate: at EQUAL HBM an int8 pool holds
    2*hd/(hd+4) more pages and preempts no more than the bf16 pool on
    the same arrival trace;
  - fail-fast: invalid --weights-dtype/--kv-dtype combinations error at
    CLI/config/runtime-build time, never at first dispatch.
"""

import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import (MODEL_CONFIGS, EngineConfig, ModelConfig,
                                 validate_quant_config)
from ollamamq_tpu.core import MQCore
from ollamamq_tpu.engine import kv_cache as kvc
from ollamamq_tpu.engine.engine import ModelRuntime
from ollamamq_tpu.engine.request import Request
from ollamamq_tpu.models import weights
from ollamamq_tpu.ops.quant import (QuantKV, QuantTensor, dequantize_tensor,
                                    kv_gather, kv_quantize, kv_write,
                                    quantize_tensor)
from ollamamq_tpu.ops.sampling import SamplingParams
from ollamamq_tpu.telemetry.journal import Journal, check_invariants

_IDS = itertools.count(1)

# Real-shaped guardrail config: llama-family GQA geometry (head_dim 64,
# grouped KV heads, SwiGLU) at a layer/width CI can afford.
GUARD_SHAPE = ModelConfig(
    name="guard-shape", vocab_size=4096, hidden_size=256,
    intermediate_size=512, num_layers=4, num_heads=4, num_kv_heads=2,
    head_dim=64, rope_theta=500_000.0, max_seq_len=512,
    tie_embeddings=True,
)


# ---------------------------------------------------------------- roundtrips
def test_weight_roundtrip_per_channel_bounds():
    """Symmetric per-channel int8: every element's roundtrip error is
    bounded by half its channel's scale (the quantization step)."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(3, 32, 48)).astype(np.float32) * 2.5)
    t = quantize_tensor(w, axis=-1)
    assert t.q.dtype == jnp.int8 and t.s.dtype == jnp.float32
    assert t.s.shape == (3, 48)
    back = np.asarray(dequantize_tensor(t, axis=-1))
    err = np.abs(back - np.asarray(w))
    per_channel_bound = np.asarray(t.s)[:, None, :] * 0.5 + 1e-6
    assert (err <= per_channel_bound).all()


def test_embed_roundtrip_per_row_bounds():
    rng = np.random.default_rng(1)
    e = jnp.asarray(rng.normal(size=(64, 16)).astype(np.float32))
    t = quantize_tensor(e, axis=0)
    assert t.s.shape == (64,)
    back = np.asarray(dequantize_tensor(t, axis=0))
    err = np.abs(back - np.asarray(e))
    assert (err <= np.asarray(t.s)[:, None] * 0.5 + 1e-6).all()


def test_kv_roundtrip_per_page_bounds():
    """KV rows quantize per (slot, head): the roundtrip error of every
    element is bounded by half that row's scale, and scales sit
    page-aligned (slot index == page * page_size + offset) so a page's
    scale rows travel with its page id."""
    rng = np.random.default_rng(2)
    L, S, Hk, hd = 3, 64, 2, 16  # the whole pool; layer 1 is written
    pool = QuantKV(jnp.zeros((L, S, Hk * hd), jnp.int8),
                   jnp.ones((L, S, Hk), jnp.float32))
    vals = jnp.asarray(rng.normal(size=(24, Hk, hd)).astype(np.float32) * 3)
    slots = jnp.asarray(rng.choice(S, size=24, replace=False))
    pool = kv_write(pool, 1, slots, vals)
    got = np.asarray(kv_gather(pool, 1, slots, hd))
    scales = np.asarray(pool.s)[1][np.asarray(slots)]  # [24, Hk]
    err = np.abs(got - np.asarray(vals))
    assert (err <= scales[..., None] * 0.5 + 1e-6).all()
    # kv_quantize is the same math the in-jit writer runs.
    q, s = kv_quantize(vals)
    assert q.dtype == jnp.int8 and s.shape == (24, Hk)


# ----------------------------------------------------------------- guardrail
def test_quant_guardrail_real_shaped():
    """The tier-1 quality gate the ISSUE names: on real-shaped weights
    (GQA, head_dim 64) the int8 tree must track bf16 greedy decisions
    and keep the worst logit error bounded relative to the logit spread."""
    out = weights.quant_guardrail(GUARD_SHAPE, seed=3, dtype=jnp.bfloat16,
                                  prompt_len=16, steps=16)
    assert out["token_match_rate"] >= 0.85, out
    assert out["rel_logit_err"] <= 0.5, out
    from ollamamq_tpu.telemetry import schema as tm

    assert tm.QUANT_LOGIT_ERR.labels(
        model=GUARD_SHAPE.name).value == pytest.approx(out["max_logit_err"])


def test_quant_guardrail_tiny_smoke():
    """test-tiny's near-tied random logits are the worst case for greedy
    agreement — the bound is loose, but a quantization bug (wrong scale
    axis, off-by-one clip) craters it to ~chance."""
    out = weights.quant_guardrail(MODEL_CONFIGS["test-tiny"], seed=1,
                                  dtype=jnp.float32, prompt_len=8, steps=8)
    assert out["token_match_rate"] >= 0.5, out
    assert out["max_logit_err"] <= 1.0, out


def test_quantize_params_rejects_moe():
    with pytest.raises(ValueError):
        weights.load_params(MODEL_CONFIGS["test-tiny-moe"], None,
                            weights_dtype="int8")


# ------------------------------------------------- quantized pallas kernels
def _mixed_stream(rng, S=160, Hk=2, hd=16, H=4, ps=8, MP=8, L=3):
    """A whole int8 pool ([L, S, Hk*hd] payload + [L, S, Hk] scales,
    every layer different); the callers attend over layer 1."""
    kraw = jnp.asarray(rng.normal(size=(L, S, Hk, hd)).astype(np.float32))
    vraw = jnp.asarray(rng.normal(size=(L, S, Hk, hd)).astype(np.float32))
    kq, ks = kv_quantize(kraw)
    vq, vs = kv_quantize(vraw)
    kq, vq = kq.reshape(L, S, Hk * hd), vq.reshape(L, S, Hk * hd)
    pt = np.zeros((3, MP), np.int32)
    pt[0, :4] = [1, 2, 3, 4]
    pt[1, :2] = [5, 6]
    pt[2, :3] = [7, 8, 9]
    spans = [(0, 10, 26), (10, 1, 11), (11, 5, 17)]  # (q_start, q_len, kv)
    tok_seq, tok_pos = [], []
    for s, (qs, ql, kv) in enumerate(spans):
        for j in range(ql):
            tok_seq.append(s)
            tok_pos.append(kv - ql + j)
    return (QuantKV(kq, ks), QuantKV(vq, vs), jnp.asarray(pt),
            jnp.asarray([s[0] for s in spans], jnp.int32),
            jnp.asarray([s[1] for s in spans], jnp.int32),
            jnp.asarray([s[2] for s in spans], jnp.int32),
            jnp.asarray(tok_seq, jnp.int32), jnp.asarray(tok_pos, jnp.int32),
            ps, H, hd)


def test_pallas_ragged_quantized_matches_jnp_interpret():
    from ollamamq_tpu.ops.attention import ragged_paged_attention_blockwise
    from ollamamq_tpu.ops.pallas.ragged_attention import (
        ragged_paged_attention_pallas)

    rng = np.random.default_rng(4)
    (kc, vc, pt, q_start, q_len, kv_len, tok_seq, tok_pos,
     ps, H, hd) = _mixed_stream(rng)
    q = jnp.asarray(rng.normal(size=(16, H, hd)).astype(np.float32))
    ref = ragged_paged_attention_blockwise(q, kc, vc, 1, pt, tok_seq,
                                           tok_pos, kv_len, ps)
    out = ragged_paged_attention_pallas(q, kc.q, vc.q, 1, pt, q_start,
                                        q_len, kv_len, ps, interpret=True,
                                        k_scale=kc.s, v_scale=vc.s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_pallas_decode_quantized_matches_jnp_interpret():
    from ollamamq_tpu.ops.attention import paged_decode_attention
    from ollamamq_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_pallas)

    rng = np.random.default_rng(5)
    (kc, vc, pt, _qs, _ql, kv_len, _ts, _tp, ps, H, hd) = _mixed_stream(rng)
    q = jnp.asarray(rng.normal(size=(3, H, hd)).astype(np.float32))
    ref = paged_decode_attention(q, kc, vc, 1, pt, kv_len, ps)
    out = paged_decode_attention_pallas(q, kc.q, vc.q, 1, pt, kv_len, ps,
                                        interpret=True,
                                        k_scale=kc.s, v_scale=vc.s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


# --------------------------------------------------------- engine integration
def make_rt(**kw):
    defaults = dict(
        model="test-tiny", max_slots=4, num_pages=96, page_size=8,
        max_pages_per_seq=16, max_new_tokens=8,
        decode_steps_per_iter=2, max_batch_tokens=48, token_granule=8,
    )
    defaults.update(kw)
    rt = ModelRuntime("test-tiny", MODEL_CONFIGS["test-tiny"],
                      EngineConfig(**defaults), dtype=jnp.float32)
    rt.tokenizer.eos_id = -1
    return rt


def run_all(rt, prompts, max_tokens=6, max_ticks=800):
    core = MQCore(None)
    reqs = []
    for p in prompts:
        req = Request(next(_IDS), f"u{len(reqs) % 3}", "test-tiny", list(p),
                      SamplingParams(max_tokens=max_tokens))
        req._inc_decode = rt.tokenizer.make_incremental_decoder()
        rt.pending_prefill.append(req)
        reqs.append(req)
    for _ in range(max_ticks):
        if all(r.stats.finished_at for r in reqs):
            break
        ran = rt.step_ragged(core)
        if not ran and any(s is not None for s in rt.slot_req):
            rt.step_decode(core, k_steps=1)
    assert all(r.stats.finished_at for r in reqs), "requests wedged"
    return [list(r.generated_ids) for r in reqs]


def test_quantized_runtime_serves_and_shrinks_kv():
    rng = np.random.default_rng(6)
    prompts = [rng.integers(3, 500, size=n).tolist() for n in (20, 7, 35)]
    bf = make_rt()
    q8 = make_rt(kv_dtype="int8", weights_dtype="int8")
    out = run_all(q8, prompts)
    assert all(len(o) == 6 for o in out)
    # int8 pool: 1 payload byte + 4/hd scale bytes per element vs 4 (f32
    # test dtype) — and the weight tree shrinks too.
    assert q8.kv_bytes < 0.40 * bf.kv_bytes
    assert q8.param_bytes < 0.45 * bf.param_bytes
    from ollamamq_tpu.telemetry import schema as tm

    assert tm.HBM_KV_BYTES.labels(model="test-tiny").value == q8.kv_bytes
    assert tm.HBM_WEIGHT_BYTES.labels(model="test-tiny").value == \
        q8.param_bytes


def _copy_map(rt):
    """Zero the residual output projections (test_spec_decoding's trick):
    the next token becomes a pure function of the last, greedy
    generation cycles, and n-gram lookup drafts actually verify. On a
    quantized runtime the projections are QuantTensors — zero both the
    payload and the scales."""
    import jax

    for key in ("wo", "w_down"):
        rt.params["layers"][key] = jax.tree_util.tree_map(
            jnp.zeros_like, rt.params["layers"][key])
    return rt


def test_spec_byte_identical_on_quantized_runtime():
    """Speculative verify on an int8 runtime is still greedy-exact
    AGAINST ITSELF: drafts verify with the same quantized forward and
    the same quantized KV writes the 1-token path would make, so
    spec-on streams match spec-off byte-for-byte."""
    rng = np.random.default_rng(7)
    cyc = rng.integers(3, 400, size=5).tolist()
    prompts = [(cyc * 10)[:40], (cyc * 5)[:24]]
    # Long enough for the copy-map's generation cycle to establish and
    # the lookup to start drafting (the repetitive regime).
    base = run_all(_copy_map(make_rt(kv_dtype="int8",
                                     weights_dtype="int8")),
                   prompts, max_tokens=40)
    rt = _copy_map(make_rt(kv_dtype="int8", weights_dtype="int8",
                           spec=True, spec_k=3, spec_min_accept=0.0))
    spec = run_all(rt, prompts, max_tokens=40)
    assert spec == base
    assert rt.spec_proposed > 0  # speculation actually exercised
    assert rt.spec_accepted > 0  # ...and drafts verified on the int8 path
    assert rt.kv_dtype == "int8"


def test_page_conservation_fuzz_quantized():
    """Randomized preemption + speculative rollback + prefix-cache
    sharing on shrunken int8 pages: free + used + cached == pool holds
    through every tick, and the journal invariant sweep stays clean."""
    rng = np.random.default_rng(8)
    rt = make_rt(kv_dtype="int8", num_pages=24, prefix_cache=True,
                 spec=True, spec_k=3, spec_min_accept=0.0, preempt_max=2)
    journal = Journal(capacity=65536)
    rt.journal = journal
    core = MQCore(None)

    def requeue(req):
        rt.pending_prefill.appendleft(req)
        return True

    rt.on_preempt = requeue
    shared = rng.integers(3, 400, size=16).tolist()
    reqs, issued = [], 0
    guard = 0
    while True:
        while issued < 18 and len(rt.pending_prefill) < 5:
            tail = rng.integers(3, 400, size=int(rng.integers(2, 30)))
            prompt = (shared + tail.tolist() if rng.random() < 0.5
                      else tail.tolist())
            req = Request(next(_IDS), f"q{issued % 4}", "test-tiny", prompt,
                          SamplingParams(max_tokens=6))
            req._inc_decode = rt.tokenizer.make_incremental_decoder()
            rt.pending_prefill.append(req)
            reqs.append(req)
            issued += 1
        ran = rt.step_ragged(core)
        if not ran and any(s is not None for s in rt.slot_req):
            rt.step_decode(core, k_steps=1)
        a = rt.cache.alloc
        assert a.free_pages + a.used_pages + a.cached_pages \
            == a.num_pages - 1, "page conservation broken"
        if issued >= 18 and all(r.stats.finished_at for r in reqs):
            break
        guard += 1
        assert guard < 8000, "fuzz wedged"
    assert not check_invariants(journal.tail(None))


def test_density_gate_equal_hbm():
    """The CI density regression gate: at the SAME HBM byte budget the
    int8 pool holds 2*hd/(hd+4) more pages (1.6x at test-tiny's hd=16;
    1.88-1.94x at real models' hd=64/128) and, driven with the same
    arrival trace, preempts no more than the bf16 pool — and finishes
    every request."""
    cfg = MODEL_CONFIGS["test-tiny"]
    ps = 8
    pages_bf16 = 12
    budget = pages_bf16 * kvc.kv_page_bytes(cfg, ps, kv_dtype="bfloat16")
    pages_int8 = budget // kvc.kv_page_bytes(cfg, ps, kv_dtype="int8")
    expected = 2 * cfg.head_dim / (cfg.head_dim + 4)
    assert pages_int8 / pages_bf16 >= 0.9 * expected

    def run_leg(kv_dtype, pages):
        rt = make_rt(kv_dtype=kv_dtype, num_pages=pages + 1,
                     max_pages_per_seq=8, preempt_max=2)
        journal = Journal(capacity=65536)
        rt.journal = journal

        def requeue(req):
            rt.pending_prefill.appendleft(req)
            return True

        rt.on_preempt = requeue
        trace = np.random.default_rng(99)
        prompts = [trace.integers(3, 400, size=20).tolist()
                   for _ in range(10)]
        run_all(rt, prompts, max_tokens=8)
        assert not check_invariants(journal.tail(None))
        return rt.preempt_count

    preempt_bf16 = run_leg("bfloat16", pages_bf16)
    preempt_int8 = run_leg("int8", pages_int8)
    assert preempt_int8 <= preempt_bf16
    assert preempt_bf16 > 0, "trace never hit the bf16 pool ceiling"


# ------------------------------------------------------------------ fail fast
def test_validate_quant_config_combinations():
    ok = validate_quant_config("bfloat16", "bfloat16")
    assert ok is None
    assert validate_quant_config("int8", "int8") is None
    assert "fp8" in validate_quant_config("fp8", "bfloat16")
    assert "--kv-dtype" in validate_quant_config("bfloat16", "fp8")
    assert "MoE" in validate_quant_config(
        "int8", "bfloat16", model_names=["mixtral:8x7b"])
    # The validator must not over-reject: int8 weights on a dense model
    # pass with either KV dtype.
    assert validate_quant_config("int8", "bfloat16") is None
    assert validate_quant_config(
        "int8", "int8", model_names=["test-tiny"]) is None


def test_cli_fails_fast_on_invalid_combinations():
    from ollamamq_tpu.cli import main

    # MoE model with int8 weights: rejected before any engine work.
    assert main(["--no-tui", "--models", "mixtral:8x7b",
                 "--weights-dtype", "int8"]) == 2


@pytest.mark.parametrize("argv", [
    ["--attention", "bucketed"], ["--pp", "2"], ["--pp-microbatches", "4"],
    ["--sp", "2"],
], ids=lambda a: a[0])
def test_cli_rejects_removed_flags(argv):
    """--attention went with the bucketed oracle, --pp and its
    microbatch knob with the pipeline path, --sp with the ring-attention
    prefill: argparse must reject each loudly (exit 2) instead of
    silently serving the one path there is."""
    from ollamamq_tpu.cli import build_parser

    with pytest.raises(SystemExit) as exc:
        build_parser().parse_args(argv)
    assert exc.value.code == 2


def test_runtime_build_fails_fast():
    with pytest.raises(ValueError):
        make_rt(kv_dtype="fp8")
    with pytest.raises(ValueError):
        ModelRuntime("test-tiny-moe", MODEL_CONFIGS["test-tiny-moe"],
                     EngineConfig(model="test-tiny-moe", max_slots=2,
                                  num_pages=16, page_size=8,
                                  max_pages_per_seq=8,
                                  weights_dtype="int8"),
                     dtype=jnp.float32)


def test_quant_tensor_is_a_pytree():
    """QuantTensor/QuantKV must flow through tree_map/scan/donation: the
    flatten must yield exactly (q, s) and rebuild the same type."""
    import jax

    t = quantize_tensor(jnp.ones((2, 4, 4)), axis=-1)
    leaves, treedef = jax.tree_util.tree_flatten(t)
    assert len(leaves) == 2
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(back, QuantTensor)
    doubled = jax.tree_util.tree_map(lambda x: x * 2, t)
    assert isinstance(doubled, QuantTensor)
