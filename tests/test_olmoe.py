"""OLMoE (MHA, whole-vector q/k norm, top-k routing that is not renormalised)
on the served path, held to its plain float32 reference.

The reference is the benchmark's (`benchmarks/reference/olmoe_decoder.py`):
one sequence, dense causal attention, every expert computed for every token.
The system's side is the real thing: `forward_ragged` over the prompt in two
chunks, then decode passes, all through the paged pool. LOGITS are compared,
not sampled ids (with random weights the largest logit changes on rounding),
in float32, with a tolerance that the same path in bfloat16 fails.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import MODEL_CONFIGS, EngineConfig, ModelConfig
from ollamamq_tpu.models import llama
from ollamamq_tpu.parallel.mesh import make_mesh
from ollamamq_tpu.parallel.sharding import (kv_cache_spec,
                                            param_partition_specs)
from testutil import olmoe_reference, reference_keys, seeded_params

OLMOE = MODEL_CONFIGS["test-tiny-olmoe"]
MIXTRAL = MODEL_CONFIGS["test-tiny-moe"]
PS, MP, NP = 8, 8, 32  # page size, pages a sequence, pages in the pool
# float32 through two different orders of summation (pages and chunks against
# one dense pass; grouped against per-expert matmuls): ~1e-5 of logits whose
# spread is ~1. bfloat16 misses by ~1e-2 (asserted below).
ATOL = 2e-4


def make_params(mc, dtype=jnp.float32, seed=0):
    return seeded_params(mc, ("q_norm", "k_norm", "attn_norm", "mlp_norm"),
                         dtype, seed)


def pools(mc, dtype):
    shape = (mc.num_layers, NP * PS, mc.kv_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def ragged_step(mc, params, kc, vc, spans, pad_to, mesh=None):
    """One `forward_ragged` over `spans` = [(row, tokens, start position)],
    padded with position -1 tokens to `pad_to`; rows without a span are
    padding rows. Returns each span's last-token logits by row."""
    B = 4
    tok, seq, pos = [], [], []
    q_start, q_len, kv_len = (np.zeros(B, np.int32) for _ in range(3))
    for row, toks, start in spans:
        q_start[row], q_len[row] = len(tok), len(toks)
        kv_len[row] = start + len(toks)
        tok += list(toks)
        seq += [row] * len(toks)
        pos += list(range(start, start + len(toks)))
    n = len(tok)
    tok += [0] * (pad_to - n)
    seq += [B - 1] * (pad_to - n)
    pos += [-1] * (pad_to - n)
    pt = np.zeros((B, MP), np.int32)  # page 0: the trash page
    for row in range(B - 1):
        pt[row] = 1 + row * MP + np.arange(MP)
    tok, seq, pos = (jnp.asarray(a, jnp.int32) for a in (tok, seq, pos))
    slots = jnp.where(pos >= 0, jnp.asarray(pt)[seq, jnp.maximum(pos, 0) // PS]
                      * PS + jnp.maximum(pos, 0) % PS, 0)
    out_idx = jnp.asarray(np.maximum(q_start + q_len - 1, 0), jnp.int32)
    logits, kc, vc, load = jax.jit(
        lambda p, kc, vc: llama.forward_ragged(
            p, mc, tok, seq, pos, slots, out_idx, kc, vc, jnp.asarray(pt),
            jnp.asarray(q_start), jnp.asarray(q_len), jnp.asarray(kv_len),
            PS, mesh=mesh, moe_load=True))(params, kc, vc)
    return {row: logits[row] for row, _, _ in spans}, kc, vc, load, pt


def decode_step(mc, params, kc, vc, pt, rows, mesh=None):
    """One `forward_decode` for `rows` = {row: (token, position)}; the other
    slots are inactive (and carry garbage)."""
    B = pt.shape[0]
    tok = np.full(B, 7, np.int32)
    pos = np.zeros(B, np.int32)
    active = np.zeros(B, np.int32)
    for row, (t, p) in rows.items():
        tok[row], pos[row], active[row] = t, p, 1
    # an inactive slot writes to the trash page
    table = np.where(active[:, None] > 0, pt, 0).astype(np.int32)
    logits, kc, vc, load = jax.jit(
        lambda p, kc, vc: llama.forward_decode(
            p, mc, jnp.asarray(tok), jnp.asarray(pos), kc, vc,
            jnp.asarray(table), PS, active=jnp.asarray(active), mesh=mesh,
            moe_load=True))(params, kc, vc)
    return logits, kc, vc, load


def serve(mc, params, dtype, seqs, n_decode, mesh=None, pad_to=32):
    """Every sequence of `seqs` ({row: tokens}): the prompt (all but the last
    n_decode tokens) in two chunks, then n_decode teacher-forced decode
    passes. Returns {row: [logits after the prompt, after each decode]} and
    the expert loads of every pass."""
    kc, vc = pools(mc, dtype)
    if mesh is not None:
        from jax.sharding import NamedSharding

        kc, vc = (jax.device_put(c, NamedSharding(mesh, kv_cache_spec()))
                  for c in (kc, vc))
    got = {row: [] for row in seqs}
    loads = []
    cut = {row: (len(t) - n_decode) // 2 for row, t in seqs.items()}
    _, kc, vc, load, pt = ragged_step(
        mc, params, kc, vc,
        [(row, t[:cut[row]], 0) for row, t in seqs.items()], pad_to, mesh)
    loads.append(load)
    last, kc, vc, load, pt = ragged_step(
        mc, params, kc, vc,
        [(row, t[cut[row]:len(t) - n_decode], cut[row])
         for row, t in seqs.items()], pad_to, mesh)
    loads.append(load)
    for row in seqs:
        got[row].append(last[row])
    for j in range(n_decode):
        rows = {row: (int(t[len(t) - n_decode + j]), len(t) - n_decode + j)
                for row, t in seqs.items()}
        logits, kc, vc, load = decode_step(mc, params, kc, vc, pt, rows, mesh)
        loads.append(load)
        for row in seqs:
            got[row].append(logits[row])
    return got, loads


def want_logits(mc, params, tokens, n_decode):
    """The reference's ONE full forward: logits at the last prompt token and
    at each teacher-forced decode token."""
    full = olmoe_reference().logits(reference_keys(mc), params,
                                    jnp.asarray(tokens, jnp.int32))
    n = len(tokens)
    return [full[i] for i in range(n - n_decode - 1, n)]


def tokens_of(seed, n, mc):
    return np.random.default_rng(seed).integers(3, mc.vocab_size, n).tolist()


def worst(got, want):
    return max(float(jnp.max(jnp.abs(g.astype(jnp.float32) - w)))
               for g, w in zip(got, want))


@pytest.mark.parametrize("mc", [OLMOE, MIXTRAL], ids=lambda c: c.name)
def test_chunked_prefill_then_decode_match_the_reference_logits(mc):
    """(a) and (d): one sequence; OLMoE semantics, and Mixtral's (top-2 of 4,
    renormalised, GQA, no q/k norm) against the same reference."""
    params = make_params(mc)
    toks = tokens_of(1, 27, mc)
    got, loads = serve(mc, params, jnp.float32, {0: toks}, n_decode=3)
    want = want_logits(mc, params, toks, 3)
    assert worst(got[0], want) < ATOL
    # every real token was routed to k experts in every layer, no more
    k, L = mc.num_experts_per_tok, mc.num_layers
    assert [int(ld.sum()) for ld in loads] == \
        [12 * k * L, 12 * k * L, k * L, k * L, k * L]


def test_the_tolerance_is_one_bfloat16_fails():
    mc = OLMOE
    toks = tokens_of(1, 27, mc)
    params = make_params(mc, jnp.bfloat16)
    got, _ = serve(mc, params, jnp.bfloat16, {0: toks}, n_decode=3)
    as_f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    assert worst(got[0], want_logits(mc, as_f32, toks, 3)) > 10 * ATOL


def test_a_second_sequence_and_padding_rows_change_nothing():
    """(b): two sequences of different lengths share every step with padding
    tokens (ragged) and inactive slots (decode): `valid` keeps the rows that
    are not tokens out of every expert, and each sequence reads what the
    reference reads for it alone."""
    mc = OLMOE
    params = make_params(mc, seed=3)
    seqs = {0: tokens_of(2, 27, mc), 2: tokens_of(3, 19, mc)}
    got, loads = serve(mc, params, jnp.float32, seqs, n_decode=3, pad_to=48)
    for row, toks in seqs.items():
        assert worst(got[row], want_logits(mc, params, toks, 3)) < ATOL
    k, L = mc.num_experts_per_tok, mc.num_layers
    assert [int(ld.sum()) for ld in loads] == \
        [(12 + 8) * k * L, (12 + 8) * k * L] + [2 * k * L] * 3


@pytest.mark.parametrize("axes", [{"ep": 2}, {"tp": 2}, {"ep": 2, "tp": 2}],
                         ids=["ep2", "tp2", "ep2xtp2"])
def test_expert_and_tensor_parallel_equal_the_unsharded_result(axes):
    """(e): on the CPU mesh. Under tp the whole-vector q/k norm reduces over
    lanes that are sharded; under ep each shard runs its own experts over
    the step's rows and a psum joins the parts."""
    mc = OLMOE
    params = make_params(mc, seed=4)
    toks = tokens_of(5, 27, mc)
    plain, _ = serve(mc, params, jnp.float32, {1: toks}, n_decode=2)
    from jax.sharding import NamedSharding

    mesh = make_mesh(dp=1, devices=jax.devices()[:int(np.prod(
        list(axes.values())))], **axes)
    sharded = jax.tree_util.tree_map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), params,
        param_partition_specs(params))
    got, _ = serve(mc, sharded, jnp.float32, {1: toks}, n_decode=2, mesh=mesh)
    assert worst(got[1], plain[1]) < ATOL
    assert worst(got[1], want_logits(mc, params, toks, 2)) < ATOL


def test_qk_norm_value_selects_the_kind_and_bad_values_are_refused():
    assert OLMOE.qk_norm_kind == "full"
    assert MODEL_CONFIGS["test-tiny-qwen3"].qk_norm_kind == "head"
    assert dataclasses.replace(OLMOE, qk_norm="head").qk_norm_kind == "head"
    assert MODEL_CONFIGS["test-tiny"].qk_norm_kind is None
    with pytest.raises(ValueError, match="qk_norm"):
        dataclasses.replace(OLMOE, qk_norm="whole")
    # what a configuration file reaches the program with (benchmarks/serve.py
    # passes no norm_topk_prob): OLMoE's published default
    assert ModelConfig(name="x", vocab_size=8, hidden_size=8,
                       intermediate_size=8, num_layers=1, num_heads=1,
                       num_kv_heads=1, head_dim=8).norm_topk_prob is False
    assert MODEL_CONFIGS["mixtral:8x7b"].norm_topk_prob is True
    # the published model: 6.92 B parameters, the q/k norm weights counted
    full = MODEL_CONFIGS["olmoe:1b-7b"]
    assert full.param_count() == 6_919_161_856
    params = llama.init_params(OLMOE, jax.random.PRNGKey(0))
    assert OLMOE.param_count() == sum(
        a.size for a in jax.tree_util.tree_leaves(params))
    assert params["layers"]["q_norm"].shape == (2, OLMOE.q_dim)


def test_engine_serves_olmoe_and_its_samples_carry_the_expert_counters():
    """(g): through TPUEngine; every step sample of the MoE model and the
    /metrics series carry the counters the device sent back with the ids."""
    from ollamamq_tpu.engine.engine import TPUEngine
    from ollamamq_tpu.engine.request import Request
    from ollamamq_tpu.ops.sampling import SamplingParams
    from ollamamq_tpu.telemetry import schema as tm
    from ollamamq_tpu.telemetry import stepprof
    from testutil import collect

    name = "test-tiny-olmoe"
    ecfg = EngineConfig(
        model=name, max_slots=4, num_pages=64, page_size=8,
        max_pages_per_seq=16, max_new_tokens=8,
        decode_steps_per_iter=2, dtype="float32",
    )
    stepprof.PROFILER.reset()
    eng = TPUEngine(ecfg, blocklist_path=None)
    eng.start()
    try:
        tok = eng.runtimes[name].tokenizer
        texts = []
        for _ in range(2):  # determinism across runs (greedy)
            rid = eng.core.enqueue("u", "127.0.0.1", name)
            req = Request(rid, "u", name, tok.encode("route me"),
                          SamplingParams(max_tokens=6))
            eng.submit(req)
            items = collect(req, timeout=120)
            assert items[-1].kind == "done", items[-1].error
            texts.append("".join(i.text for i in items if i.kind == "token"))
        assert texts[0] == texts[1] and len(texts[0]) > 0
    finally:
        eng.stop()
    samples = [s for s in stepprof.PROFILER.tail()
               if s["mode"] in ("ragged", "decode")]
    assert samples and {s["mode"] for s in samples} == {"ragged", "decode"}
    k, L, E = OLMOE.num_experts_per_tok, OLMOE.num_layers, OLMOE.num_experts
    for s in samples:
        passes = s["k_cap"] if s["mode"] == "decode" else 1
        # a decode scan's planned tokens: every pass routes its live rows
        assert 0 < s["moe_assignments"] <= max(s["tokens"], passes) * k * L \
            * (passes if s["mode"] == "ragged" else 1)
        assert s["moe_assignments"] % (k * L) == 0
        assert 0 < s["moe_pairs_hit"] <= min(s["moe_assignments"],
                                             passes * L * E)
        assert 0 < s["moe_load_max"] <= s["moe_assignments"] // L
        assert s["moe_load_mean"] == pytest.approx(
            s["moe_assignments"] / (passes * L * E), abs=1e-4)
    first = samples[0]  # the prompt alone: BOS + 8 bytes
    assert first["mode"] == "ragged"
    assert first["moe_assignments"] == first["tokens"] * k * L
    total = sum(s["moe_assignments"] for s in samples)
    assert tm.MOE_ASSIGNMENTS_TOTAL.labels(model=name).value >= total
    assert tm.MOE_EXPERT_PAIRS_HIT_TOTAL.labels(model=name).value > 0
