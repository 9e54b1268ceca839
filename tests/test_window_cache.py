"""The window layers' cache and kernels (PR 50): a per-slot RING of K/V rows
beside the paged pool, a window bound in the mask AND in the walk of both
attention kernels and of their jnp twins.

What is held here: the Pallas kernels in interpret mode against the jnp
twin and the materialising reference, over ring tables, on every body the
ragged kernel has (tiles of 8, the tall stretch) and both inner products;
the ring's arithmetic (`ring_table`, `ring_write_slots`) against a brute
force over positions; that a slot's window-layer rows do NOT grow with its
context and the paged pool holds the full layers only — as array shapes, as
the runtime's byte counts and as the step samples' counters; that `window`
0 traces what it traced before; and, for a described v5e, that the step
programs at the published widths compile with pool and rings updated in
place and the window launches under names of their own."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import (ATTENTION, MODEL_CONFIGS, WINDOW,
                                 EngineConfig)
from ollamamq_tpu.ops.attention import (alloc_ring,
                                        paged_decode_attention_any,
                                        ragged_attention_any,
                                        ragged_paged_attention,
                                        ring_first_page, ring_table,
                                        ring_write_slots)
from ollamamq_tpu.ops.pallas import paged_attention, ragged_attention
from test_ragged_attention import F32_TOL, _f32, assert_kernel_close

NAME = "test-tiny-k-exaone"
KX = MODEL_CONFIGS[NAME]
PS, W, S = 32, 128, 4          # page size, window, slots
ROWS = 128 + 256 + 32          # a ring of 13 pages: window + a step + a page


def rings(hk, hd, layers=2, seed=0, dtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)
    shape = (layers, (S + 1) * ROWS, hk * hd)
    return (jnp.asarray(rng.standard_normal(shape), dtype),
            jnp.asarray(rng.standard_normal(shape), dtype))


def stream(spans, T):
    """`spans` = [(slot, tokens, context at the span's end)] as the arrays
    of a ragged step padded to T tokens (padding rows: the trash slot)."""
    q_start = np.full(S, T, np.int32)
    q_len, kv_len = np.zeros(S, np.int32), np.zeros(S, np.int32)
    slot_ids = np.full(S, S, np.int32)
    seq, pos = [], []
    for r, (slot, n, kv) in enumerate(spans):
        q_start[r], q_len[r], kv_len[r], slot_ids[r] = len(seq), n, kv, slot
        seq += [r] * n
        pos += list(range(kv - n, kv))
    n = len(seq)
    return (n, jnp.asarray(seq + [0] * (T - n), jnp.int32),
            jnp.asarray(pos + [-1] * (T - n), jnp.int32),
            *(jnp.asarray(a) for a in (q_start, q_len, kv_len, slot_ids)))


def brute(q, k_ring, v_ring, layer, slot, p, hk):
    """Token at position p of `slot`: a softmax over the ring rows of
    positions max(0, p - W + 1) .. p, by position alone."""
    H, hd = q.shape
    at = slot * ROWS + np.arange(max(0, p - W + 1), p + 1) % ROWS
    k = np.asarray(k_ring[layer, at], np.float32).reshape(-1, hk, hd)
    v = np.asarray(v_ring[layer, at], np.float32).reshape(-1, hk, hd)
    qq = np.asarray(q, np.float32).reshape(hk, H // hk, hd)
    s = np.einsum("kgd,skd->kgs", qq, k) / np.sqrt(hd)
    p_ = np.exp(s - s.max(-1, keepdims=True))
    p_ /= p_.sum(-1, keepdims=True)
    return np.einsum("kgs,skd->kgd", p_, v).reshape(H, hd)


# mixed rows on a 256-token rung (a tall stretch inside the 200-token span),
# decode rows only, and a span that starts at position 0
STREAMS = {
    "tall": (256, [(0, 1, 900), (2, 200, 1500), (1, 1, 50), (3, 40, 40)]),
    "rows": (16, [(3, 1, 129), (0, 1, 128), (1, 1, 7), (2, 1, 4000)]),
    "chunk": (64, [(1, 33, 161), (0, 9, 9)]),
}


@pytest.mark.parametrize("heads", [(8, 2, 128), (4, 4, 128), (8, 4, 64)],
                         ids=["gqa4x128", "mha128", "gqa2x64"])
@pytest.mark.parametrize("which", sorted(STREAMS))
def test_ragged_kernel_with_a_window_agrees_with_its_twin(heads, which):
    """The ragged kernel in interpret mode, the blockwise jnp twin and the
    materialising reference over one ring table, and all of them with a
    softmax over the window's positions found by arithmetic alone."""
    H, hk, hd = heads
    T, spans = STREAMS[which]
    n, seq, pos, q_start, q_len, kv_len, slot_ids = stream(spans, T)
    kr, vr = rings(hk, hd)
    q = jnp.asarray(np.random.default_rng(1).standard_normal((T, H, hd)),
                    jnp.bfloat16)
    pt, base = ring_table(slot_ids, kv_len, q_len, W, ROWS, PS, T)
    args = (1, pt, seq, pos, kv_len, q_start, q_len, PS)

    def twin(v):  # the float32 twin over the rings' values and `v`
        return ragged_attention_any("jnp", _f32(q), _f32(kr), v, *args,
                                    window=W, pos_base=base)[:n]

    kern = ragged_attention_any("pallas", q, kr, vr, *args, interpret=True,
                                window=W, pos_base=base)
    full = ragged_paged_attention(_f32(q), _f32(kr), _f32(vr), 1, pt, seq,
                                  pos, kv_len, PS, window=W, pos_base=base)
    # bf16 rings: the output's rounding and P's, from the case's values
    twin, _ = assert_kernel_close(kern[:n], q.dtype, vr, twin)
    np.testing.assert_allclose(np.asarray(full[:n]), twin, **F32_TOL)
    for r, (slot, m, kv) in enumerate(spans):  # each span's ends
        for t, p in ((int(q_start[r]), kv - m), (int(q_start[r]) + m - 1,
                                                 kv - 1)):
            np.testing.assert_allclose(
                twin[t], brute(q[t], kr, vr, 1, slot, p, hk), **F32_TOL)


@pytest.mark.parametrize("heads,inner", [((8, 2, 128), None),
                                         ((4, 4, 128), None),
                                         ((4, 4, 128), "mxu"),
                                         ((8, 4, 64), None)],
                         ids=["gqa_mxu", "mha_vpu", "mha_mxu", "hd64"])
def test_decode_kernel_with_a_window_agrees_with_its_twin(heads, inner):
    H, hk, hd = heads
    kr, vr = rings(hk, hd, seed=2)
    seq_lens = jnp.asarray([900, 50, 1500, 128], jnp.int32)
    slots = jnp.arange(S, dtype=jnp.int32)
    pt, base = ring_table(slots, seq_lens, jnp.ones_like(seq_lens), W, ROWS,
                          PS, 1)
    assert pt.shape == (S, 8)  # the window and a page, in whole blocks
    q = jnp.asarray(np.random.default_rng(3).standard_normal((S, H, hd)),
                    jnp.bfloat16)
    def twin(v):
        return paged_decode_attention_any("jnp", _f32(q), _f32(kr), v, 0, pt,
                                          seq_lens, PS, window=W,
                                          pos_base=base)

    kern = paged_attention.paged_decode_attention_pallas(
        q, kr, vr, 0, pt, seq_lens, PS, interpret=True, inner=inner,
        window=W, pos_base=base)
    twin, _ = assert_kernel_close(kern, q.dtype, vr, twin)
    for s in range(S):
        np.testing.assert_allclose(
            twin[s], brute(q[s], kr, vr, 0, s, int(seq_lens[s]) - 1, hk),
            **F32_TOL)


def test_a_mask_alone_would_read_stale_rows():
    """The ring holds the LAST `ROWS` positions: a launch whose table started
    at position 0 (a window served as a mask over the context) would name
    pages whose rows later positions have overwritten. The table's first
    page is what makes the ring readable at all."""
    kv, n = np.asarray([1500]), np.asarray([200])
    first = ring_first_page(kv, n, W, PS)
    assert int(first[0]) == (1500 - 200 - 127) // PS == 36
    assert 1500 - int(first[0]) * PS <= ROWS  # the walk fits the ring
    assert 1500 > ROWS                        # ...the context does not


def test_window_zero_traces_what_it_traced_before():
    """`window` 0 (every model but this family) adds nothing to a kernel's
    trace: no operand, no scalar, no mask term."""
    q = jnp.zeros((16, 8, 128), jnp.bfloat16)
    pool = jnp.zeros((2, 64 * PS, 256), jnp.bfloat16)
    pt = jnp.zeros((S, 16), jnp.int32)
    z = jnp.zeros((S,), jnp.int32)

    def traced(**kw):
        return str(jax.make_jaxpr(
            lambda q, kc, vc: ragged_attention.ragged_paged_attention_pallas(
                q, kc, vc, 1, pt, z, z, z, PS, interpret=True, **kw)
        )(q, pool, pool))

    plain, windowed = traced(), traced(window=W, pos_base=z)
    assert ragged_attention.WINDOW_NAME in windowed
    assert "swa_" not in plain and plain != windowed
    assert plain == traced(window=0, pos_base=None)


# ------------------------------------------------------------ the ring
def test_ring_rows_and_the_trash_slot():
    ring = alloc_ring(3, S, ROWS, (256, 256))
    assert ring.k.shape == ring.v.shape == (3, (S + 1) * ROWS, 256)
    assert ring.rows == ROWS and ring.nbytes == 2 * 3 * 5 * ROWS * 256 * 2
    assert alloc_ring(0, S, ROWS, (256, 256)) is None
    slots = jnp.asarray([0, 0, 3, 1], jnp.int32)
    pos = jnp.asarray([5, ROWS + 5, 2 * ROWS - 1, -1], jnp.int32)
    at = ring_write_slots(slots, pos, pos >= 0, ROWS, S)
    assert at.tolist() == [5, 5, 3 * ROWS + ROWS - 1, S * ROWS]  # trash
    # a pytree whose row count is static: a jit's carry, donated
    leaves, tree = jax.tree_util.tree_flatten(ring)
    assert len(leaves) == 2 and tree.unflatten(leaves).rows == ROWS


def test_ring_table_lists_the_pages_of_the_window():
    slots = jnp.asarray([2, 0, S, 1], jnp.int32)
    kv = jnp.asarray([1500, 40, 0, 4000], jnp.int32)
    n = jnp.asarray([200, 40, 0, 1], jnp.int32)
    pt, base = ring_table(slots, kv, n, W, ROWS, PS, 256)
    pages = ROWS // PS
    assert pt.shape[1] % 8 == 0 and pt.shape[1] * PS >= W + 256 + PS - 2
    assert base.tolist() == [36 * PS, 0, 0, (4000 - 128) // PS * PS]
    for r in range(4):
        first = int(base[r]) // PS
        want = [int(slots[r]) * pages + (first + j) % pages
                for j in range(pt.shape[1])]
        assert pt[r].tolist() == want
    # every position a query sees is on a listed page, at its ring row
    for r, (lo, hi) in enumerate([(1500 - 200 - 127, 1500), (0, 40)]):
        for p in (lo, hi - 1):
            col = (p - int(base[r])) // PS
            assert int(pt[r, col]) * PS + p % PS \
                == int(slots[r]) * ROWS + p % ROWS


# ------------------------- a slot's rows do not grow with its context
def _runtime(**kw):
    from ollamamq_tpu.engine.engine import ModelRuntime

    ecfg = EngineConfig(**{**dict(
        model=NAME, max_slots=4, num_pages=64, page_size=8,
        max_pages_per_seq=16, max_batch_tokens=32, token_granule=16), **kw})
    return ModelRuntime(NAME, KX, ecfg, dtype=jnp.float32)


def test_the_window_layers_bytes_are_fixed_and_the_pool_holds_full_layers():
    """A slot's window-layer bytes are window layers x ring rows x a K and a
    V row, whatever the context the runtime is built for; the paged pool's
    page bytes are ONE layer's (the tiny stack has one full layer)."""
    from ollamamq_tpu.telemetry import schema as tm

    small, large = _runtime(), _runtime(num_pages=256, max_pages_per_seq=64)
    row = 2 * KX.kv_dim * 4  # K and V rows, float32
    rows = KX.ring_rows(32, 8)
    assert rows == 8 + 32 + 8
    for rt in (small, large):
        ring = rt.cache.slot_state.ring
        assert ring.rows == rows
        assert ring.k.shape == (KX.count(WINDOW), 5 * rows, KX.kv_dim)
        assert rt.state_bytes["swa_ring_bytes"] \
            == 4 * 5 * rows * row == ring.nbytes
        assert rt.cache.kc.shape[0] == rt.cache.vc.shape[0] == KX.count(ATTENTION) == 1
        assert rt.stats()["swa_ring_bytes"] == ring.nbytes
    assert large.kv_bytes == 4 * small.kv_bytes  # the pool grows; not these
    assert small.kv_bytes == 64 * 8 * row        # ...and is one layer's
    gauge = {k: v.value for k, v in tm.HBM_SWA_RING_BYTES._children.items()}
    assert gauge[(NAME,)] == small.state_bytes["swa_ring_bytes"]
    plain = _runtime_of("test-tiny")
    assert plain.state_bytes["swa_ring_bytes"] == 0
    assert plain.cache.slot_state is None


def _runtime_of(name):
    from ollamamq_tpu.engine.engine import ModelRuntime

    ecfg = EngineConfig(model=name, max_slots=2, num_pages=16, page_size=8,
                        max_pages_per_seq=4)
    return ModelRuntime(name, MODEL_CONFIGS[name], ecfg, dtype=jnp.float32)


def test_prefix_cache_and_migration_are_off_with_a_window_layer():
    rt = _runtime(prefix_cache=True)
    assert rt.cache.prefix_cache is None  # a page of the full layers rebuilds no ring
    assert rt.export_request(1) is None


def test_the_rings_are_the_same_arrays_after_a_long_request():
    """Serve a request several times the ring through the engine's own two
    step programs: the rings' shapes are what they were, the ids are the
    ids of a runtime with a ring twice as long (nothing ever read a row the
    ring had let go)."""
    from testutil import single_device_greedy_tokens

    kw = dict(max_slots=2, num_pages=64, page_size=8, max_pages_per_seq=32,
              token_granule=16, decode_steps_per_iter=8)
    prompt = "the window slides over a long prompt " * 3  # 111 tokens
    a = single_device_greedy_tokens(NAME, prompt, max_tokens=40,
                                    max_batch_tokens=32, **kw)
    b = single_device_greedy_tokens(NAME, prompt, max_tokens=40,
                                    max_batch_tokens=96, **kw)
    assert len(a) == 40 and a == b
    assert KX.ring_rows(32, 8) * 3 < 111 + 40  # three turns of the ring


def test_note_swa_counts_pairs_and_rows_by_position():
    """The step sample's counters against a brute force over positions."""
    from ollamamq_tpu.engine.step_work import StepWork

    w, ps = KX.sliding_window, 8
    work = StepWork(KX, ps, NAME)

    class Sample:  # what the window layers' row of the table noted
        def note(self, **kw):
            if "swa_pairs" in kw:
                self.noted = kw

    def brute_counts(spans):
        pairs = rows = walk = full = 0
        for n, kv in spans:
            for p in range(kv - n, kv):
                pairs += min(p + 1, w)
            rows += min(kv, n + w - 1)
            walk += kv - max(0, kv - n - (w - 1)) // ps * ps
            full += kv
        return dict(swa_pairs=pairs, swa_ctx_rows=rows, swa_walk_rows=walk,
                    swa_full_rows=full)

    sp = Sample()
    spans = [(1, 200), (16, 16), (5, 7), (12, 100), (1, 3)]
    work.note(sp, [n for n, _ in spans], [kv for _, kv in spans])
    assert sp.noted == brute_counts(spans)
    assert sp.noted["swa_walk_rows"] < sp.noted["swa_full_rows"] // 2
    # a scan of k passes: k spans of one token at successive contexts
    work.note(sp, [4, 4], [10, 300], scan=True)
    assert sp.noted == brute_counts(
        [(1, kv) for end in (10, 300) for kv in range(end - 3, end + 1)])
    # a model without window layers notes nothing
    sp.noted = None
    StepWork(MODEL_CONFIGS["test-tiny"], ps, "test-tiny").note(sp, [4], [10])
    assert sp.noted is None


def test_step_samples_carry_the_window_counters(monkeypatch):
    """Three requests over the engine's own loop — spans beside decode rows,
    chunks, fused scans: every launched step says what its window layers
    attended beside what its full layer did."""
    from ollamamq_tpu.ops.sampling import SamplingParams
    from test_step_overlap import _engine, _prompt, _rt, drive

    eng = _engine(NAME)
    arrivals = [(i, f"u{i}", _prompt(i, 20 + 30 * i),
                 SamplingParams(max_tokens=10, temperature=0.0))
                for i in range(3)]
    got, samples = drive(eng, arrivals, False, monkeypatch)
    assert all(len(ids) == 10 for ids, _, _ in got.values())
    rt = _rt(eng)
    assert rt.cache.kc.shape[0] == 1 and rt.cache.slot_state.ring.k.shape[0] == 4
    assert {s["mode"] for s in samples} == {"ragged", "decode"}
    for s in samples:
        assert s["swa_pairs"] >= s["swa_ctx_rows"] >= 1
        assert s["swa_ctx_rows"] <= s["swa_walk_rows"] <= s["swa_full_rows"]
        assert s["attn_pairs"] >= s["swa_pairs"]  # the full layer's, beside
        assert s["swa_full_rows"] == (
            s["attn_pairs"] if s["mode"] == "decode" else s["attn_ctx_rows"])
    assert any(s["swa_walk_rows"] < s["swa_full_rows"] for s in samples)
