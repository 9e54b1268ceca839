"""tests/test_deepseek_v32.py, third file: a wide span attended in the
expanded form of ops/pallas/mla_attention.py (PR 49), interpret mode against
the jnp twin, and the layer over the expanded body (PR 55)."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.models import llama
from ollamamq_tpu.ops import mla
from test_deepseek_v32 import DS, make_params


def _paged_stream(spans, ps, mp, n_pages, rng, pad_to=32, rows=0):
    """A ragged step's metadata for `spans` = (tokens, context at the span's
    end) a row, each sequence on scattered pages of its own: (page table
    with two spare rows, tok_seq, tok_pos, q_start, q_len, kv_len) as int32
    arrays and the stream's real length; the stream is padded to `pad_to`,
    the table to `rows` where that is more."""
    rows = max(rows, len(spans) + 2)
    pt = np.zeros((rows, mp), np.int32)
    perm = rng.permutation(np.arange(1, n_pages))
    used = 0
    qs, ql, kl, ts, tp = [], [], [], [], []
    for r, (n, kv) in enumerate(spans):
        need = -(-kv // ps)
        pt[r, :need] = perm[used:used + need]
        used += need
        qs.append(len(ts)); ql.append(n); kl.append(kv)
        ts += [r] * n
        tp += list(range(kv - n, kv))
    T = len(ts)
    Tp = -(-T // pad_to) * pad_to
    ts += [0] * (Tp - T)
    tp += [-1] * (Tp - T)
    while len(qs) < rows:
        qs.append(Tp); ql.append(0); kl.append(0)
    return (jnp.asarray(pt), *(jnp.asarray(a, jnp.int32)
                               for a in (ts, tp, qs, ql, kl)), T)


# A prefill span of at least WIDE tokens is attended in the EXPANDED form
# (PR 49): programs of the same launch expand each block's keys and values
# once a group of heads and leave the span's rows in v_head_dim lanes; every
# other row keeps the absorbed tiles, bit for bit. (spans = (tokens, context
# at the span's end) a row, under a WIDE of 48; mp the table's pages a
# sequence.) Head widths of whole lane tiles, which the body needs; pages of
# 8 put 32 in a block of the walk, so a table of 90 (or 41) pages needs
# padding to whole blocks; 32 heads are two programs of WIDE_GROUP.
WIDE_CASES = {
    # a prompt's first chunk: thr is -inf, every position is kept
    "alone_at_base_0": dict(spans=[(64, 64)], served=64, mp=41),
    # a later chunk, the selection real; the last block holds 44 positions
    "alone_past_index_topk": dict(spans=[(64, 300)], served=64),
    # two blocks and 188 positions of a third, a table of 90 pages
    "a_partial_last_block": dict(spans=[(48, 700)], served=48),
    # the cell's step in small: decode rows first, one wide span, a short one
    "decode_rows_a_wide_span_a_short_one": dict(
        spans=[(1, 150), (1, 77), (1, 513), (70, 600), (9, 40)], served=70),
    # both take the expanded programs, each over its own sequence's pages
    "two_wide_spans": dict(spans=[(1, 9), (50, 520), (60, 90)], served=110),
    # one token short: the tiles, and today's bits
    "one_short_of_wide": dict(spans=[(1, 30), (47, 400)], served=0),
    # the trash page at the largest finite value: a walk's last block reads
    # it past the span's last page, and what is expanded from it is masked
    "the_trash_page_at_the_largest_finite": dict(
        spans=[(56, 530)], served=56,
        poison=float(jnp.finfo(jnp.bfloat16).max)),
}


@pytest.fixture
def wide_of_48(monkeypatch):
    """The kernel's WIDE at 48 tokens (a constant it reads as it traces: the
    traces kept from before, and these, are dropped)."""
    from ollamamq_tpu.ops.pallas import mla_attention as ka

    monkeypatch.setattr(ka, "WIDE", 48)
    jax.clear_caches()
    yield ka
    jax.clear_caches()


def _wide_case(name, H=32, dense=False):
    """(the case, the stream's real length, the kernel's arguments, the
    expanded form's operands, the twin's answer through W_uv, W_uv).
    `dense`: no selection — the dense kernel's arguments
    (tests/test_latent_dense_wide.py)."""
    from ollamamq_tpu.ops.pallas import mla_attention as ka

    case = WIDE_CASES[name]
    spans = case["spans"]
    rng = np.random.default_rng(len(name))
    ps, rank, lanes, dn, dv, dr, topk = 8, 128, 256, 128, 128, 64, 16
    mp = case.get("mp", 90)
    n_pages = 2 + sum(-(-kv // ps) for _, kv in spans)
    lat = jnp.asarray(rng.standard_normal((2, n_pages * ps, lanes)) * 0.3,
                      jnp.bfloat16).at[:, :, rank + dr:].set(0)
    lat = lat.at[:, :ps].set(case.get("poison", 3e4))
    pt, tok_seq, tok_pos, qs, ql, kl, T = _paged_stream(spans, ps, mp,
                                                        n_pages, rng)
    Tp = tok_pos.shape[0]
    scores = jnp.asarray(rng.standard_normal(
        (Tp, ka.context_lanes(mp, ps))), jnp.float32)
    thr = mla.select_threshold(scores, tok_pos, topk)
    q_nope, q_rope = (jnp.asarray(rng.standard_normal((Tp, H, n)) * 0.3,
                                  jnp.bfloat16) for n in (dn, dr))
    wukv = jnp.asarray(rng.standard_normal((rank, H, dn + dv))
                       * rank ** -0.5, jnp.bfloat16)
    pad = jnp.zeros((Tp, H, lanes - rank - dr), jnp.float32)
    q_abs = jnp.concatenate([jnp.einsum(
        "thn,chn->thc", q_nope, wukv[..., :dn],
        preferred_element_type=jnp.float32), q_rope.astype(jnp.float32),
        pad], axis=-1).astype(jnp.bfloat16)
    expanded = (jnp.concatenate([q_nope, q_rope, pad.astype(jnp.bfloat16)],
                                axis=-1), jnp.transpose(wukv, (1, 2, 0)))
    if dense:
        scores = thr = None
    args = (q_abs, *(() if dense else (scores, thr)), lat, 1, pt, qs, ql, kl,
            ps, rank)

    def w_uv(o):
        return np.asarray(jnp.einsum(
            "thc,chv->thv", o, wukv[..., dn:],
            preferred_element_type=jnp.float32), np.float32)

    twin = w_uv(mla.sparse_attention(q_abs, scores, thr, lat, 1, pt, tok_seq,
                                     tok_pos, ps, rank))
    return case, T, args, expanded, twin, w_uv


@pytest.mark.parametrize("name", sorted(WIDE_CASES))
def test_a_wide_span_is_attended_in_the_expanded_form(name, wide_of_48):
    """Interpret mode against ops/mla.sparse_attention followed by W_uv: the
    tokens of spans of at least WIDE tokens, and no others, come back in
    `o_v`, through W_uv already; every other row is what the launch without
    the expanded operands gives, bit for bit."""
    ka = wide_of_48
    case, T, args, expanded, twin, w_uv = _wide_case(name)
    today = ka.mla_sparse_paged_attention_pallas(*args, interpret=True)
    o, o_v, served = ka.mla_sparse_paged_attention_pallas(
        *args, interpret=True, expanded=expanded)
    served = np.asarray(served)
    want = np.zeros(len(served), bool)
    for start, (n, _) in zip(np.cumsum([0] + [n for n, _ in case["spans"]]),
                             case["spans"]):
        want[start:start + n] = n >= ka.WIDE
    assert (served == want).all() and served.sum() == case["served"]
    others = ~served
    others[T:] = False
    assert np.array_equal(np.asarray(o, np.float32)[others],
                          np.asarray(today, np.float32)[others])
    got = np.where(served[:, None, None], np.asarray(o_v, np.float32),
                   w_uv(o))[:T]
    assert np.isfinite(got).all()
    # two roundings apart: K and V are rounded where the absorbed q is
    assert np.abs(got - twin[:T]).max() <= 2 ** -7 * max(
        1.0, np.abs(twin[:T]).max())


# A layer over a launch that holds the expanded body computes the ABSORBED
# form — q through W_uk before the launch, the attended latent through W_uv
# behind it — for the stream's first ABSORBED_LEAD rows alone where every row
# behind them is a wide span's or padding (`few`, chosen on the device from
# the step's own spans), and for the rung otherwise. (spans under a WIDE of
# 48 on a rung of 64, the lead 32; `few`: which branch the step takes.)
LEAD_CASES = {
    # the cell's step in small: rows 2..56 are the span's, 57..63 padding
    "decode_rows_and_a_wide_span": dict(
        spans=[(1, 90), (1, 33), (55, 200)], few=True),
    # a narrow span behind the lead reads the absorbed form: the rung
    "a_wide_span_and_a_narrow_one_behind_the_lead": dict(
        spans=[(1, 90), (1, 33), (50, 300), (5, 20)], few=False),
    # a prompt's last chunk, under WIDE, alone on the wide rung
    "a_narrow_span_alone": dict(spans=[(1, 90), (40, 200)], few=False),
}


def _control_flow_outside_kernels(jaxpr) -> list:
    """The `cond` and `while` equations of a traced function, by name, the
    kernels' own aside."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name in ("cond", "while"):
            found.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _control_flow_outside_kernels(sub)
    return found


class TestALayerOverTheExpandedBody:
    """`_latent_attention_op` with the kernel's schedule (interpret mode),
    at head widths of whole lane tiles, under a WIDE of 48: one set of
    weights, pools and traced kernels for the cases."""

    @pytest.fixture(scope="class")
    def layer(self):
        from ollamamq_tpu.ops.pallas import mla_attention as ka

        patch = pytest.MonkeyPatch()
        patch.setattr(ka, "WIDE", 48)  # read as the kernels trace
        jax.clear_caches()
        mc = dataclasses.replace(
            DS, name="wide-lanes", num_heads=16, num_kv_heads=16,
            head_dim=192, kv_lora_rank=128, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, index_head_dim=64)
        assert mc.latent_lanes == 256
        params = make_params(mc, dtype=jnp.bfloat16)
        lp = {k: v[0] for k, v in params["layers"].items()
              if k in llama.MLA_PARAMS or k == "wo"}
        ps, n_pages = 8, 64
        rng = np.random.default_rng(4)
        kc = jnp.asarray(rng.standard_normal((1, n_pages * ps, 256)) * 0.3,
                         jnp.bfloat16).at[:, :, 192:].set(0)
        vc = jnp.asarray(rng.standard_normal((1, n_pages * ps, 64)),
                         jnp.bfloat16)
        hidden = jnp.asarray(rng.standard_normal((1, 64, mc.hidden_size)),
                             jnp.bfloat16)
        widths = (mc.num_heads, mc.latent_lanes, mc.kv_lora_rank,
                  mc.qk_nope_head_dim, mc.v_head_dim)

        def stream(spans, T=64):
            """(run(impl, few) -> the layer's [T, D] float32 and what the
            schedule answered; few: ops/mla.absorbed_lead's answer; real)"""
            pt, ts, tp, qs, ql, kl, real = _paged_stream(
                spans, ps, 40, n_pages, np.random.default_rng(3), pad_to=T,
                rows=6)  # one shape, one trace of the kernels, for the cases
            slots = jnp.where(tp >= 0, llama.flat_slot_indices(
                pt[ts], jnp.maximum(tp, 0)[:, None], ps)[:, 0], 0)
            few = mla.absorbed_lead("pallas", qs, ql, T, *widths)

            def run(impl, few=None, trace=False):
                seen = []

                def attn_fn(q, row, index, expanded=None):
                    _, _, out = llama._latent_ragged(
                        mc, q, row, index, kc, vc, 0, slots, pt, ts, tp, qs,
                        ql, kl, ps, impl, True, expanded=expanded)
                    seen.append(out)
                    return out

                def op(h):
                    return llama._latent_attention_op(
                        mc, lp, h, jnp.maximum(tp, 0)[None], attn_fn, few)

                if trace:
                    return jax.make_jaxpr(op)(hidden[:, :T])
                op = jax.jit(op) if impl == "jnp" else op
                return np.asarray(op(hidden[:, :T]), np.float32)[0], seen[0]
            return run, few, real

        yield types.SimpleNamespace(stream=stream, widths=widths, ka=ka)
        patch.undo()
        jax.clear_caches()

    def test_takes_a_wide_spans_rows_as_the_kernel_leaves_them(self, layer):
        """Against the jnp twin's schedule: the layer builds the expanded
        form's q and `[W_uk | W_uv]^T`, the kernel serves the wide span from
        them, and `attn_out` takes those rows through W_uv already and the
        others through it — one answer, and `wide` names the span's rows."""
        run, _, real = layer.stream(
            LEAD_CASES["a_wide_span_and_a_narrow_one_behind_the_lead"][
                "spans"])
        (twin, plain), (got, answer) = run("jnp"), run("pallas")
        assert not isinstance(plain, tuple)
        wide = np.asarray(answer[2])[0]
        assert wide[2:52].all() and wide.sum() == 50
        assert np.abs(got - twin)[:real].max() <= 2 ** -6 * max(
            1.0, np.abs(twin[:real]).max())

    @pytest.mark.parametrize("name", sorted(LEAD_CASES))
    def test_computes_the_absorbed_form_for_the_rows_that_read_it(
            self, name, layer):
        """Against the same layer with the absorbed form over every row of
        the rung (`few` None: the formulation before): a wide span's rows to
        the bit — `o_v` is untouched — and every other real row within one
        bfloat16 rounding of it on the `few` branch (a contraction over 32
        rows may be tiled otherwise than one over the rung), to the bit on
        the other; all within the twin's bound; and the engine's count of
        the rows is the branch the device took."""
        case = LEAD_CASES[name]
        run, (lead, few), real = layer.stream(case["spans"])
        assert lead == layer.ka.ABSORBED_LEAD == 32
        assert bool(few) == case["few"]
        assert layer.ka.absorbed_rows(
            [n for n, _ in case["spans"]], 64, *layer.widths) == (
                lead if case["few"] else 64)
        before, answer = run("pallas")
        got, _ = run("pallas", (lead, few))
        wide = np.array(answer[2])[0]
        wide[real:] = False
        others = ~wide
        others[real:] = False
        assert wide.sum() == sum(n for n, _ in case["spans"] if n >= 48)
        assert np.array_equal(got[wide], before[wide])
        if not case["few"]:
            assert np.array_equal(got[others], before[others])
        scale = max(1.0, np.abs(before[:real]).max())
        assert np.abs(got - before)[others].max() <= 2 ** -8 * scale
        twin, _ = run("jnp")
        assert np.abs(got - twin)[:real].max() <= 2 ** -6 * max(
            1.0, np.abs(twin[:real]).max())

    def test_traces_no_conditional_where_nothing_is_expanded(self, layer):
        """A rung under WIDE, and the jnp path on any: `few` is None and the
        layer's trace holds no `cond` and no loop (on the wide rung one
        of each: the conditional before the launch, the loop over W_uv's
        tiles of rows behind it)."""
        spans = [(1, 90), (20, 200)]
        run, few, _ = layer.stream(spans, T=32)
        assert few is None
        assert mla.absorbed_lead("jnp", None, None, 64, *layer.widths) is None
        assert _control_flow_outside_kernels(
            run("pallas", trace=True).jaxpr) == []
        run, few, _ = layer.stream(spans)
        assert _control_flow_outside_kernels(
            run("pallas", few, trace=True).jaxpr) == ["cond", "while"]


def _kernel_jaxpr(fn, *shapes):
    """The pallas_call a traced launch holds, as text: its grid and blocks,
    and the kernel's body."""
    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found = find(sub)
                if found is not None:
                    return found

    eqn = find(jax.make_jaxpr(fn)(*shapes).jaxpr)
    return str(eqn.params["grid_mapping"]) + str(eqn.params["jaxpr"])


def test_no_other_launch_holds_the_expanded_body():
    """The dense kernel's traced program is the one PR 48's tree traced (a
    digest of its text at openPangu's widths, a ragged rung and the scan's
    tiles of one: take it again from `_kernel_jaxpr` only with a change that
    means to touch that kernel), and the masked kernel's on a rung under
    WIDE is the same program with the expanded operands as without."""
    import hashlib

    from ollamamq_tpu.ops.pallas import mla_attention as ka

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt)

    bf, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    pool = s((2, 4096, 640), bf)
    meta = (s((8, 40), i32), s((8,), i32), s((8,), i32), s((8,), i32))
    for (T, tile), digest in (((512, None), "896b332f0bdee44d"),
                              ((16, 1), "c9460fcf403aa502")):
        text = str(jax.make_jaxpr(
            lambda q, pool, *m: ka.mla_dense_paged_attention_pallas(
                q, pool, 1, *m, 32, 512, tile=tile))(
                    s((T, 128, 640), bf), pool, *meta))
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
    T = ka.WIDE - ka.WIDE % -64 - 64  # the rung under WIDE
    C = ka.context_lanes(40, 32)
    sel = (s((T, 128, 640), bf), s((T, C), f32), s((T,), f32), pool)
    plain = _kernel_jaxpr(
        lambda q, sc, thr, pool, *m: ka.mla_sparse_paged_attention_pallas(
            q, sc, thr, pool, 1, *m, 32, 512), *sel, *meta)
    given = _kernel_jaxpr(
        lambda q, sc, thr, pool, qe, w, *m:
        ka.mla_sparse_paged_attention_pallas(
            q, sc, thr, pool, 1, *m, 32, 512, expanded=(qe, w)),
        *sel, s((T, 128, 256), bf), s((128, 256, 512), bf), *meta)
    assert ka.expands(512, 128, 640, 512, 128, 128)
    assert not ka.expands(T, 128, 640, 512, 128, 128)
    assert plain == given


# `ModelRuntime._note_latent` puts the kernel's own count of those tokens on
# the step's sample: (spans, rung, wide tokens) at DeepSeek-V3.2's widths.
WIDE_STEPS = {
    "the_cells_step": ([(1, 9000)] * 5 + [(507, 12000)], 512, 507, 32),
    "a_tail_and_a_head": ([(1, 9000)] * 4 + [(188, 16000), (320, 320)], 512,
                          320, 512),
    "a_wide_span_alone": ([(512, 4096)], 512, 512, 32),
    "a_wide_span_and_padding": ([(1, 9000)] * 16 + [(400, 400)], 512, 400,
                                32),
    "two_short_spans": ([(250, 8200), (262, 262)], 512, 0, 512),
    "a_rung_under_wide": ([(1, 9000), (255, 255)], 256, 0, 0),
    "decode_rows_alone": ([(1, 9000)] * 16, 16, 0, 0),
}


@pytest.mark.parametrize("name", sorted(WIDE_STEPS))
def test_step_sample_carries_mla_wide_tokens(name):
    """From a step's composition alone, beside `mla_rows`: a ragged step's,
    where the kernel serves; nothing in a fused scan or on the jnp path. And
    `mla_absorbed_rows` beside it: the lead where every row behind it is a
    wide span's, the rung where one is not, 0 where nothing is expanded."""
    import dataclasses
    import functools
    import types

    from ollamamq_tpu.config import MODEL_CONFIGS
    from ollamamq_tpu.engine.step_work import KernelCounts, StepWork
    from ollamamq_tpu.ops.pallas.kv_contract import tall_tokens
    from ollamamq_tpu.ops.pallas.mla_attention import (WIDE, absorbed_rows,
                                                       wide_tokens)
    from ollamamq_tpu.telemetry import schema as tm

    spans, rung, wide, absorbed = WIDE_STEPS[name]
    assert wide == sum(n for n, _ in spans if n >= WIDE) * (rung >= WIDE)
    series = [c.labels(model="wide-" + name) for c in (
        tm.MLA_ROWS_TOTAL, tm.DSA_CTX_TOKENS_TOTAL,
        tm.DSA_SELECTED_TOKENS_TOTAL, tm.MLA_WIDE_TOKENS_TOTAL,
        tm.MLA_ABSORBED_ROWS_TOTAL)]
    cfg = dataclasses.replace(MODEL_CONFIGS["test-tiny-deepseek-v32"],
                              kv_lora_rank=512, index_topk=2048)
    widths = dict(heads=128, lanes=640, rank=512, nope=128, v=128)
    work = StepWork(cfg, 32, "wide-" + name, KernelCounts(
        tall_tokens, functools.partial(wide_tokens, **widths),
        functools.partial(absorbed_rows, **widths)))
    tokens, kv = zip(*spans)
    noted = {}
    sp = types.SimpleNamespace(note=noted.update)
    work.note(sp, list(tokens), list(kv), stream_len=rung)
    assert noted["mla_wide_tokens"] == wide
    assert noted["mla_absorbed_rows"] == absorbed
    assert noted["mla_rows"] == sum(n for n, _ in spans)
    assert series[3].value == wide and series[4].value == absorbed
    work.note(sp, [8] * len(kv), list(kv), scan=True)
    assert noted["mla_wide_tokens"] == noted["mla_absorbed_rows"] == 0
    # the jnp path: no kernel, nothing expanded
    StepWork(cfg, 32, "wide-" + name).note(sp, list(tokens), list(kv),
                                           stream_len=rung)
    assert noted["mla_wide_tokens"] == noted["mla_absorbed_rows"] == 0
    assert series[3].value == wide and series[4].value == absorbed
