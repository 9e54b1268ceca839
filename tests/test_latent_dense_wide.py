"""A wide span of a model with NO indexer attended in the expanded form
(PR 64): the dense kernel of ops/pallas/mla_attention.py with the masked
kernel's expanded programs, interpret mode against the jnp twin
(tests/test_deepseek_v32_wide.py has the masked kernel's cases, whose streams
these are), the layer over it with a full-rank q and no rotation
(Kimi-Linear's latent layers), and which launches hold the body."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import MODEL_CONFIGS
from ollamamq_tpu.models import llama
from ollamamq_tpu.ops import mla
from test_deepseek_v32_wide import (LEAD_CASES, WIDE_CASES,
                                    _control_flow_outside_kernels,
                                    _kernel_jaxpr, _paged_stream, _wide_case,
                                    wide_of_48)  # noqa: F401 (a fixture)


@pytest.mark.parametrize("heads", [32, 128])
@pytest.mark.parametrize("name", sorted(WIDE_CASES))
def test_a_dense_wide_span_is_attended_in_the_expanded_form(name, heads,
                                                            wide_of_48):
    """Interpret mode against ops/mla.sparse_attention with no selection
    followed by W_uv, at Kimi-Linear's 32 heads (two programs of WIDE_GROUP)
    and at openPangu's 128 (eight): the tokens of spans of at least WIDE
    tokens, and no others, come back in `o_v`, through W_uv already; every
    other row is what the launch without the expanded operands gives, bit
    for bit."""
    ka = wide_of_48
    case, T, args, expanded, twin, w_uv = _wide_case(name, heads, dense=True)
    today = ka.mla_dense_paged_attention_pallas(*args, interpret=True)
    o, o_v, served = ka.mla_dense_paged_attention_pallas(
        *args, interpret=True, expanded=expanded)
    served = np.asarray(served)
    want = np.concatenate([np.full(n, n >= ka.WIDE)
                           for n, _ in case["spans"]])
    assert (served[:T] == want).all() and not served[T:].any()
    assert served.sum() == case["served"]
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


class TestANopeLayerOverTheExpandedBody:
    """`_latent_attention_op` with a full-rank q (`q_lora_rank` 0) and no
    rotation (`mla_use_nope`) over the dense kernel's schedule (interpret
    mode), at head widths of whole lane tiles, under a WIDE of 48."""

    @pytest.fixture(scope="class")
    def layer(self):
        from ollamamq_tpu.ops.pallas import mla_attention as ka

        patch = pytest.MonkeyPatch()
        patch.setattr(ka, "WIDE", 48)  # read as the kernels trace
        jax.clear_caches()
        mc = dataclasses.replace(
            MODEL_CONFIGS["test-tiny-kimi-linear"], name="nope-wide-lanes",
            num_heads=16, num_kv_heads=16, head_dim=192, kv_lora_rank=128,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)
        assert mc.latent_lanes == 256 and mc.mla_use_nope
        assert not (mc.q_lora_rank or mc.index_topk)
        H, c, D = mc.num_heads, mc.kv_lora_rank, mc.hidden_size
        rng = np.random.default_rng(7)

        def w(*shape):
            return jnp.asarray(rng.standard_normal(shape)
                               * shape[0] ** -0.5, jnp.bfloat16)

        lp = {"wq": w(D, H * 192), "mla_wdkv": w(D, c + 64),
              "mla_kv_norm": jnp.ones((c,), jnp.bfloat16),
              "mla_wukv": w(c, H * 256), "wo": w(H * 128, D)}
        ps, n_pages = 8, 64
        kc = jnp.asarray(rng.standard_normal((1, n_pages * ps, 256)) * 0.3,
                         jnp.bfloat16).at[:, :, 192:].set(0)
        hidden = jnp.asarray(rng.standard_normal((1, 64, D)), jnp.bfloat16)
        widths = (H, mc.latent_lanes, c, 128, 128)

        def stream(spans, T=64):
            pt, ts, tp, qs, ql, kl, real = _paged_stream(
                spans, ps, 40, n_pages, np.random.default_rng(3), pad_to=T,
                rows=6)
            slots = jnp.where(tp >= 0, llama.flat_slot_indices(
                pt[ts], jnp.maximum(tp, 0)[:, None], ps)[:, 0], 0)
            few = mla.absorbed_lead("pallas", qs, ql, T, *widths)

            def run(impl, few=None, trace=False, name=None):
                seen = []

                def attn_fn(q, row, index, expanded=None):
                    assert index is None
                    _, _, out = llama._latent_ragged(
                        mc, q, row, index, kc, None, 0, slots, pt, ts, tp,
                        qs, ql, kl, ps, impl, True, name=name,
                        expanded=expanded)
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

        yield stream, widths, ka
        patch.undo()
        jax.clear_caches()

    @pytest.mark.parametrize("name", sorted(LEAD_CASES))
    def test_agrees_with_the_twin_with_and_without_the_lead(self, name,
                                                            layer):
        """Against the jnp twin's schedule, the absorbed form over the rung
        (`few` None) and over the rows that read it (`few` as
        ops/mla.absorbed_lead answers): the wide span's rows leave the
        launch through W_uv already, to the bit either way; the others
        within a bfloat16 rounding of each other; and the engine's count of
        the absorbed rows is the branch the device took."""
        stream, widths, ka = layer
        case = LEAD_CASES[name]
        run, (lead, few), real = stream(case["spans"])
        assert lead == ka.ABSORBED_LEAD and bool(few) == case["few"]
        assert ka.absorbed_rows([n for n, _ in case["spans"]], 64,
                                *widths) == (lead if case["few"] else 64)
        (twin, plain), (before, answer) = run("jnp"), run("pallas")
        assert not isinstance(plain, tuple)
        got, _ = run("pallas", (lead, few))
        wide = np.array(answer[2])[0]
        wide[real:] = False
        others = ~wide
        others[real:] = False
        assert wide.sum() == sum(n for n, _ in case["spans"] if n >= 48)
        assert np.array_equal(got[wide], before[wide])
        if not case["few"]:
            assert np.array_equal(got[others], before[others])
        scale = max(1.0, np.abs(twin[:real]).max())
        assert np.abs(got - before)[others].max() <= 2 ** -8 * scale
        for mine in (before, got):
            assert np.abs(mine - twin)[:real].max() <= 2 ** -6 * scale

    def test_a_named_launch_and_a_narrow_rung_expand_nothing(self, layer):
        """The prediction module's launch (`name=`) answers one array on
        the wide rung, as every launch does on a rung under WIDE, where the
        layer's trace holds no conditional and no loop."""
        stream, widths, ka = layer
        spans = LEAD_CASES["decode_rows_and_a_wide_span"]["spans"]
        run, few, _ = stream(spans)
        assert few is not None
        _, answer = run("pallas", name=ka.MTP_NAME)
        assert not isinstance(answer, tuple)
        run, few, _ = stream([(1, 90), (20, 200)], T=32)
        assert few is None
        assert _control_flow_outside_kernels(
            run("pallas", trace=True).jaxpr) == []


def _shape(shape, dt):
    return jax.ShapeDtypeStruct(shape, dt)


def _dense(ka, T, heads, tile=None, name=None, expanded=False):
    """The dense kernel's pallas_call at a 640-lane pool, as text."""
    bf, i32 = jnp.bfloat16, jnp.int32
    meta = (_shape((8, 40), i32),) + (_shape((8,), i32),) * 3
    more = (_shape((T, heads, 256), bf), _shape((heads, 256, 512), bf)) \
        if expanded else ()
    return _kernel_jaxpr(
        lambda q, pool, pt, qs, ql, kl, *e:
        ka.mla_dense_paged_attention_pallas(
            q, pool, 1, pt, qs, ql, kl, 32, 512, tile=tile, name=name,
            expanded=e or None),
        _shape((T, heads, 640), bf), _shape((2, 4096, 640), bf), *meta,
        *more)


@pytest.mark.parametrize("heads", [32, 128])
def test_a_dense_launch_under_wide_or_with_a_name_is_the_program_it_was(
        heads):
    """With the expanded operands a rung under WIDE, the decode scan's
    tiles of one and the prediction module's launch on the wide rung trace
    the program they trace without them (whose digests at openPangu's widths
    tests/test_deepseek_v32_wide.py pins); the wide rung's own launch is
    another: `heads / WIDE_GROUP` more programs, two more operands, one more
    result."""
    from ollamamq_tpu.ops.pallas import mla_attention as ka

    under = ka.WIDE - ka.WIDE % -64 - 64
    assert ka.expands(512, heads, 640, 512, 128, 128)
    assert not ka.expands(under, heads, 640, 512, 128, 128)
    for T, tile, name in ((under, None, None), (16, 1, None),
                          (512, None, ka.MTP_NAME)):
        assert _dense(ka, T, heads, tile, name) == _dense(
            ka, T, heads, tile, name, expanded=True), (T, tile, name)
    plain, wide = _dense(ka, 512, heads), _dense(ka, 512, heads,
                                                 expanded=True)
    assert plain != wide
    assert f"grid=({512 // ka.ATTEND_TILE},)" in plain
    assert f"grid=({512 // ka.ATTEND_TILE + heads // ka.WIDE_GROUP},)" in wide


# The masked kernel's pallas_call on the 512-token rung at DeepSeek-V3.2's
# widths WITH its expanded body, as the tree before PR 64 traced it (grid,
# blocks and body: `_kernel_jaxpr`): the dense launch's choice of operands
# and scratch is made at trace time and leaves this one's program alone.
# Take it again only with a change that means to touch that kernel.
MASKED_WIDE_DIGEST = "574a6fba0b5f2b43"


def test_the_masked_kernels_expanded_program_is_the_one_it_was():
    from ollamamq_tpu.ops.pallas import mla_attention as ka

    bf, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    T, C = 512, ka.context_lanes(40, 32)
    meta = (_shape((8, 40), i32),) + (_shape((8,), i32),) * 3
    text = _kernel_jaxpr(
        lambda q, sc, thr, pool, qe, w, *m:
        ka.mla_sparse_paged_attention_pallas(
            q, sc, thr, pool, 1, *m, 32, 512, expanded=(qe, w)),
        _shape((T, 128, 640), bf), _shape((T, C), f32), _shape((T,), f32),
        _shape((2, 4096, 640), bf), _shape((T, 128, 256), bf),
        _shape((128, 256, 512), bf), *meta)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == MASKED_WIDE_DIGEST


# (spans, rung, heads) -> (wide tokens, absorbed rows) for a model with no
# indexer: Kimi-Linear's cell's step, openPangu's.
DENSE_STEPS = {
    "kimi_decode_rows_and_a_chunk": ([(1, 9000)] * 15 + [(497, 12000)], 512,
                                     32, 497, 32),
    "kimi_a_prompts_last_chunk": ([(1, 9000)] * 15 + [(200, 16384)], 512, 32,
                                  0, 512),
    "openpangu_verify_spans_past_the_lead": (
        [(2, 900)] * 31 + [(450, 1800)], 512, 128, 450, 512),
    "openpangu_a_verify_pass": ([(2, 900)] * 32, 64, 128, 0, 0),
}


@pytest.mark.parametrize("name", sorted(DENSE_STEPS))
def test_step_sample_of_a_model_with_no_indexer_carries_the_wide_counts(
        name):
    """`kernel_counts` binds the latent kernel's own tests for every latent
    model on the Pallas path, and the dense latent row notes them beside
    `mla_rows`: a ragged step's, nothing for a scan or on the jnp path."""
    import types

    from ollamamq_tpu.engine.step_work import StepWork, kernel_counts
    from ollamamq_tpu.telemetry import schema as tm

    spans, rung, heads, wide, absorbed = DENSE_STEPS[name]
    cfg = dataclasses.replace(
        MODEL_CONFIGS["test-tiny-kimi-linear"], num_heads=heads,
        num_kv_heads=heads, head_dim=192, kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128)
    assert cfg.latent_lanes == 640 and not cfg.index_topk
    assert kernel_counts(cfg, "jnp") is None
    assert kernel_counts(MODEL_CONFIGS["test-tiny"],
                         "pallas").wide_tokens is None
    model = "dense-wide-" + name
    series = [c.labels(model=model) for c in (
        tm.MLA_WIDE_TOKENS_TOTAL, tm.MLA_ABSORBED_ROWS_TOTAL)]
    work = StepWork(cfg, 32, model, kernel_counts(cfg, "pallas"))
    tokens, kv = zip(*spans)
    noted = {}
    sp = types.SimpleNamespace(note=noted.update)
    work.note(sp, list(tokens), list(kv), stream_len=rung)
    assert (noted["mla_wide_tokens"], noted["mla_absorbed_rows"]) \
        == (wide, absorbed)
    assert noted["mla_rows"] == sum(tokens) and noted["mla_pairs"] > 0
    assert [s.value for s in series] == [wide, absorbed]
    work.note(sp, [8] * len(kv), list(kv), scan=True)
    assert noted["mla_wide_tokens"] == noted["mla_absorbed_rows"] == 0
    StepWork(cfg, 32, model).note(sp, list(tokens), list(kv),
                                  stream_len=rung)
    assert noted["mla_wide_tokens"] == noted["mla_absorbed_rows"] == 0
    assert [s.value for s in series] == [wide, absorbed]
