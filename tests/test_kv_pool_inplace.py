"""The KV pool is one buffer, read and written by layer index.

The pool ([L, S, Hk*hd]) rides the forwards' layer loop as its carry
(models/llama.py:scan_layers); attention — both Pallas kernels and their
jnp twins — takes the WHOLE pool and a layer index. These tests pin the
values: (a) attending over layer l of the whole pool is bit-identical to
attending over that layer alone; (b) a write at layer l changes the
written rows of layer l and nothing else; and the migration blob keeps
the wire format ([L, n, Hk, hd] arrays) it had when the pool was stored
per head. That the compiled step programs really update the pool in
place is tests/test_chip_compile.py's to show."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.engine import kv_cache as kvc
from ollamamq_tpu.models import llama
from ollamamq_tpu.ops.attention import (paged_decode_attention,
                                        paged_decode_attention_any,
                                        ragged_attention_any,
                                        ragged_paged_attention_blockwise)
from ollamamq_tpu.ops.quant import QuantKV, kv_quantize, kv_write

L, PS, MP, HK, H, HD = 3, 8, 4, 2, 4, 16
S = 16 * PS
LANES = HK * HD
# One prefill span, one decode row, one padding row.
PT = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [0, 0, 0, 0]], jnp.int32)
Q_START = jnp.asarray([0, 9, 10], jnp.int32)
Q_LEN = jnp.asarray([9, 1, 0], jnp.int32)
KV_LEN = jnp.asarray([20, 13, 0], jnp.int32)
TOK_SEQ = jnp.asarray([0] * 9 + [1], jnp.int32)
TOK_POS = jnp.asarray(list(range(11, 20)) + [12], jnp.int32)


def _pool(kind, seed):
    """A whole pool with every layer different: bf16-valued floats, or
    an int8 payload with its scale planes."""
    rng = np.random.default_rng(seed)
    raw = jnp.asarray(rng.normal(size=(L, S, HK, HD)), jnp.float32)
    if kind == "int8":
        q, s = kv_quantize(raw)
        return QuantKV(q.reshape(L, S, LANES), s)
    return raw.reshape(L, S, LANES).astype(jnp.bfloat16)


def _only(pool, layer):
    """The one-layer pool holding just `layer` — what a call "on pool[l]"
    sees."""
    if isinstance(pool, QuantKV):
        return QuantKV(pool.q[layer][None], pool.s[layer][None])
    return pool[layer][None]


def _queries(n, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n, H, HD)), jnp.bfloat16)


def _ragged(impl, q, kc, vc, layer):
    return ragged_attention_any(impl, q, kc, vc, layer, PT, TOK_SEQ, TOK_POS,
                                KV_LEN, Q_START, Q_LEN, PS, interpret=True)


def _decode(impl, q, kc, vc, layer):
    return paged_decode_attention_any(impl, q, kc, vc, layer, PT[:2],
                                      KV_LEN[:2], PS, interpret=True)


# (kernel, pool kind): bf16 through the Pallas kernels (interpret mode),
# int8 through the jnp twins select_attn_impl routes int8 pools to.
KERNELS = {
    "ragged-pallas-bf16": (_ragged, "pallas", "bf16", 10),
    "decode-pallas-bf16": (_decode, "pallas", "bf16", 2),
    "ragged-jnp-int8": (_ragged, "jnp", "int8", 10),
    "decode-jnp-int8": (_decode, "jnp", "int8", 2),
}


@pytest.mark.parametrize("layer", range(L), ids=["first", "middle", "last"])
@pytest.mark.parametrize("name", KERNELS)
def test_attention_by_layer_index_equals_attention_on_that_layer(name, layer):
    call, impl, kind, n_q = KERNELS[name]
    kc, vc, q = _pool(kind, 1), _pool(kind, 2), _queries(n_q, 3)
    whole = np.asarray(call(impl, q, kc, vc, layer), np.float32)
    alone = np.asarray(call(impl, q, _only(kc, layer), _only(vc, layer), 0),
                       np.float32)
    np.testing.assert_array_equal(whole, alone)
    # ... and it is that layer's attention, not another's.
    other = np.asarray(call(impl, q, kc, vc, (layer + 1) % L), np.float32)
    assert np.abs(whole - other).max() > 1e-2


def test_int8_twins_agree_on_the_whole_pool():
    """The two jnp int8 paths (blockwise ragged, materializing decode)
    read the same rows of the same layer."""
    kc, vc = _pool("int8", 1), _pool("int8", 2)
    q = _queries(10, 3)
    rag = ragged_paged_attention_blockwise(q, kc, vc, 1, PT, TOK_SEQ,
                                           TOK_POS, KV_LEN, PS)
    dec = paged_decode_attention(q[9:], kc, vc, 1, PT[1:2], KV_LEN[1:2], PS)
    np.testing.assert_allclose(np.asarray(rag[9:], np.float32),
                               np.asarray(dec, np.float32),
                               rtol=2e-2, atol=2e-2)


def _bits(pool):
    if isinstance(pool, QuantKV):
        return [np.asarray(pool.q), np.asarray(pool.s).view(np.uint32)]
    return [np.asarray(pool).view(np.uint16)]


@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("layer", range(L), ids=["first", "middle", "last"])
def test_a_write_at_one_layer_leaves_every_other_bit_unchanged(layer, kind):
    pool = _pool(kind, 4)
    slots = jnp.asarray([3, 40, 41, 127], jnp.int32)
    vals = jnp.asarray(np.random.default_rng(5).normal(size=(4, HK, HD)),
                       jnp.float32 if kind == "int8" else jnp.bfloat16)
    for before, after in zip(_bits(pool),
                             _bits(kv_write(pool, layer, slots, vals))):
        changed = np.zeros(before.shape, bool)
        changed[layer, np.asarray(slots)] = True
        assert (after[~changed] == before[~changed]).all()
        assert (after[changed] != before[changed]).mean() > 0.9


def test_forward_ragged_writes_its_rows_and_nothing_else(tiny_cfg,
                                                         tiny_params):
    """The layer loop carries the pool: after a step every layer holds
    new rows at exactly the step's write slots, and every other bit of
    the pool the step was given."""
    cfg = tiny_cfg
    rng = np.random.default_rng(6)
    shape = (cfg.num_layers, S, cfg.num_kv_heads * cfg.head_dim)
    kc = jnp.asarray(rng.normal(size=shape), jnp.float32)
    vc = jnp.asarray(rng.normal(size=shape), jnp.float32)
    ws = np.asarray([PT[s][p // PS] * PS + p % PS
                     for s, p in zip(TOK_SEQ, TOK_POS)], np.int32)
    tokens = rng.integers(1, cfg.vocab_size, size=10).astype(np.int32)
    _, kc2, vc2 = llama.forward_ragged(
        tiny_params, cfg, jnp.asarray(tokens), TOK_SEQ, TOK_POS,
        jnp.asarray(ws), jnp.asarray([8, 9, 0], jnp.int32), kc, vc, PT,
        Q_START, Q_LEN, KV_LEN, PS)
    written = np.zeros(shape[:2], bool)
    written[:, ws] = True
    for before, after in ((kc, kc2), (vc, vc2)):
        before, after = np.asarray(before), np.asarray(after)
        assert (after[~written] == before[~written]).all()
        assert (after[written] != before[written]).all()


def test_a_hybrid_step_writes_its_rows_of_pool_and_conv_state_only():
    """A stack whose layers differ (LFM2) carries TWO arrays through the
    layer loop: the pool, which has a layer for each ATTENTION layer only,
    and the conv layers' per-slot state. After a ragged step each holds new
    values at exactly the step's rows — the pool at its write slots, the
    state at the slots of the rows that had tokens — and every other bit
    it was given (other slots, the padding row's trash slot aside). After a
    decode pass: the active slots' state rolled, the others' kept."""
    from ollamamq_tpu.config import MODEL_CONFIGS
    from ollamamq_tpu.ops import shortconv

    cfg = MODEL_CONFIGS["test-tiny-lfm2"]
    params = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(7)
    n_attn, n_conv, n_slots = 3, 6, 5
    shape = (n_attn, S, cfg.kv_dim)
    kc = jnp.asarray(rng.normal(size=shape), jnp.float32)
    vc = jnp.asarray(rng.normal(size=shape), jnp.float32)
    conv = jnp.asarray(rng.normal(size=shortconv.alloc_state(
        n_conv, n_slots, cfg.conv_L_cache, cfg.hidden_size).shape),
        jnp.float32)
    assert conv.shape == (n_conv, 2, n_slots, cfg.hidden_size)
    ws = np.asarray([PT[s][p // PS] * PS + p % PS
                     for s, p in zip(TOK_SEQ, TOK_POS)], np.int32)
    tokens = rng.integers(1, cfg.vocab_size, size=10).astype(np.int32)
    slot_ids = jnp.asarray([3, 1, n_slots], jnp.int32)  # rows 0, 1; padding
    _, kc2, vc2, conv2 = llama.forward_ragged(
        params, cfg, jnp.asarray(tokens), TOK_SEQ, TOK_POS, jnp.asarray(ws),
        jnp.asarray([8, 9, 0], jnp.int32), kc, vc, PT, Q_START, Q_LEN,
        KV_LEN, PS, conv_state=conv, slot_ids=slot_ids,
        is_first=jnp.zeros(3, jnp.int32))
    written = np.zeros(shape[:2], bool)
    written[:, ws] = True
    for before, after in ((kc, kc2), (vc, vc2)):
        before, after = np.asarray(before), np.asarray(after)
        assert (after[~written] == before[~written]).all()
        assert (after[written] != before[written]).all()
    before, after = np.asarray(conv), np.asarray(conv2.conv)
    # [conv layers, K-1, slots, D]: a tap a plane, a slot a row of it; the
    # padding row's slot, n_slots, is none of them
    assert (after[:, :, [0, 2, 4]] == before[:, :, [0, 2, 4]]).all()
    assert (after[:, :, 3] != before[:, :, 3]).all()  # a span of 9: both taps
    # a span of ONE token: the old last tap moved up, the new z behind it
    assert (after[:, 0, 1] == before[:, 1, 1]).all()
    assert (after[:, 1, 1] != before[:, 1, 1]).all()

    active = jnp.asarray([0, 1, 0, 1, 0], jnp.int32)
    pt5 = jnp.where(active[:, None] > 0,
                    jnp.asarray([[6, 0, 0, 0]] * 5, jnp.int32), 0)
    _, _, _, conv3 = llama.forward_decode(
        params, cfg, jnp.asarray([5, 6, 7, 8, 9], jnp.int32),
        jnp.asarray([0, 13, 0, 20, 0], jnp.int32), kc2, vc2, pt5, PS,
        active=active, conv_state=conv2)
    last = np.asarray(conv3.conv)
    assert (last[:, :, [0, 2, 4]] == after[:, :, [0, 2, 4]]).all()
    for slot in (1, 3):
        assert (last[:, 0, slot] == after[:, 1, slot]).all()
        assert (last[:, 1, slot] != after[:, 1, slot]).all()


@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_migration_blob_keeps_its_wire_format(kind):
    """gather_page_run views the pool's rows per head at the boundary, so
    the packed blob is byte for byte what a fleet member that stores its
    pool as [L, S, Hk, hd] packs for the same pages — and it scatters back
    into other pages of another pool unchanged."""
    kc, vc = _pool(kind, 7), _pool(kind, 8)
    pages, idx = [5, 2, 9], np.concatenate(
        [np.arange(p * PS, (p + 1) * PS) for p in [5, 2, 9]])

    def per_head(pool):  # the per-head pool's take(axis=1): [L, n, Hk, hd]
        return np.asarray(pool).reshape(L, S, HK, HD)[:, idx]

    if kind == "int8":
        old = {"k_pages": per_head(kc.q), "v_pages": per_head(vc.q),
               "k_scale": np.asarray(kc.s)[:, idx],
               "v_scale": np.asarray(vc.s)[:, idx]}
    else:
        old = {"k_pages": per_head(kc), "v_pages": per_head(vc)}
    data = kvc.gather_page_run(kc, vc, pages, PS, HD)
    assert data["k_pages"].shape == (L, 3 * PS, HK, HD)
    head = {"version": 1, "kv_dtype": kind, "page_size": PS}
    assert (kvc.pack_migration_blob({**head, **data})
            == kvc.pack_migration_blob({**head, **old}))

    blob = kvc.unpack_migration_blob(kvc.pack_migration_blob(
        {**head, **data}))
    kc2, vc2 = kvc.scatter_page_run(_pool(kind, 9), _pool(kind, 10),
                                    [1, 7, 3], PS, blob)
    back = kvc.gather_page_run(kc2, vc2, [1, 7, 3], PS, HD)
    for key, arr in data.items():
        np.testing.assert_array_equal(
            np.asarray(back[key]).view(np.uint8), arr.view(np.uint8))
