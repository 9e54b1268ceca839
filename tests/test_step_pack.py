"""One upload a step (engine/step_pack.py): a step program's host inputs
are fields of ONE int32 buffer, opened inside the program.

  - for every jit-key shape the engine uses, the buffer unpacked INSIDE
    a jitted function equals the fields it was made from bit for bit
    (floats compared as their words: -0.0, subnormals, inf and a NaN
    pattern included), and padding nobody wrote holds the fill values;
  - `PRNGKey(counter)` traced from the buffer's `rng` field has the
    eager call's bits, so no sampled stream moves;
  - a launched buffer is never written again: the next composition gets
    a fresh one (the transfer may alias host memory, and the step may
    still be running).
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ollamamq_tpu.config import EngineConfig
from ollamamq_tpu.engine import kv_cache as kvc
from ollamamq_tpu.engine import step_pack
from ollamamq_tpu.engine.engine import ModelRuntime
from ollamamq_tpu.ops.sampling import SamplingParams

S, MP, W = 64, 256, 64

# (id, layout, the jit key it serves): the ragged ladder's ends with and
# without drafts, the fused scan at k = 1 and 8, a sequence-parallel
# prompt. The draft cap and k are jit keys but not layout keys.
SHAPES = [
    ("ragged-T16-k0", lambda: step_pack.ragged_layout(16, S, MP, W)),
    ("ragged-T16-spec", lambda: step_pack.ragged_layout(16, S, MP, W)),
    ("ragged-T512-k0", lambda: step_pack.ragged_layout(512, S, MP, W)),
    ("ragged-T512-spec", lambda: step_pack.ragged_layout(512, S, MP, W)),
    ("decode-k1", lambda: step_pack.decode_layout(S, MP)),
    ("decode-k8", lambda: step_pack.decode_layout(S, MP)),
]

# Words a float field must carry untouched: -0.0, the smallest and the
# largest subnormal, inf, a quiet NaN with a payload, an ordinary value.
ODD_WORDS = np.array([0x80000000, 0x00000001, 0x007FFFFF, 0x7F800000,
                      0x7FC00123, 0x3F8CCCCD], np.uint32)


def _words(x) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(x)).view(np.uint32)


def _random_fields(lay, rng):
    fields = {}
    for name, view in zip(lay.names, lay.views(lay.new())):
        if view.dtype == np.float32:
            w = rng.integers(0, 2**32, size=view.shape, dtype=np.uint32)
            flat = w.reshape(-1)
            flat[:len(ODD_WORDS)] = ODD_WORDS[:flat.size]
            fields[name] = w.view(np.float32)
        else:
            fields[name] = rng.integers(-2**31, 2**31, size=view.shape,
                                        dtype=np.int64).astype(np.int32)
    return fields


@pytest.mark.parametrize("make", [m for _, m in SHAPES],
                         ids=[i for i, _ in SHAPES])
def test_unpacked_in_a_jit_equals_the_fields_bit_for_bit(make):
    lay = make()
    fields = _random_fields(lay, np.random.default_rng(lay.size))
    buf = lay.new()
    assert buf.dtype == np.int32 and buf.shape == (lay.size,)
    for name, view in zip(lay.names, lay.views(buf)):
        view[...] = fields[name]
    out = jax.jit(lay.unpack)(buf)
    assert len(out) == len(lay.names)
    for name, got in zip(lay.names, out):
        want = fields[name]
        assert got.shape == want.shape and got.dtype == want.dtype, name
        assert np.array_equal(_words(got), _words(want)), name
    # One field a name, no two overlapping, nothing of the buffer unused.
    assert sum(f.size for f in fields.values()) == lay.size
    assert lay.names[-1] == "rng"
    assert tuple(v.dtype for v in lay.sampling(buf)) == tuple(
        fields[n].dtype for n in step_pack.SAMPLING)


@pytest.mark.parametrize("make", [m for _, m in SHAPES],
                         ids=[i for i, _ in SHAPES])
def test_a_fresh_buffer_holds_the_padding_values(make):
    lay = make()
    a, b = lay.new(), lay.new()
    assert a is not b and not np.shares_memory(a, b)
    got = dict(zip(lay.names, jax.jit(lay.unpack)(a)))
    want = {"tok_pos": -1, "seed_rows": -1, "top_p": 1.0, "pen": 1.0,
            "pt": kvc.TRASH_PAGE}
    if "q_start" in got:  # a padding row: no tokens, the trash ring row
        want.update(q_start=got["tokens"].shape[0], slot_ids=S)
    for name, x in got.items():
        assert np.all(np.asarray(x) == want.get(name, 0)), name


@pytest.mark.parametrize("counter", [1, 7, 2**31 - 1])
def test_the_key_made_in_the_program_has_the_eager_keys_bits(counter):
    lay = step_pack.decode_layout(4, 8)
    buf = lay.new()
    lay.view(buf, "rng")[0] = counter

    def program(buf):
        rng = lay.unpack(buf)[-1]
        key = jax.random.PRNGKey(rng[0])
        return key, jax.random.split(key, 3)

    key, subs = jax.jit(program)(buf)
    eager = jax.random.PRNGKey(counter)
    assert np.array_equal(_words(key), _words(eager))
    assert np.array_equal(_words(subs), _words(jax.random.split(eager, 3)))


def test_the_rng_counter_stays_inside_an_int32_field():
    rt = object.__new__(ModelRuntime)
    rt._rng_counter = 2**31 - 2
    assert rt._next_rng() == 2**31 - 1
    assert rt._next_rng() == 0  # wraps; never overflows the field
    assert rt._next_rng() == 1


@pytest.mark.parametrize("temperature", [0.0, 0.9], ids=["greedy", "sampled"])
def test_a_launched_buffer_is_never_written_again(temperature):
    """Pipelined ticks of a real runtime, every dispatch's buffer kept
    with a copy of what it held at launch: later compositions — which
    advance positions, page tables and sampling rows — leave each one
    as it was, every launch gets its own buffer, and every generative
    sample reports the one transfer it made."""
    from ollamamq_tpu.engine.engine import TPUEngine
    from ollamamq_tpu.telemetry.stepprof import PROFILER

    eng = TPUEngine(
        EngineConfig(model="test-tiny", max_slots=4, num_pages=96,
                     page_size=8, max_pages_per_seq=16,
                     max_batch_tokens=32,
                     token_granule=8, decode_steps_per_iter=4),
        models={"test-tiny": None}, blocklist_path=None, dtype=jnp.float32)
    rt = eng.runtimes["test-tiny"]
    launched = []  # (site, buffer as handed over, its contents then)
    for site in ("_dispatch_ragged", "_dispatch_decode"):
        def spy(*args, _orig=getattr(rt, site), _site=site):
            launched.append((_site, args[-1], args[-1].copy()))
            return _orig(*args)
        setattr(rt, site, spy)
    PROFILER.reset()
    reqs = [eng.enqueue_request(
        f"u{i}", "", "test-tiny",
        prompt_tokens=[10 + (7 * i + 3 * j) % 200 for j in range(n)],
        sampling=SamplingParams(max_tokens=12, temperature=temperature,
                                seed=11 * (i % 2)))
        for i, n in enumerate((70, 15, 33, 9))]
    for _ in range(400):  # the engine's own pipelined tick, by hand
        if all(r.stats.finished_at for r in reqs):
            break
        eng._loop_once()
    eng._settle_all()
    assert all(r.stats.finished_at for r in reqs), "requests wedged"
    assert {s for s, *_ in launched} == {"_dispatch_ragged",
                                         "_dispatch_decode"}
    assert len({id(b) for _, b, _ in launched}) == len(launched)
    for site, buf, then in launched:
        assert buf.dtype == np.int32 and buf.ndim == 1
        assert np.array_equal(buf, then), site
    for (_, a, _), (_, b, _) in itertools.combinations(launched, 2):
        assert not np.shares_memory(a, b)
    gen = [s for s in PROFILER.tail()
           if s["mode"] in ("ragged", "decode")]
    assert sum(s["overlapped"] for s in gen) >= 3  # steps really overlapped
    assert len(gen) == len(launched)
    assert all(s["h2d_transfers"] == 1 for s in gen), gen
    assert sorted(s["h2d_bytes"] for s in gen) == sorted(
        b.nbytes for _, b, _ in launched)
