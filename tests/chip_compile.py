"""What the four `test_chip_compile*.py` files share (one file was one worker's
910 s of a 1470 s limit): the shapes they compile for a DESCRIBED TPU v5e
(`conftest.py:v5e`), the engine's own step programs lowered for it, and
`scripts/step_hlo_copies.py` run on a configuration file."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from ollamamq_tpu.config import ModelConfig
from ollamamq_tpu.models import llama
from ollamamq_tpu.ops.attention import (paged_decode_attention_any,
                                        ragged_attention_any)
from ollamamq_tpu.ops.quant import QuantKV
from ollamamq_tpu.parallel.mesh import make_mesh
from ollamamq_tpu.parallel.sharding import kv_cache_spec


# llama3.2:1b heads (config.py) under the CLI defaults: 64 slots, 256
# pages a sequence, a 1024-page pool of 32-token pages.
H, HK, HD = 32, 8, 64
B, MP, PS, NP = 64, 256, 32, 1024
T = 64
LAYERS = 4  # a small stack: the kernels read layer 2 of it by index


def _shapes(sharding_of, kv_dtype=jnp.bfloat16, heads=(H, HK, HD), T=T):
    """(q_ragged, q_decode, pool, page_table, [T] meta, [B] meta) as
    ShapeDtypeStructs; `sharding_of(spec)` places each."""
    def s(shape, dt, spec=P()):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding_of(spec))

    h, hk, hd = heads
    heads = P(None, "tensor", None)
    pool = s((LAYERS, NP * PS, hk * hd), kv_dtype, kv_cache_spec())
    if kv_dtype == jnp.int8:
        pool = QuantKV(pool, s((LAYERS, NP * PS, hk), jnp.float32,
                               kv_cache_spec()))
    return (s((T, h, hd), jnp.bfloat16, heads),
            s((B, h, hd), jnp.bfloat16, heads), pool,
            s((B, MP), jnp.int32), s((T,), jnp.int32), s((B,), jnp.int32))


def _compile_ragged(shapes, mesh=None):
    q, _, pool, pt, per_tok, per_seq = shapes
    return jax.jit(
        lambda q, kc, vc, pt, ts, tp, kl, qs, ql: ragged_attention_any(
            "pallas", q, kc, vc, 2, pt, ts, tp, kl, qs, ql, PS, mesh=mesh)
    ).lower(q, pool, pool, pt, per_tok, per_tok, per_seq, per_seq,
            per_seq).compile()


def _compile_decode(shapes, mesh=None):
    _, q, pool, pt, _, per_seq = shapes
    return jax.jit(
        lambda q, kc, vc, pt, sl: paged_decode_attention_any(
            "pallas", q, kc, vc, 2, pt, sl, PS, mesh=mesh)
    ).lower(q, pool, pool, pt, per_seq).compile()


def _compile_at(v5e, compile_fn, tp, heads, tokens):
    if tp == 1:
        mesh, one = None, SingleDeviceSharding(v5e.devices[0])
        sharding_of = lambda spec: one  # noqa: E731
    else:
        mesh = make_mesh(tp=tp, devices=v5e.devices)
        sharding_of = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    compiled = compile_fn(_shapes(sharding_of, heads=heads, T=tokens),
                          mesh=mesh)
    assert "tpu_custom_call" in compiled.as_text()


# llama3.2:1b widths over a small stack and a small vocabulary (the
# logits are the one temporary that would outgrow a layer's pool here,
# and they are not what this test is about).
LOOP_CFG = ModelConfig(
    name="chip-compile-1b-widths", vocab_size=2048, hidden_size=2048,
    intermediate_size=8192, num_layers=LAYERS, num_heads=H, num_kv_heads=HK,
    head_dim=HD, max_seq_len=MP * PS, rope_theta=5e5, rms_norm_eps=1e-5,
    tie_embeddings=True)


def _lower_step_program(v5e, which, monkeypatch, cfg=LOOP_CFG):
    """One of the pipelined loop's two programs as the engine jits it,
    lowered for one described chip: (lowered, the packed input's words,
    the bytes of the state it carries)."""
    from types import SimpleNamespace

    from ollamamq_tpu.config import ATTENTION
    from ollamamq_tpu.engine import engine as eng_mod
    from ollamamq_tpu.engine.engine import ModelRuntime

    one = SingleDeviceSharding(v5e.devices[0])

    def s(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    # The jit itself, not the first-call wrapper that times the compile.
    monkeypatch.setattr(eng_mod, "_sp_note_compile",
                        lambda rt, site, key, cache, fn: cache.setdefault(
                            key, fn))
    S, W = B, 64
    rt = object.__new__(ModelRuntime)
    rt.cfg, rt.attn_impl, rt.mesh = cfg, "pallas", None
    rt.ecfg = SimpleNamespace(page_size=PS, max_slots=S,
                              max_pages_per_seq=MP, repeat_last_n=W)
    rt._prefill_jits, rt._decode_jits = {}, {}
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(lambda a: s(a.shape, a.dtype), shapes)
    # K and V rows — or a latent-attention model's latent rows and index
    # keys: two pools of different widths (ModelConfig.kv_row_dims).
    pool, pool2 = (s((cfg.cache_layers, NP * PS, lanes), jnp.bfloat16)
                   for lanes in cfg.kv_row_dims)
    recent, last_ids = s((S + 1, W)), s((S,))
    # The per-slot state: None (no leaf) for a model without such layers,
    # the conv window's array, or a SlotState with the rule's state too.
    # ...or a WindowState with the window layers' K/V rings.
    conv = jax.tree_util.tree_map(
        lambda a: s(a.shape, a.dtype),
        jax.eval_shape(lambda: llama.alloc_slot_state(
            cfg, S, ring_rows=cfg.ring_rows(T, PS))))
    drafts = ()
    if which == "mq_spec_step":  # the ragged step of a --spec runtime whose
        rt.mtp = True  # proposer is the model's prediction module
        drafts = (s((S + 1,)),) * 2  # its drafts and its rows' lengths
        fn = rt._get_ragged_jit(T, 1, (True, True, True))
        words = rt._ragged_layout(T).size
    elif which == "mq_ragged_step":
        fn = rt._get_ragged_jit(T, 0, (True, True, True))
        words = rt._ragged_layout(T).size
    else:
        fn = rt._get_decode_jit(8, (True, True, True))
        words = rt._decode_layout().size
    carried = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(
        (pool, pool2, recent, last_ids, conv, drafts)))
    # The step's host inputs are ONE packed int32 array (step_pack).
    return fn.lower(params, s((words,)), pool, pool2, recent, last_ids,
                    conv, *drafts), words, carried


_STEP_PROGRAMS = {}


def step_program(v5e, which, monkeypatch, cfg=LOOP_CFG):
    """`_lower_step_program`, compiled ONCE a (configuration, program) for
    the cases that read it: (lowered, compiled, words, bytes carried)."""
    if (cfg, which) not in _STEP_PROGRAMS:
        lowered, words, carried = _lower_step_program(v5e, which,
                                                      monkeypatch, cfg)
        _STEP_PROGRAMS[cfg, which] = (lowered, lowered.compile(), words,
                                      carried)
    return _STEP_PROGRAMS[cfg, which]


def _step_hlo_copies(capsys, name, *flags):
    """`scripts/step_hlo_copies.py` on `benchmarks/configs/<name>.json`, in
    this process and within its own time limit: the programs' lines and the
    stacks whose shape a listed weight copy has."""
    import contextlib
    import json
    import signal
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import step_hlo_copies

    @contextlib.contextmanager
    def time_limit(seconds):
        def stop(signum, frame):
            raise TimeoutError(f"no result within {seconds} s")
        was = signal.signal(signal.SIGALRM, stop)
        signal.alarm(seconds)
        try:
            yield
        finally:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, was)

    config = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                          "configs", name + ".json")
    with time_limit(240):
        assert step_hlo_copies.main([config, *flags]) == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    programs, last = lines[:-1], lines[-1]
    assert last["programs"] == len(programs)
    re_laid = {name for p in programs for c in p["weight_copies"]
               for name in c["stacks"]}
    return programs, re_laid


def _file_model(name):
    """(`benchmarks/configs/<name>.json` as a dict, its ModelConfig at the
    published widths); `benchmarks` is on the path once `_step_hlo_copies`
    has imported the script."""
    import json

    from benchmarks import serve

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", name + ".json")) as f:
        cfg = json.load(f)
    return cfg, serve.model_config(cfg, False)
