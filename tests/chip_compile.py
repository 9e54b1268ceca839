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


def _script():
    """`scripts/step_hlo_copies.py`, imported (it puts the repo's root, and
    so `benchmarks`, on the path)."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import step_hlo_copies

    return step_hlo_copies


def _lower_step_program(v5e, which, cfg=LOOP_CFG, fresh=False):
    """One of the pipelined loop's two programs as the engine jits it
    (`engine/step_program.py`'s builders; `fresh`: a jit object of its own,
    traced anew), lowered for one described chip: (lowered, the packed
    input's words, the bytes of the state it carries)."""
    from ollamamq_tpu.engine import step_program as built_by

    dims = built_by.StepDims(PS, B, MP, 64)
    # `mq_spec_step`: the ragged step of a --spec runtime whose proposer is
    # the model's prediction module, with its drafts and its rows' lengths.
    mtp = which == "mq_spec_step"
    args = _script().step_args(cfg, dims, v5e.devices, num_pages=NP,
                               ring_tokens=T, mtp=mtp, default_layouts=True)
    every, built = (True, True, True), dict(attn_impl="pallas", mesh=None,
                                            fresh=fresh)
    if which == "mq_decode_scan":
        fn = built_by.decode_scan(cfg, dims, 8, every, **built)
        words = dims.decode_layout().size
    else:
        fn = built_by.ragged_step(cfg, dims, T, int(mtp), every, mtp=mtp,
                                  **built)
        words = dims.ragged_layout(T).size
    # The step's host inputs are ONE packed int32 array (step_pack).
    return args.lower(fn, words), words, args.carried_bytes


_STEP_PROGRAMS = {}


def step_program(v5e, which, cfg=LOOP_CFG):
    """`_lower_step_program`, compiled ONCE a (configuration, program) for
    the cases that read it: (lowered, compiled, words, bytes carried)."""
    if (cfg, which) not in _STEP_PROGRAMS:
        lowered, words, carried = _lower_step_program(v5e, which, cfg)
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

    step_hlo_copies = _script()

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
