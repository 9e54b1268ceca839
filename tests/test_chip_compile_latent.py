"""Ask the chip's compiler, without the chip (test_chip_compile.py), the
latent-attention models: DeepSeek-V3.2's and openPangu's step programs and
kernels at their widths, and their configuration files.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chip_compile import B, MP, NP, PS, T, _step_hlo_copies, step_program
from ollamamq_tpu.config import ModelConfig
from ollamamq_tpu.models import llama


# DeepSeek-V3.2's layers (config.py) over its dense layer and two expert
# layers, 16 of the router's 256 experts held, a small vocabulary: the three
# kernels of ops/pallas/mla_attention.py at 128 heads over a 640-lane latent
# pool and a 128-lane index-key pool.
DEEPSEEK_CFG = ModelConfig(
    name="chip-compile-deepseek-v32-widths", vocab_size=2048,
    hidden_size=7168, intermediate_size=18432, num_layers=3, num_heads=128,
    num_kv_heads=128, head_dim=192, max_seq_len=MP * PS, rope_theta=10000.0,
    rms_norm_eps=1e-6, q_lora_rank=1536, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    index_n_heads=64, index_head_dim=128, index_topk=2048,
    rope_scaling={"type": "yarn", "factor": 40, "beta_fast": 32,
                  "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096},
    num_experts=16, router_experts=256, num_experts_per_tok=8, n_group=8,
    topk_group=4, n_shared_experts=1, moe_intermediate_size=2048,
    first_k_dense_replace=1, router_score="sigmoid", use_expert_bias=True,
    norm_topk_prob=True, norm_topk_eps=1e-20, routed_scaling_factor=2.5)


@pytest.mark.parametrize("which", ["mq_ragged_step", "mq_decode_scan"])
def test_deepseek_width_step_programs_carry_both_pools_in_place(
        v5e, which):
    """Latent attention with the indexer's selection (PR 39), at
    DeepSeek-V3.2's widths: the indexer's, the selection's and the sparse
    attention's kernels compile for the chip, one launch each a traced layer
    body (the dense layer's and the expert layers'), exactly one of them
    named `...paged_attention...` a body; the latent pool [3, S, 640] and
    the index-key pool [3, S, 128] — two arrays of different widths under
    one page table — the ring and the id carry all come back aliased (no
    second copy of either pool); and the temporaries hold no [tokens,
    context] float32 score a HEAD: the one [T, C] score a token is 2 MB here
    (64 x 8192 x 4 B), all 128 heads' would be 268 MB, the bound is a quarter
    of that above what the program holds without the indexer."""
    _, compiled, _, carried = step_program(v5e, which, DEEPSEEK_CFG)
    text = compiled.as_text()
    for name, n in (("mla_sparse_paged_attention_pallas", 2),
                    ("dsa_index_pallas", 2), ("dsa_select_pallas", 2)):
        assert len(re.findall(r'kernel_name = "%s"' % name, text)) == n \
            or text.count(name) >= n, name
    assert len(re.findall(r"%gmm[.\d]* = ", text)) == 3
    pools = 3 * NP * PS * (640 + 128) * 2
    assert carried >= pools
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= carried, (mem, carried)
    tokens = T if which == "mq_ragged_step" else B
    per_head = tokens * MP * PS * 4
    assert mem.temp_size_in_bytes < 128 * per_head // 4 + 512 * 2 ** 20, mem


@pytest.mark.parametrize("tokens", [256, 512])
def test_the_sparse_latent_kernel_compiles_with_its_expanded_body(v5e,
                                                                  tokens):
    """The masked latent attention kernel at DeepSeek-V3.2's widths on the
    512-token rung (PR 49): its absorbed tiles AND its expanded programs —
    16 heads' keys and values of a 256-token block expanded in VMEM, the
    512 stream tokens a program's rows — are one Mosaic kernel the chip's
    compiler takes, under the file's VMEM limit, over the cell's own pool
    and table (12,384 pages of 32, 520 a sequence); a rung under WIDE holds
    the tiles alone and gives the one result."""
    from ollamamq_tpu.ops.pallas import mla_attention as ka

    one = SingleDeviceSharding(v5e.devices[0])

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    heads, lanes, rank, rows, pages = 128, 640, 512, 17, 520
    C = ka.context_lanes(pages, PS)
    lowered = jax.jit(
        lambda q, sc, thr, pool, pt, qs, ql, kl, qe, w:
        ka.mla_sparse_paged_attention_pallas(
            q, sc, thr, pool, 2, pt, qs, ql, kl, PS, rank,
            expanded=(qe, w))).lower(
        s((tokens, heads, lanes), bf), s((tokens, C), f32),
        s((tokens,), f32), s((5, 12384 * PS, lanes), bf),
        s((rows, pages), i32), s((rows,), i32), s((rows,), i32),
        s((rows,), i32), s((tokens, heads, 256), bf),
        s((heads, 256, rank), bf))
    compiled = lowered.compile()
    out = jax.tree.leaves(compiled.out_info)
    expands = ka.expands(tokens, heads, lanes, rank, 128, 128)
    assert expands == (tokens >= ka.WIDE) and len(out) == (3 if expands
                                                           else 1)
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          compiled.as_text())) == 1


@pytest.mark.parametrize("heads,pages,per_seq", [(32, 12672, 536),
                                                 (128, 4656, 104)],
                         ids=["kimi_linear", "openpangu"])
def test_the_dense_latent_kernel_compiles_with_its_expanded_body(
        v5e, heads, pages, per_seq):
    """The dense latent attention kernel on the 512-token rung with the
    expanded programs (PR 64) — the masked kernel's body with the
    selection's operands, scratch and comparison compiled out — at
    Kimi-Linear's 32 heads (two programs of 16) and at openPangu's 128
    (eight), each over its cell's own pool and table: one Mosaic kernel the
    chip's compiler takes under the file's VMEM limit, three results; with a
    `name` (the prediction module's launch) the tiles alone, one result."""
    from ollamamq_tpu.ops.pallas import mla_attention as ka

    one = SingleDeviceSharding(v5e.devices[0])

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    bf, i32 = jnp.bfloat16, jnp.int32
    tokens, lanes, rank, rows = 512, 640, 512, 17
    assert ka.expands(tokens, heads, lanes, rank, 128, 128)
    for name, results in ((None, 3), (ka.MTP_NAME, 1)):
        compiled = jax.jit(
            lambda q, pool, pt, qs, ql, kl, qe, w:
            ka.mla_dense_paged_attention_pallas(
                q, pool, 1, pt, qs, ql, kl, PS, rank, name=name,
                expanded=(qe, w))).lower(
            s((tokens, heads, lanes), bf), s((2, pages * PS, lanes), bf),
            s((rows, per_seq), i32), s((rows,), i32), s((rows,), i32),
            s((rows,), i32), s((tokens, heads, 256), bf),
            s((heads, 256, rank), bf)).compile()
        assert len(jax.tree.leaves(compiled.out_info)) == results
        assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                              compiled.as_text())) == 1


# openPangu-Ultra-MoE's layers (config.py) over its dense layer and two
# expert layers, 16 of the router's 256 experts held, a small vocabulary, and
# the prediction module: the dense latent attention kernel at 128 heads over a
# 640-lane latent pool of 3 + 1 layers, no second pool.
OPENPANGU_CFG = ModelConfig(
    name="chip-compile-openpangu-widths", vocab_size=2048,
    hidden_size=7680, intermediate_size=18432, num_layers=3, num_heads=128,
    num_kv_heads=128, head_dim=192, max_seq_len=MP * PS,
    rope_theta=25_600_000.0, rms_norm_eps=1e-5, q_lora_rank=1536,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, sandwich_norm=True, num_experts=16, router_experts=256,
    num_experts_per_tok=8, n_shared_experts=1, moe_intermediate_size=2048,
    first_k_dense_replace=1, router_score="sigmoid", norm_topk_prob=True,
    norm_topk_eps=1e-20, routed_scaling_factor=2.5,
    num_nextn_predict_layers=1)


def test_openpangu_width_spec_step_carries_the_pool_and_the_drafts_in_place(
        v5e):
    """The `--spec` runtime's ragged step with the prediction module (PR 42),
    at openPangu-Ultra-MoE's widths: the dense latent attention kernel — the
    attention kernel with the selection's operands compiled out — compiles
    for the chip, one launch a traced layer body under the name
    `_ops.ATTENTION` counts and ONE more for the module's block under its
    own; the module's expert layer launches the grouped matmul a third time;
    the latent pool [4, S, 640] (the module's rows its last layer), the
    second pool of NO lanes, the ring, the id carry, the draft carry and the
    length carry (PR 44) all come back aliased."""
    from ollamamq_tpu.ops.pallas.mla_attention import MTP_NAME

    _, compiled, _, carried = step_program(
        v5e, "mq_spec_step", OPENPANGU_CFG)
    text = compiled.as_text()
    names = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target="
                       r"\"tpu_custom_call\"", text)
    trunk = [n for n in names if n.startswith(
        "mla_dense_paged_attention_pallas")]
    module = [n for n in names if n.startswith(MTP_NAME)]
    assert (len(trunk), len(module)) == (2, 1), names
    assert "paged_attention" not in MTP_NAME
    assert not any("mla_sparse" in n or "dsa_" in n for n in names)
    assert sum(n.startswith("gmm") for n in names) == 3 * 2  # layers, module
    pool = 4 * NP * PS * 640 * 2
    assert carried >= pool + (B + 1) * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= carried, (mem, carried)
    # no [tokens, context] score a head leaves the kernel
    assert mem.temp_size_in_bytes < 128 * T * MP * PS * 4 // 4 \
        + 512 * 2 ** 20, mem


@pytest.mark.parametrize("held", [False, True],
                         ids=["row_major", "as_served"])
def test_no_step_program_re_lays_a_latent_stack(v5e, capsys, held):
    """`scripts/step_hlo_copies.py` on openPangu's configuration file at its
    `rehearse` sizes (PR 45): with every weight row-major the chip's compiler
    puts a `copy` of a layer of `mla_wuq` and of `mla_wukv` into the `--spec`
    step (the re-layout that was 2.0 ms of a 15 ms pass at the published
    widths); with the two stacks in the formats `llama.weight_formats` names
    — as a runtime holds them — it puts none. (At these sizes the toy expert
    stacks' 64 lanes get a copy of their own into the grouped matmul: the
    check is of the stacks the rule names.) Within its own time limit: two
    compiles of some ten seconds."""
    programs, re_laid = _step_hlo_copies(
        capsys, "openpangu-ultra-moe-ep16-d5", "--rehearse", "--min-mb", "0",
        *(() if held else ("--default-layouts",)))
    assert [p["program"] for p in programs] == ["mq_ragged_step"]
    latent = set(llama.CONTRACTED_MINOR)
    assert (re_laid & latent == set()) if held else (latent <= re_laid), \
        programs[0]["weight_copies"]


def _file_ragged_step(v5e, name, tokens=None, rehearse=False):
    """(`scripts/step_hlo_copies.py` as a module, the compiled text of the
    ragged step of `benchmarks/configs/<name>.json` at a stream of `tokens`
    — its `--max-batch-tokens` by default — with the weights in the formats
    a runtime holds them in)."""
    import json
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import step_hlo_copies

    from benchmarks import serve
    from ollamamq_tpu import cli

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", name + ".json")) as f:
        cfg = json.load(f)
    flags = cli.build_parser().parse_args(
        ["--models", cfg["name"]] + serve.server_flags(cfg, rehearse))
    lowered, _ = step_hlo_copies.step_programs(
        serve.model_config(cfg, rehearse), flags, v5e,
        tokens or flags.max_batch_tokens)
    return step_hlo_copies, lowered["mq_ragged_step"].compile().as_text()


def _device_ops(script, hlo):
    """[(computation, opcode, line)] of a compiled module's text, the
    insides of fusions left out (`step_hlo_copies.moves` has the rule)."""
    fused = {c for line in hlo.splitlines() if " fusion(" in line
             for c in script._CALLS.findall(line)}
    out, at = [], None
    for line in hlo.splitlines():
        head = script._COMPUTATION.match(line)
        if head:
            at = head["name"]
        elif at not in fused and (m := script._ANY_INSTR.match(line)):
            out.append((at, m.group(1), line))
    return out


@pytest.mark.parametrize("name,heads,min_mb", [
    ("deepseek-v3.2-ep16-d5", 128, 32),
    ("openpangu-ultra-moe-ep16-d5", 128, 32),
    ("kimi-linear-48b-a3b-ep4-d8", 32, 16)])
def test_the_wide_rung_computes_the_absorbed_form_of_the_rung_in_a_branch(
        v5e, name, heads, min_mb):
    """A latent model's configuration file, the 512-token ragged step as
    served (PR 55: DeepSeek-V3.2's; since PR 64 every latent model's launch
    on this rung holds the expanded body — openPangu's with its prediction
    module behind the trunk, Kimi-Linear's two latent layers among its six
    KDA layers, a full-rank q): the absorbed q of the RUNG — the contraction
    `bthn,chn->bthc` over 512 rows, its `bf16[512,heads,640]` result and
    that result's re-layout for the kernel's tiles (84 MB at 128 heads, 21
    at 32) — is computed inside the branch a conditional takes on a step
    with a narrow span behind the lead; on the other branch (`few`: a
    prompt's chunk behind a few decode rows) nothing of `min_mb` is copied,
    and the contraction outside any branch runs over the 32 rows of the
    lead. W_uv's contraction `bthc,chv->bthv` runs over 32 rows a trip,
    inside a loop's body, and nowhere over the rung. The prediction module's
    block (`mtp_block`: its launch has a name and expands nothing) is the
    rung's absorbed form outside any branch, as it was."""
    script, hlo = _file_ragged_step(v5e, name, 512)
    conds = script.branches(hlo)
    assert conds, conds  # one a traced layer body
    full = {b[0] for b in conds.values()}  # lax.cond's false branch
    few = {b[1] for b in conds.values()}
    moved = script.moves(hlo, min_mb * 2 ** 20)
    q_abs = [m for m in moved if m["dims"] == [512, heads, 640]]
    assert q_abs and all(m["of"] in full for m in q_abs), q_abs
    assert not [m for m in moved if m["of"] in few], moved
    seen = {}  # rows of the contraction -> the computations it is an op of
    for at, op, line in _device_ops(script, hlo):
        if "/mtp_block/" in line:
            continue
        if "bthn,chn->bthc/dot_general" in line and op == "fusion":
            dims = script._INSTR.match(line)["dims"].split(",")
            n = 512 if "512" in dims[:2] else 32 if "32" in dims[:2] \
                else None  # [512, heads, .] or [1, 32, heads, .]
            seen.setdefault(n, set()).add(at)
        if "bthc,chv->bthv/dot_general" in line and op == "fusion":
            assert "/attn_out/while/body/" in line, line  # a tile a trip
    assert set(seen) == {512, 32}, seen
    assert seen[512] <= full and not seen[32] & full, seen


# Instructions of openPangu's ragged `--spec` step at the file's `rehearse`
# sizes, the insides of fusions left out, as the tree BEFORE PR 55 compiled
# it: its layers run `_latent_attention_op` too, and at those sizes (head
# widths of no whole lane tile, a rung of 64) no launch holds the expanded
# body: PR 55 and PR 64 mean to leave them what they were. Take the
# number again (`len(_device_ops(...))`) only with a change that means to
# move that program.
OPENPANGU_REHEARSE_OPS = 1350


def test_a_latent_layer_with_no_expanded_body_is_the_program_it_was(v5e):
    """...and holds no conditional: where nothing is expanded a layer has
    nothing to choose (a rung under WIDE by its trace:
    `tests/test_deepseek_v32_wide.py`, `tests/test_latent_dense_wide.py`)."""
    script, hlo = _file_ragged_step(v5e, "openpangu-ultra-moe-ep16-d5",
                                    rehearse=True)
    assert script.branches(hlo) == {}
    assert len(_device_ops(script, hlo)) == OPENPANGU_REHEARSE_OPS
