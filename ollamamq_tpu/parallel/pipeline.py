"""Pipeline parallelism: layers sharded over the mesh "pipe" axis.

The reference scales by adding whole HTTP backends (one full model copy
each — /root/reference/src/dispatcher.rs:434-482); it has no way to serve
a model LARGER than one backend's memory. Pipeline parallelism is that
missing axis: the stacked layer parameters [L, ...] (already the repo's
scan-over-layers layout, models/llama.py) shard their leading L dim over
the "pipe" mesh axis, so each chip group holds only L/P layers' weights
and L/P layers' KV pages — the per-chip HBM footprint drops by P.

TPU-native schedule (not a translation of GPU send/recv pipelines):
  - One `jax.shard_map` over the whole mesh; each pipe stage runs the
    SAME traced program (SPMD), scanning its local layer stack.
  - GPipe-style microbatching: the batch splits into M microbatches; at
    schedule step t, stage p works on microbatch (t - p). Activations
    hand off between stages via a single `lax.ppermute` per step — XLA
    lowers it to an ICI neighbor copy that overlaps the next stage's
    compute. M + P - 1 steps drain the pipeline.
  - Bubble steps (t - p outside [0, M)) compute on garbage and write
    their K/V to the allocator's trash page (slot 0 — engine/kv_cache.py
    TRASH_PAGE), keeping every step fully static-shaped: no cond, no
    dynamic shapes, one compiled program.
  - Composes with tensor parallelism INSIDE each stage: head/FFN dims
    stay sharded over "tensor" and the row-parallel matmuls (wo, w_down)
    reduce via `lax.psum` — identity when tp == 1, Megatron-style TP
    when tp > 1 (works with replicated-group KV too, since the shards'
    local shapes carry the already-rewritten head counts). Embedding and
    lm_head stay vocab-sharded over "tensor" via masked local lookup +
    psum.

All three serving forwards share one stage body (`_tp_layer`) and one
schedule loop (`_pipeline_schedule`); they differ only in the attention
call and the per-microbatch operands. Numerics match the single-device
forwards exactly (same per-layer math, same f32 softmax); only the
schedule is distributed — pinned by tests/test_pipeline.py against
forward_prefill / forward_prefill_chunk / forward_decode.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ollamamq_tpu.config import ModelConfig
from ollamamq_tpu.models.llama import rmsnorm, scan_layers
from ollamamq_tpu.ops.attention import (
    causal_attention,
    flat_slot_indices,
    paged_chunk_attention_blockwise,
    paged_decode_attention_any,
)
from ollamamq_tpu.ops.quant import kv_write
from ollamamq_tpu.ops.rope import apply_rope
from ollamamq_tpu.parallel.mesh import AXIS_PIPE, AXIS_TENSOR
from ollamamq_tpu.parallel.sharding import (kv_cache_spec,
                                            pipeline_param_specs)

KV_SPEC = kv_cache_spec(pp=True)  # [L, S, Hk*hd]: layers by stage, lanes by kv head


def n_microbatches(batch: int, pipe: int, requested: Optional[int] = None) -> int:
    """Microbatch count: the largest divisor of `batch` that is <= the
    requested count (default: the stage count, which keeps every stage
    busy in steady state with the fewest handoffs)."""
    m = min(requested or pipe, batch)
    while batch % m:
        m -= 1
    return max(m, 1)


# ---------------------------------------------------------------------------
# Per-stage layer math (tensor-parallel inside the stage).
#
# Mirrors models/llama.py's layer bodies, except the head / FFN dims are
# tensor-LOCAL shards and the row-parallel outputs (wo, w_down) reduce
# with an explicit psum — under shard_map the collective XLA would
# otherwise infer from shardings must be written out.
# ---------------------------------------------------------------------------


def _tp_qkv(cfg: ModelConfig, lp: dict, h: jnp.ndarray):
    B, T, _ = h.shape
    hd = cfg.head_dim
    q = jnp.einsum("btd,de->bte", h, lp["wq"])
    k = jnp.einsum("btd,de->bte", h, lp["wk"])
    v = jnp.einsum("btd,de->bte", h, lp["wv"])
    if "bq" in lp:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = q.reshape(B, T, q.shape[-1] // hd, hd)
    k = k.reshape(B, T, k.shape[-1] // hd, hd)
    v = v.reshape(B, T, v.shape[-1] // hd, hd)
    if cfg.qk_norm_kind == "head":  # "full" is refused at start-up
        q = rmsnorm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rmsnorm(k, lp["k_norm"], cfg.rms_norm_eps)
    return q, k, v


def _tp_mlp(lp: dict, h: jnp.ndarray) -> jnp.ndarray:
    gate = jnp.einsum("btd,df->btf", h, lp["w_gate"])
    up = jnp.einsum("btd,df->btf", h, lp["w_up"])
    down = jnp.einsum("btf,fd->btd", jax.nn.silu(gate) * up, lp["w_down"])
    return lax.psum(down, AXIS_TENSOR)


def _tp_layer(cfg, lp, x, positions, l, kc, vc, attn_and_cache):
    """One transformer layer on this stage — the SINGLE definition of the
    stage layer math (prefill, chunk, and decode inject only the
    attention/KV-write schedule via `attn_and_cache`).

    x: [mb, T, D]; kc/vc: this stage's WHOLE local pool
    [L_loc, S, Hk_loc*hd], carried through the layer loop; l: the local
    layer index. attn_and_cache(q, k, v, l, kc, vc) -> (attn [mb, T,
    H_loc, hd], kc, vc) writes the new K/V into pool[l] wherever its
    schedule wants them, then attends.
    """
    B, T, _ = x.shape
    h = rmsnorm(x, lp["attn_norm"], cfg.rms_norm_eps)
    q, k, v = _tp_qkv(cfg, lp, h)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    attn, kc, vc = attn_and_cache(q, k, v, l, kc, vc)
    delta = jnp.einsum("bte,ed->btd", attn.reshape(B, T, -1), lp["wo"])
    x = x + lax.psum(delta, AXIS_TENSOR)
    h2 = rmsnorm(x, lp["mlp_norm"], cfg.rms_norm_eps)
    return x + _tp_mlp(lp, h2), kc, vc


def _stage(cfg, layers, x, positions, kc, vc, attn_and_cache):
    """Run this stage's local layer stack over one microbatch; the local
    pool is the loop's carry (models/llama.py:scan_layers)."""

    def body(x, lp, l, kc, vc):
        return *_tp_layer(cfg, lp, x, positions, l, kc, vc,
                          attn_and_cache), None

    return scan_layers(body, x, layers, kc, vc)[:3]


# ---------------------------------------------------------------------------
# Vocab-sharded embedding / logits under shard_map.
# ---------------------------------------------------------------------------


def _embed_lookup(embed_local: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """Gather from a vocab-sharded embedding: each tensor shard looks up
    the ids it owns, everything else contributes zero, psum combines."""
    ti = lax.axis_index(AXIS_TENSOR)
    v_loc = embed_local.shape[0]
    loc = tokens - ti * v_loc
    ok = (loc >= 0) & (loc < v_loc)
    x = embed_local[jnp.clip(loc, 0, v_loc - 1)]
    x = jnp.where(ok[..., None], x, jnp.zeros((), embed_local.dtype))
    return lax.psum(x, AXIS_TENSOR)


def _final_logits(params: dict, cfg: ModelConfig, x_last: jnp.ndarray) -> jnp.ndarray:
    """x_last: [B, D] last-position hiddens (zero on every stage but the
    last). Returns replicated [B, V]: psum over pipe folds the stages
    (zeros elsewhere), all_gather over tensor stitches the vocab shards."""
    xf = rmsnorm(x_last, params["final_norm"], cfg.rms_norm_eps)
    head = params.get("lm_head", params["embed"])
    logits = jnp.einsum(
        "bd,vd->bv", xf.astype(jnp.float32), head.astype(jnp.float32)
    )
    logits = lax.psum(logits, AXIS_PIPE)
    return lax.all_gather(logits, AXIS_TENSOR, axis=1, tiled=True)


# ---------------------------------------------------------------------------
# The GPipe schedule, shared by all three forwards.
# ---------------------------------------------------------------------------


def _pipeline_schedule(pipe, M, x_all, kc, vc, run_stage):
    """Drive M microbatches through `pipe` stages (M + pipe - 1 steps).

    x_all: [M, mb, T, D] stage-0 inputs (embedded microbatches).
    run_stage(m, valid, inp, kc, vc) -> (h_out [mb, T, D], kc, vc,
    x_last [mb, D]) runs THIS stage's layers on microbatch m (`valid`
    False on bubble steps — the callback must redirect its KV writes to
    the trash page then). Returns (out_x [M, mb, D] last-stage results,
    kc, vc).
    """
    p = lax.axis_index(AXIS_PIPE)
    M_, mb = x_all.shape[0], x_all.shape[1]
    out_x = jnp.zeros((M_, mb, x_all.shape[-1]), x_all.dtype)
    h0 = jnp.zeros(x_all.shape[1:], x_all.dtype)

    def step(t, carry):
        h_state, kc, vc, out_x = carry
        m = jnp.clip(t - p, 0, M - 1)
        valid = (t >= p) & (t - p < M)
        inp = jnp.where(
            p == 0,
            lax.dynamic_index_in_dim(x_all, m, 0, keepdims=False),
            h_state,
        )
        h_out, kc, vc, x_last = run_stage(m, valid, inp, kc, vc)
        prev = lax.dynamic_index_in_dim(out_x, m, 0, keepdims=False)
        row = jnp.where(valid & (p == pipe - 1), x_last, prev)
        out_x = lax.dynamic_update_index_in_dim(out_x, row, m, 0)
        perm = [(d, (d + 1) % pipe) for d in range(pipe)]
        h_nxt = lax.ppermute(h_out, AXIS_PIPE, perm)
        return h_nxt, kc, vc, out_x

    _, kc, vc, out_x = lax.fori_loop(0, M + pipe - 1, step, (h0, kc, vc, out_x))
    return out_x, kc, vc


def _pick(stack, m):
    return lax.dynamic_index_in_dim(stack, m, 0, keepdims=False)


def _last_valid(h_out, lens):
    """[mb, T, D] -> [mb, D] at each row's last valid position."""
    last = jnp.clip(lens - 1, 0, h_out.shape[1] - 1)
    return jnp.take_along_axis(h_out, last[:, None, None], axis=1)[:, 0]


# ---------------------------------------------------------------------------
# Pipelined forwards (drop-in signatures vs the llama.py single-mesh ones).
# ---------------------------------------------------------------------------


def pp_forward_prefill(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, T] right-padded
    seq_lens: jnp.ndarray,  # [B]
    k_cache: jnp.ndarray,  # [L, S, Hk*hd], L sharded over "pipe"
    v_cache: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, max_pages]
    page_size: int,
    mesh: Mesh,
    n_micro: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pipelined prefill; returns (last_logits [B, V], k_cache', v_cache').
    Exact vs forward_prefill — schedule-only difference."""
    B, T = tokens.shape
    pipe = mesh.shape[AXIS_PIPE]
    M = n_microbatches(B, pipe, n_micro)
    mb = B // M

    def body(params, tokens, seq_lens, kc, vc, pt):
        x_all = _embed_lookup(params["embed"], tokens).reshape(M, mb, T, -1)
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (mb, T))
        pos_b = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        slots_all = flat_slot_indices(pt, pos_b, page_size).reshape(M, mb, T)
        lens_all = seq_lens.reshape(M, mb)

        def run_stage(m, valid, inp, kc, vc):
            lens = _pick(lens_all, m)
            slots = jnp.where(valid, _pick(slots_all, m), 0)  # bubbles->trash

            def attn_and_cache(q, k, v, l, kc, vc):
                kc = kv_write(kc, l, slots, k)
                vc = kv_write(vc, l, slots, v)
                return causal_attention(q, k, v, lens), kc, vc

            h_out, kc, vc = _stage(cfg, params["layers"], inp, positions,
                                   kc, vc, attn_and_cache)
            return h_out, kc, vc, _last_valid(h_out, lens)

        out_x, kc, vc = _pipeline_schedule(pipe, M, x_all, kc, vc, run_stage)
        return _final_logits(params, cfg, out_x.reshape(B, -1)), kc, vc

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(pipeline_param_specs(params), P(), P(), KV_SPEC, KV_SPEC, P()),
        out_specs=(P(), KV_SPEC, KV_SPEC),
        check_vma=False,
    )(params, tokens, seq_lens, k_cache, v_cache, page_table)


def pp_forward_prefill_chunk(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, C] one chunk of the prompt, right-padded
    start: jnp.ndarray,  # [B] global position of the chunk's first token
    chunk_lens: jnp.ndarray,  # [B] valid tokens in this chunk
    k_cache: jnp.ndarray,  # [L, S, Hk*hd], L sharded over "pipe"
    v_cache: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, max_pages] — covers prefix AND chunk
    page_size: int,
    mesh: Mesh,
    n_micro: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pipelined chunked prefill (long prompts beyond the largest bucket);
    chaining chunks reproduces pp_forward_prefill exactly. Returns
    (last-valid-position logits [B, V], caches')."""
    B, C = tokens.shape
    pipe = mesh.shape[AXIS_PIPE]
    M = n_microbatches(B, pipe, n_micro)
    mb = B // M

    def body(params, tokens, start, chunk_lens, kc, vc, pt):
        x_all = _embed_lookup(params["embed"], tokens).reshape(M, mb, C, -1)
        pos_b = start[:, None] + jnp.broadcast_to(
            jnp.arange(C, dtype=jnp.int32), (B, C)
        )
        slots_all = flat_slot_indices(pt, pos_b, page_size).reshape(M, mb, C)
        pos_all = pos_b.reshape(M, mb, C)
        start_all = start.reshape(M, mb)
        clen_all = chunk_lens.reshape(M, mb)
        pt_all = pt.reshape(M, mb, -1)

        def run_stage(m, valid, inp, kc, vc):
            st, cl = _pick(start_all, m), _pick(clen_all, m)
            ptm = _pick(pt_all, m)
            slots = jnp.where(valid, _pick(slots_all, m), 0)  # bubbles->trash

            def attn_and_cache(q, k, v, l, kc, vc):
                kc = kv_write(kc, l, slots, k)
                vc = kv_write(vc, l, slots, v)
                # Blockwise online-softmax walk over the already-written
                # prefix + this chunk (mirrors forward_prefill_chunk).
                attn = paged_chunk_attention_blockwise(
                    q, kc, vc, l, ptm, st, cl, page_size
                )
                return attn, kc, vc

            h_out, kc, vc = _stage(cfg, params["layers"], inp,
                                   _pick(pos_all, m), kc, vc, attn_and_cache)
            return h_out, kc, vc, _last_valid(h_out, cl)

        out_x, kc, vc = _pipeline_schedule(pipe, M, x_all, kc, vc, run_stage)
        return _final_logits(params, cfg, out_x.reshape(B, -1)), kc, vc

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(pipeline_param_specs(params), P(), P(), P(), KV_SPEC,
                  KV_SPEC, P()),
        out_specs=(P(), KV_SPEC, KV_SPEC),
        check_vma=False,
    )(params, tokens, start, chunk_lens, k_cache, v_cache, page_table)


def pp_forward_decode(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B] last generated token per slot
    positions: jnp.ndarray,  # [B]
    k_cache: jnp.ndarray,  # [L, S, Hk*hd], L sharded over "pipe"
    v_cache: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, max_pages]
    page_size: int,
    mesh: Mesh,
    n_micro: Optional[int] = None,
    attn_impl: str = "jnp",  # "jnp" reference | "pallas" ragged TPU kernel
    interpret: bool = False,  # pallas interpret mode (CPU tests)
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pipelined single decode step; returns (logits [B, V], caches').

    The Pallas decode kernel runs per-device inside the shard_map stage
    (each stage's pallas_call sees its local pool and local layer index)."""
    B = tokens.shape[0]
    pipe = mesh.shape[AXIS_PIPE]
    M = n_microbatches(B, pipe, n_micro)
    mb = B // M

    def body(params, tokens, positions, kc, vc, pt):
        x_all = _embed_lookup(params["embed"], tokens).reshape(M, mb, 1, -1)
        ws_all = flat_slot_indices(pt, positions[:, None], page_size)[:, 0]
        ws_all = ws_all.reshape(M, mb)
        pos_all = positions.reshape(M, mb)
        pt_all = pt.reshape(M, mb, -1)

        def run_stage(m, valid, inp, kc, vc):
            pos = _pick(pos_all, m)
            ptm = _pick(pt_all, m)
            ws = jnp.where(valid, _pick(ws_all, m), 0)  # bubbles->trash

            def attn_and_cache(q, k, v, l, kc, vc):
                kc = kv_write(kc, l, ws, k[:, 0])
                vc = kv_write(vc, l, ws, v[:, 0])
                attn = paged_decode_attention_any(
                    attn_impl, q[:, 0], kc, vc, l, ptm, pos + 1, page_size,
                    interpret=interpret,
                )
                return attn[:, None], kc, vc  # [mb, 1, H_loc, hd]

            h_out, kc, vc = _stage(cfg, params["layers"], inp, pos[:, None],
                                   kc, vc, attn_and_cache)
            return h_out, kc, vc, h_out[:, 0]

        out_x, kc, vc = _pipeline_schedule(pipe, M, x_all, kc, vc, run_stage)
        return _final_logits(params, cfg, out_x.reshape(B, -1)), kc, vc

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(pipeline_param_specs(params), P(), P(), KV_SPEC, KV_SPEC, P()),
        out_specs=(P(), KV_SPEC, KV_SPEC),
        check_vma=False,
    )(params, tokens, positions, k_cache, v_cache, page_table)
