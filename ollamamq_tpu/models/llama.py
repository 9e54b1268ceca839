"""Llama/Qwen-family decoder in pure functional JAX.

Design notes (TPU-first):
  - Layer parameters are STACKED along a leading `num_layers` axis and the
    forward is a `lax.scan` over layers — one traced layer body, fast XLA
    compile. The KV pool ([L, S, Hk*hd], the layout the kernels DMA from)
    is the scan's CARRY, never its xs/ys (`scan_layers`): each layer
    scatters the step's rows into `pool[l]` in place and attention reads
    the whole pool by layer index, so a step moves the rows it writes and
    the pages it reads — not the pool.
  - Served: `forward_ragged` (one flattened stream of prefill spans and
    decode tokens over the paged pool), `forward_decode` (one token per
    slot: the fused scan's body), `forward_prefill_sp`, `forward_embed`
    and the encoder; all shape-static => one jit per padded shape.
    `forward_prefill` (whole prompts, dense causal attention) is the
    plain oracle that tests, the benchmark's reference and the dry run
    compare them with; the engine does not call it.
  - All matmuls run in the params dtype (bf16 on TPU => MXU), softmax and
    logits in f32.
  - Qwen2.5 support = `attn_bias=True` in ModelConfig; the same code path
    serves both families (capability parity with the reference's two
    stress-test models, /root/reference/test_dispatcher.sh:5-7). Qwen3 and
    OLMoE are `qk_norm` ("head" / "full"); Mixtral and OLMoE replace the
    FFN by routed experts (`num_experts`, models/moe.py).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ollamamq_tpu.config import ModelConfig
from ollamamq_tpu.models.moe import STACKED, init_moe_layer_params, moe_mlp
from ollamamq_tpu.ops.attention import (
    causal_attention,
    bidirectional_attention,
    flat_slot_indices,
    paged_decode_attention_any,
    ragged_attention_any,
)
from ollamamq_tpu.ops.quant import embed_lookup, kv_write, logits_head, qeinsum
from ollamamq_tpu.ops.rope import apply_rope

# Stage names on the device trace (jax.named_scope: op metadata only, the
# lowered programs compute the same thing). README's span table lists
# them; tests/test_trace_spans.py finds each in the lowered ragged and
# decode programs. An MoE model's "mlp" holds models/moe.py:SCOPES.
SCOPES = ("embed", "attn_qkv", "kv_write", "attention", "attn_out", "mlp",
          "lm_head", "sampling")


def _adtype(params: dict):
    """Activation dtype for a forward: norm weights are never quantized,
    so final_norm carries the compute dtype even when embed/matmul
    weights are int8 QuantTensors."""
    return params["final_norm"].dtype


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """Random-init a params pytree (layers stacked on axis 0)."""
    d, qd, kvd, f = cfg.hidden_size, cfg.q_dim, cfg.kv_dim, cfg.intermediate_size
    L, v = cfg.num_layers, cfg.vocab_size
    keys = jax.random.split(key, 10)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(dtype)

    layers = {
        "attn_norm": jnp.ones((L, d), dtype),
        "wq": w(keys[0], (L, d, qd), d),
        "wk": w(keys[1], (L, d, kvd), d),
        "wv": w(keys[2], (L, d, kvd), d),
        "wo": w(keys[3], (L, qd, d), qd),
        "mlp_norm": jnp.ones((L, d), dtype),
        "w_gate": w(keys[4], (L, d, f), d),
        "w_up": w(keys[5], (L, d, f), d),
        "w_down": w(keys[6], (L, f, d), f),
    }
    if cfg.attn_bias:
        layers["bq"] = jnp.zeros((L, qd), dtype)
        layers["bk"] = jnp.zeros((L, kvd), dtype)
        layers["bv"] = jnp.zeros((L, kvd), dtype)
    if cfg.qk_norm_kind == "head":
        # Qwen3: per-head RMSNorm on q/k (weight over head_dim).
        layers["q_norm"] = jnp.ones((L, cfg.head_dim), dtype)
        layers["k_norm"] = jnp.ones((L, cfg.head_dim), dtype)
    elif cfg.qk_norm_kind == "full":
        # OLMoE: RMSNorm over the whole projected q / k vector.
        layers["q_norm"] = jnp.ones((L, qd), dtype)
        layers["k_norm"] = jnp.ones((L, kvd), dtype)
    if cfg.num_experts:
        # MoE family: the dense FFN is replaced by routed experts.
        for dense in ("w_gate", "w_up", "w_down"):
            del layers[dense]
        layers.update(init_moe_layer_params(cfg, keys[9], dtype))
    params = {
        "embed": w(keys[7], (v, d), d),
        "final_norm": jnp.ones((d,), dtype),
        "layers": layers,
    }
    if not cfg.tie_embeddings and not cfg.is_encoder:
        params["lm_head"] = w(keys[8], (v, d), d)
    return params


def _qkv(cfg: ModelConfig, lp: dict, h: jnp.ndarray):
    """Project hidden -> q,k,v with head reshape. h: [B, T, D]."""
    B, T, _ = h.shape
    q = qeinsum("btd,de->bte", h, lp["wq"])
    k = qeinsum("btd,de->bte", h, lp["wk"])
    v = qeinsum("btd,de->bte", h, lp["wv"])
    if cfg.attn_bias:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    if cfg.qk_norm_kind == "full":
        # Over all heads' lanes at once, BEFORE the split into heads (under
        # tp the lanes are sharded: GSPMD reduces the mean across shards).
        q = rmsnorm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rmsnorm(k, lp["k_norm"], cfg.rms_norm_eps)
    q = q.reshape(B, T, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm_kind == "head":
        q = rmsnorm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rmsnorm(k, lp["k_norm"], cfg.rms_norm_eps)
    return q, k, v


def _mlp(lp: dict, h: jnp.ndarray) -> jnp.ndarray:
    gate = qeinsum("btd,df->btf", h, lp["w_gate"])
    up = qeinsum("btd,df->btf", h, lp["w_up"])
    return qeinsum("btf,fd->btd", jax.nn.silu(gate) * up, lp["w_down"])


def _ffn(cfg: ModelConfig, lp: dict, h: jnp.ndarray, valid=None,
         mesh=None, impl: str = "jnp", layer=None):
    """Dense SwiGLU or routed mixture-of-experts, by model family; returns
    (delta, expert load [E] int32 — None for a dense model).

    `valid` ([B, T] bool) marks real tokens, `mesh` is the caller's jit
    mesh and `impl` its `attn_impl`; only MoE routing consumes them
    (padding/inactive rows are routed to no expert; the grouped matmul
    runs under a shard_map, as a Pallas kernel or its XLA twin). `layer`:
    inside `scan_layers`, where `lp` holds the expert stacks whole.
    """
    if cfg.num_experts:
        return moe_mlp(cfg, lp, h, valid=valid, mesh=mesh, impl=impl,
                       layer=layer)
    return _mlp(lp, h), None


@jax.named_scope("lm_head")
def _logits(params: dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    head = params.get("lm_head", params["embed"])
    return logits_head(x, head)


def scan_layers(body, x, layers, k_cache, v_cache):
    """The ONE layer loop of every forward that touches the KV pool
    (prefill, ragged and decode).

    The pool is the loop's CARRY: `body(x, lp, l, k_cache, v_cache) ->
    (x, k_cache, v_cache, per_layer)` gets the layer index `l`, writes
    with one scatter on the carried pool (ops/quant.kv_write) and attends
    over `pool[l]` by index, and the loop returns the buffers it was given
    — with the jit sites' donation, XLA updates the pool in place. A pool
    passed as a scan's xs and returned as its ys cannot alias: every pass
    would build a second pool and copy each layer out and back.
    `per_layer` (small: an MoE layer's expert load, else None) comes back
    stacked [L, ...] as the fourth result. An MoE model's expert stacks
    (moe.STACKED) are not sliced by the scan either: `lp` holds them
    whole, for `_ffn(..., layer=l)` to read by index.
    """
    n_layers = k_cache.shape[0]
    whole = {k: w for k, w in layers.items() if k in STACKED}
    sliced = {k: w for k, w in layers.items() if k not in STACKED}

    def step(carry, per_layer):
        x, kc, vc = carry
        lp, l = per_layer
        x, kc, vc, out = body(x, {**lp, **whole}, l, kc, vc)
        return (x, kc, vc), out

    (x, k_cache, v_cache), outs = jax.lax.scan(
        step, (x, k_cache, v_cache),
        (sliced, jnp.arange(n_layers, dtype=jnp.int32)))
    return x, k_cache, v_cache, outs


def _layer_step(cfg: ModelConfig, lp: dict, x: jnp.ndarray,
                positions: jnp.ndarray, attn_fn, valid=None, mesh=None,
                impl: str = "jnp", layer=None):
    """One transformer layer over a full [B, T, D] sequence.

    The SINGLE definition of the layer math for every full-sequence
    forward (prefill, sequence-parallel prefill, encoder) — only the
    attention schedule differs, injected as `attn_fn(q, k, v) -> [B,T,H,hd]`.
    Returns (x', k, v, expert load) so callers can scatter K/V into the
    paged cache; the load is None for a dense model (`_ffn`).
    (forward_decode keeps its own body: it must write K/V into the
    loop-carried pool BEFORE attending.)
    """
    B, T, _ = x.shape
    with jax.named_scope("attn_qkv"):
        h = rmsnorm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv(cfg, lp, h)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    attn = attn_fn(q, k, v)
    with jax.named_scope("attn_out"):
        x = x + qeinsum("bte,ed->btd", attn.reshape(B, T, cfg.q_dim),
                        lp["wo"])
    with jax.named_scope("mlp"):
        h2 = rmsnorm(x, lp["mlp_norm"], cfg.rms_norm_eps)
        delta, load = _ffn(cfg, lp, h2, valid=valid, mesh=mesh, impl=impl,
                           layer=layer)
    return x + delta, k, v, load


def forward_prefill(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, T] int32, right-padded
    seq_lens: jnp.ndarray,  # [B] valid lengths
    k_cache: jnp.ndarray,  # [L, S, Hk*hd] flat slot pool (donated; loop carry)
    v_cache: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, max_pages]; padding rows point at trash page
    page_size: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Whole prompts in one dense causal pass (the oracle: see the module
    docstring); returns (last_logits [B, V], k_cache', v_cache').

    Padding positions scatter into the allocator's reserved trash page, so
    the write is fully static-shaped — no dynamic trimming needed.
    """
    B, T = tokens.shape
    x = embed_lookup(params["embed"], tokens, _adtype(params))
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    slots = flat_slot_indices(page_table, positions, page_size)  # [B, T]

    def body(x, lp, l, kc, vc):
        x, k, v, load = _layer_step(
            cfg, lp, x, positions,
            lambda q, k, v: causal_attention(q, k, v, seq_lens),
            valid=positions < seq_lens[:, None], layer=l,
        )
        return x, kv_write(kc, l, slots, k), kv_write(vc, l, slots, v), load

    x, k_cache, v_cache, _ = scan_layers(body, x, params["layers"], k_cache,
                                         v_cache)
    last = jnp.clip(seq_lens - 1, 0, T - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)  # [B,1,D]
    logits = _logits(params, cfg, x_last)[:, 0, :]  # [B, V]
    return logits, k_cache, v_cache


def forward_ragged(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [T] int32 flattened mixed-batch token stream
    tok_seq: jnp.ndarray,  # [T] int32 sequence (batch row) per token
    tok_pos: jnp.ndarray,  # [T] int32 kv position per token (-1 = pad)
    write_slots: jnp.ndarray,  # [T] int32 flat cache slot per token
    out_idx: jnp.ndarray,  # [B] or [B, O] int32 stream indices to read logits at
    k_cache: jnp.ndarray,  # [L, S, Hk*hd] (donated; loop carry)
    v_cache: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, max_pages]
    q_start: jnp.ndarray,  # [B] span offset per sequence
    q_len: jnp.ndarray,  # [B] span length (0 = padding row)
    kv_len: jnp.ndarray,  # [B] context length incl. the span
    page_size: int,
    attn_impl: str = "jnp",  # "jnp" reference | "pallas" ragged TPU kernel
    interpret: bool = False,
    mesh=None,  # the mesh this forward is jitted over (pallas under tp)
    moe_load: bool = False,  # also return the [L, E] expert loads
):
    """ONE forward over a ragged mixed batch: variable-length prefill
    spans and single decode tokens share a flattened [T] token stream —
    no per-sequence bucket padding. Each layer writes the stream's K/V
    into its pages, then every token attends causally over its own
    sequence's paged context (forward_decode generalized to multi-token
    spans: a prompt fed span by span reproduces forward_prefill). `out_idx` names
    the stream positions whose logits leave the forward: a [B] vector
    (each sequence's last token — the classic shape) returns [B, V];
    a [B, O] matrix (speculative verification reads a logit at EVERY
    draft position of a span) returns [B, O, V]. Padding rows
    (q_len == 0) yield garbage logits the caller ignores. Returns
    (logits, caches'), and with `moe_load` (an MoE model's step program
    asks) the rows each expert of each layer got, [L, E] int32, fourth.
    """
    T = tokens.shape[0]
    with jax.named_scope("embed"):
        x = embed_lookup(params["embed"], tokens,
                         _adtype(params))[None]  # [1,T,D]
    positions = jnp.maximum(tok_pos, 0)[None, :]  # [1, T] RoPE positions
    valid = (tok_pos >= 0)[None, :]

    def body(x, lp, l, kc, vc):
        def attn_fn(q, k, v):  # [1, T, H, hd]
            nonlocal kc, vc
            with jax.named_scope("kv_write"):
                kc = kv_write(kc, l, write_slots, k[0])
                vc = kv_write(vc, l, write_slots, v[0])
            with jax.named_scope("attention"):
                out = ragged_attention_any(
                    attn_impl, q[0], kc, vc, l, page_table, tok_seq,
                    tok_pos, kv_len, q_start, q_len, page_size,
                    interpret=interpret, mesh=mesh,
                )
            return out[None]

        x, _, _, load = _layer_step(cfg, lp, x, positions, attn_fn,
                                    valid=valid, mesh=mesh, impl=attn_impl,
                                    layer=l)
        return x, kc, vc, load

    x, k_cache, v_cache, load = scan_layers(body, x, params["layers"],
                                            k_cache, v_cache)
    if out_idx.ndim == 1:
        x_last = x[0][out_idx]  # [B, D]
        logits = _logits(params, cfg, x_last[None])[0]  # [B, V]
    else:
        x_last = x[0][out_idx]  # [B, O, D]
        logits = _logits(params, cfg, x_last)  # [B, O, V]
    if moe_load:
        return logits, k_cache, v_cache, load
    return logits, k_cache, v_cache


def forward_decode(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B] int32 — last generated token per slot
    positions: jnp.ndarray,  # [B] int32 — position of `tokens` in each seq
    k_cache: jnp.ndarray,  # [L, S, Hk*hd] (donated; loop carry)
    v_cache: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, max_pages]
    page_size: int,
    attn_impl: str = "jnp",  # "jnp" reference | "pallas" ragged TPU kernel
    active=None,  # [B] int32/bool — live decode slots (None = all live)
    mesh=None,  # the mesh this forward is jitted over (pallas under tp)
    moe_load: bool = False,  # also return the [L, E] expert loads
):
    """One decode step for the whole batch; returns (logits [B,V], caches')
    and, with `moe_load`, the [L, E] expert loads (as forward_ragged).

    `active` feeds MoE routing only: parked slots carry garbage tokens
    that are routed to no expert (models/moe.py).
    """
    B = tokens.shape[0]
    valid = None if active is None else (active > 0)[:, None]
    with jax.named_scope("embed"):
        x = embed_lookup(params["embed"], tokens,
                         _adtype(params))[:, None, :]  # [B,1,D]
    pos2 = positions[:, None]  # [B,1]
    write_slots = flat_slot_indices(page_table, pos2, page_size)[:, 0]  # [B]
    seq_lens = positions + 1

    def body(x, lp, l, kc, vc):
        with jax.named_scope("attn_qkv"):
            h = rmsnorm(x, lp["attn_norm"], cfg.rms_norm_eps)
            q, k, v = _qkv(cfg, lp, h)  # [B,1,H,hd]
            q = apply_rope(q, pos2, cfg.rope_theta)
            k = apply_rope(k, pos2, cfg.rope_theta)
        with jax.named_scope("kv_write"):
            kc = kv_write(kc, l, write_slots, k[:, 0])
            vc = kv_write(vc, l, write_slots, v[:, 0])
        with jax.named_scope("attention"):
            attn = paged_decode_attention_any(
                attn_impl, q[:, 0], kc, vc, l, page_table, seq_lens,
                page_size, mesh=mesh,
            )  # [B,H,hd]
        with jax.named_scope("attn_out"):
            x = x + qeinsum("be,ed->bd", attn.reshape(B, cfg.q_dim),
                            lp["wo"])[:, None, :]
        with jax.named_scope("mlp"):
            h2 = rmsnorm(x, lp["mlp_norm"], cfg.rms_norm_eps)
            delta, load = _ffn(cfg, lp, h2, valid=valid, mesh=mesh,
                               impl=attn_impl, layer=l)
        return x + delta, kc, vc, load

    x, k_cache, v_cache, load = scan_layers(body, x, params["layers"],
                                            k_cache, v_cache)
    logits = _logits(params, cfg, x)[:, 0, :]
    if moe_load:
        return logits, k_cache, v_cache, load
    return logits, k_cache, v_cache


def forward_prefill_sp(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, T] — T sharded over the mesh "seq" axis
    seq_lens: jnp.ndarray,  # [B]
    mesh,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sequence-parallel prefill for long contexts: activations sharded
    along T over the "seq" mesh axis, attention via ring attention
    (K/V blocks rotate over ICI). Returns (last_logits [B,V],
    k_stack [L,B,T,Hk,hd], v_stack) — the caller scatters K/V into the
    paged pool. Numerics match forward_prefill exactly (same f32 online
    softmax), only the schedule is distributed.
    """
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from ollamamq_tpu.parallel.mesh import AXIS_SEQ
    from ollamamq_tpu.parallel.ring_attention import ring_attention

    B, T = tokens.shape
    seq_sharded = NamedSharding(mesh, PS(None, AXIS_SEQ, None))
    x = embed_lookup(params["embed"], tokens, _adtype(params))
    x = jax.lax.with_sharding_constraint(x, seq_sharded)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    def body(carry, lp):
        x = carry
        x, k, v, _ = _layer_step(
            cfg, lp, x, positions,
            lambda q, k, v: ring_attention(q, k, v, seq_lens, mesh),
            valid=positions < seq_lens[:, None],
        )
        x = jax.lax.with_sharding_constraint(x, seq_sharded)
        return x, (k, v)

    x, (k_stack, v_stack) = jax.lax.scan(body, x, params["layers"])
    last = jnp.clip(seq_lens - 1, 0, T - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)
    logits = _logits(params, cfg, x_last)[:, 0, :]
    return logits, k_stack, v_stack


def forward_embed(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, T]
    seq_lens: jnp.ndarray,  # [B]
) -> jnp.ndarray:
    """Embeddings from a GENERATIVE model: causal forward (no KV write),
    masked mean pool of the final-norm hidden states, L2 norm — llama.cpp's
    default pooling for causal models, which is what the reference's Ollama
    backends run for /api/embed on e.g. llama3 (README.md /api/embed row).
    """
    B, T = tokens.shape
    x = embed_lookup(params["embed"], tokens, _adtype(params))
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    def body(carry, lp):
        x = carry
        x, *_ = _layer_step(
            cfg, lp, x, positions,
            lambda q, k, v: causal_attention(q, k, v, seq_lens),
            valid=positions < seq_lens[:, None],
        )
        return x, None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps).astype(jnp.float32)
    mask = (positions < seq_lens[:, None]).astype(jnp.float32)[:, :, None]
    pooled = jnp.sum(x * mask, axis=1) / jnp.maximum(jnp.sum(mask, axis=1), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)


def forward_encoder(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, T]
    seq_lens: jnp.ndarray,  # [B]
) -> jnp.ndarray:
    """Embedding encoder: bidirectional attention + masked mean pool + L2 norm."""
    B, T = tokens.shape
    x = embed_lookup(params["embed"], tokens, _adtype(params))
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    def body(carry, lp):
        x = carry
        x, *_ = _layer_step(
            cfg, lp, x, positions,
            lambda q, k, v: bidirectional_attention(q, k, v, seq_lens),
            valid=positions < seq_lens[:, None],
        )
        return x, None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps).astype(jnp.float32)
    mask = (positions < seq_lens[:, None]).astype(jnp.float32)[:, :, None]
    pooled = jnp.sum(x * mask, axis=1) / jnp.maximum(jnp.sum(mask, axis=1), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)
