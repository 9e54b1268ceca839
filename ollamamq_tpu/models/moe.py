"""Mixture-of-experts FFN (Mixtral, OLMoE, LFM2, DeepSeek-V3.2, openPangu,
Qwen3-Next) with expert parallelism.

The reference serves MoE models only by proxying to an Ollama backend that
happens to run one (llama.cpp does the routing on CPU/GPU); it has no
expert-parallel story at all. Here MoE is a first-class layer family, with
ONE dispatch for every model of it:

  - Routing is token-choice top-k, in float32, and a function of the
    config (`route`): the score over all experts is a softmax (Mixtral,
    OLMoE) or a sigmoid (`router_score`, LFM2); with `use_expert_bias` the
    top k are taken by score + a per-expert bias, but a chosen expert's
    WEIGHT is its score without the bias; `norm_topk_prob` divides the
    kept weights by their sum (+ `norm_topk_eps`) (Mixtral, LFM2: yes;
    OLMoE: no); `routed_scaling_factor` multiplies them.
  - The dispatch is DROPLESS: the (token, k-slot) assignments are sorted
    by expert, each expert's contiguous rows go through its SwiGLU as
    three grouped matmuls, and the rows are un-sorted and summed with
    their router weights. Shapes are static (tokens x k rows, whatever
    the routing), so there is no capacity, nothing to overflow, and every
    token's every routed expert contributes, exactly, at any token count.
  - The grouped matmul has a kernel and a twin, chosen with the attention
    kernels (`impl`, the forwards' `attn_impl`): "pallas" is jax's
    megablox `gmm` (op `gmm` on the device trace), tiled so that a
    128-row tile meets a [2048, 1024] weight tile, or the tiles nearest
    that which DIVIDE the matrix (`gmm_tiling`, below) — a decode pass
    is bound by streaming each hit expert's weights once; anything else
    is `jax.lax.ragged_dot` (XLA's own: `ragged-dot` on a TPU trace, where
    its 512-row tiles make the same pass compute-bound, 2.3 x slower on a
    v5e; PERF.md section 6, PR 27).
  - `n_group` / `topk_group` limit the selection to the best groups of
    experts (DeepSeek-V3's), `n_shared_experts` (or, of a width of its
    own, `shared_expert_intermediate_size`) adds a dense SwiGLU every
    token passes (scope `moe_shared`), which `shared_expert_gate`
    multiplies by sigmoid(x w_sg) (Qwen3-Next's; scope `moe_shared_gate`
    inside it), and `router_experts` /
    `expert_offset` make the layer ONE CHIP'S SHARE of a wider one: the
    router scores all the published experts and normalises over all it
    chose, this program holds `num_experts` of them and adds what those
    give — what expert parallelism asks of a layer, without the exchange.
  - Padding rows and idle decode slots (`valid` false) are assigned to no
    expert: they sort behind every group, are multiplied by no weight and
    count in no statistic.
  - Sharding: `we_*` split over the mesh axes "expert" (the expert dim)
    and "tensor" (the per-expert FFN dim), parallel/sharding.py. XLA
    cannot partition the grouped matmul, so on a mesh the expert FFN runs
    under a shard_map: every shard sees the step's sorted rows, computes
    its own experts' (and its own FFN columns') part, and one psum joins
    the parts. A step is at most a few thousand rows, so no all-to-all is
    needed.

Expert weights are stacked [L, E, ...] over the layers that HAVE experts
(a stack with a dense prefix has fewer than `num_layers`; `layer` counts
them). The step forwards do NOT hand the
layer loop a slice of them: a kernel's operand cannot be a fused slice, so
XLA would copy each layer's three matrices out of the stack (0.8 GB a layer
for OLMoE: twice the bytes the matmuls themselves read). Like the KV pool
they stay whole (`STACKED`, models/llama.py:scan_layers) and `moe_mlp` reads
them by `layer`: the kernel sees [L*E, ...] and group sizes that are zero
outside this layer's experts. `moe_mlp` also returns how many rows each
expert got: the step programs reduce that to the counters of `load_stats`.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from ollamamq_tpu.config import EXPERTS, ModelConfig
from ollamamq_tpu.ops.quant import qeinsum
from ollamamq_tpu.parallel.mesh import AXIS_EXPERT, AXIS_TENSOR

log = logging.getLogger("ollamamq.moe")

# Stage names inside llama's "mlp" scope on the device trace, in order.
SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
# ...and the shared experts' stage, for a model that has them.
SHARED_SCOPES = ("moe_shared",)
# ...and, inside it, the shared expert's sigmoid gate, for a model with one.
SHARED_GATE_SCOPES = ("moe_shared_gate",)
# Layer params the layer loop reads whole, by layer index.
STACKED = ("we_gate", "we_up", "we_down")
# What load_stats() returns, in order (int32 each).
LOAD_STATS = ("assignments", "pairs_hit", "load_max")
# megablox tiling (rows, contraction, columns) of the "pallas" grouped
# matmul where it walks the matrices in whole tiles: of the four measured on
# a v5e at OLMoE's shapes (PERF.md section 6, PR 27) the one nearest the
# weight-streaming floor from 128 to 4096 rows. Where it does not, gmm_tiling
# takes tiles that do (PR 68). megablox masks a contraction remainder — the
# last k tile's blocks go through float32, a compare and a select, and the
# MXU contracts the whole tile, zeros and all — and a column remainder moves
# a sliver of weights for a whole tile's contraction. One launch of a step's
# rows on a v5e, µs (share of the hit experts' byte floor), the fixed tiles
# clipped -> the tiles chosen (scripts/gmm_bench.py; my chip runs, PR 68):
#
#   kimi-linear  gate/up [2304, 1024]  357.4 (49 %) -> (2304, 1024) 236.4 (74 %)
#                  (1152, 1024) 250.4: no mask, two k tiles; (768, 1024) 248.9
#                down    [1024, 2304]  273.6 (64 %) -> (1024, 2304) 235.7 (74 %)
#                  (1024, 768) 242.6, (1024, 1152) 240.8
#   deepseek     gate/up [7168, 2048]  277.7 (67 %) -> (1792, 1024) 269.4 (69 %)
#   openpangu    gate/up [7680, 2048]  214.6 (72 %) -> (1920, 1024) 215.8 (72 %)
#   lfm2         gate/up [2048, 1792]  328.6 (81 %), kept: its remainder of 768
#                  columns is byte-bound; (2048, 896) 326.0
#   olmoe 247.2 / 257.9, qwen3-next 390.2 / 406.6, k-exaone 268.9 / 224.4,
#   mimo 105.1 / 94.7 (gate/up / down): whole tiles already, kept; so are
#   deepseek's, openpangu's and lfm2's down projections (250.3, 212.6, 329.1).
#
#   xing4.0      gate/up [3584, 1024]  629.1 (87 %) -> (1792, 1024) 628.1 (88 %)
#                down    [1024, 3584]  (1024, 1024) 649.1 (85 %): the rule's,
#                  three and a HALF column tiles -> (1024, 1792) 628.0 (88 %),
#                  two whole ones (GMM_SHAPE_TILES: this shape alone; 256 rows
#                  on 61 of 64 experts, my chip run, PR 69); (512, 1792) 628.8
#
# One k tile beats several at the same bytes (k-exaone's down, k = 2048,
# against its gate of three tiles; mimo's gate as (4096, 512) 96.8) — the
# row block stays and an expert's weights are not fetched again where its
# rows cross a row tile — and a wider column tile a narrower one (olmoe's
# down (1024, 2048) 249.9, qwen3-next's (512, 2048) 388.8): ROADMAP A16.
GMM_TILING = (128, 2048, 1024)
# (k, n) -> (tk, tn) where one launch measured another tiling at least 3 %
# faster than the rule's choice, for that shape alone (the table above).
GMM_SHAPE_TILES = {(1024, 3584): (1024, 1792)}
# A column remainder under a tile / GMM_SLIVER is a sliver: a [tk, 1024]
# tile step takes the MXU 128 x tk x 1024 multiply-adds whatever it moved,
# and under ~545 columns of bf16 that is more than their bytes' time.
GMM_SLIVER = 2
# What a tiling's blocks may take of Mosaic's 16 MiB of scoped VMEM on a v5e
# (gmm_vmem_bytes): the compiler took 14.5 MiB of them and refused 16.75.
GMM_VMEM_BYTES = 14 * 2**20
# Standard deviation of a seeded-random selection bias: a tenth of the
# router logits', so it moves the choice of some tokens' k-th expert.
ROUTER_BIAS_SD = 0.1
# The shared experts' weights (a dense SwiGLU every token passes, sliced by
# layer like the router) and the fold_in constant of their init keys.
SHARED = ("ws_gate", "ws_up", "ws_down")
SHARED_KEY = 0x73686172
# The shared expert's gate w_sg [L, D]: its output times sigmoid(x . w_sg).
SHARED_GATE = ("w_shared_gate",)


def init_moe_layer_params(cfg: ModelConfig, key: jax.Array, dtype) -> dict:
    """Router + stacked expert weights for every layer that has experts:
    the routed-FFN entries of the `layers` tree when cfg.num_experts > 0.
    The selection bias is a float32 buffer and is drawn NON-zero: a
    forward that drops it, or weights by the biased score, computes
    another model."""
    d, f = cfg.hidden_size, cfg.expert_width
    # (the prediction module's block is one more entry of every stack)
    L = cfg.count(EXPERTS) + cfg.num_nextn_predict_layers
    E, R = cfg.num_experts, cfg.router_width
    # (One more key only where there is a bias: the families without one
    # keep the weights their seeds have always drawn.)
    keys = jax.random.split(key, 4 + cfg.use_expert_bias)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / jnp.sqrt(fan_in)).astype(dtype)

    out = {
        "w_router": w(keys[0], (L, d, R), d),
        "we_gate": w(keys[1], (L, E, d, f), d),
        "we_up": w(keys[2], (L, E, d, f), d),
        "we_down": w(keys[3], (L, E, f, d), f),
    }
    if cfg.use_expert_bias:
        out["router_bias"] = ROUTER_BIAS_SD * jax.random.normal(
            keys[4], (L, R), jnp.float32)
    fs = cfg.shared_width
    if fs:
        # (One more key only where there is a gate, as for the bias.)
        sk = jax.random.split(jax.random.fold_in(key, SHARED_KEY),
                              3 + cfg.shared_expert_gate)
        out.update(ws_gate=w(sk[0], (L, d, fs), d), ws_up=w(sk[1], (L, d, fs), d),
                   ws_down=w(sk[2], (L, fs, d), fs))
        if cfg.shared_expert_gate:
            # x . w_sg ~ N(0, 1): the gate spreads over (0, 1), so a
            # forward that drops it computes another model.
            out["w_shared_gate"] = w(sk[3], (L, d), d)
    return out


def _dividing(dim: int, cap: int) -> list:
    """The multiples of 128 up to `cap` that divide `dim`, largest first."""
    return [t for t in range(cap - cap % 128, 0, -128) if dim % t == 0]


def gmm_vmem_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """What megablox's pipeline keeps in VMEM at a tiling: the weight, row
    and output blocks, each double-buffered, and the float32 accumulator."""
    return 2 * itemsize * (tk * tn + tm * tk + tm * tn) + 4 * tm * tn


@functools.lru_cache(maxsize=None)
def gmm_tiling(m: int, k: int, n: int, itemsize: int = 2,
               caps: tuple = GMM_TILING, vmem: int = GMM_VMEM_BYTES) -> tuple:
    """(tm, tk, tn) of the "pallas" grouped matmul of [m, k] rows with
    [G, k, n] weights: `caps` where they divide the matrices, else tiles
    that do (the table above GMM_TILING). A function of (k, n) alone,
    static at trace time; logs its choice once a shape."""
    tm, tk, tn = caps[0], min(caps[1], k), min(caps[2], n)

    def fits(tk, tn):
        return gmm_vmem_bytes(tm, tk, tn, itemsize) <= vmem

    measured = GMM_SHAPE_TILES.get((k, n)) if caps == GMM_TILING else None
    if measured and fits(*measured):
        log.info("gmm tiles (tm, tk, tn) = %s for (m, k, n) = %s (measured)",
                 (tm, *measured), (m, k, n))
        return (tm, *measured)

    # Either way: the whole dimension where the blocks fit, else the largest
    # tile that divides it, else the clip as it was.
    if 0 < n % tn < tn // GMM_SLIVER:
        # The last column tile would move a sliver of weights and contract
        # a whole tile: compute-bound where the whole tiles are byte-bound.
        tn = next((t for t in [n, *_dividing(n, tn)] if fits(tk, t)), tn)
    if k % tk:
        # The last contraction tile would be masked — a float32 pass over
        # both blocks — and contracted whole, zeros and all; with one tile
        # the row block also stays for the experts that share a row tile.
        tk = next((t for t in [k, *_dividing(k, tk)] if fits(t, tn)), tk)
    log.info("gmm tiles (tm, tk, tn) = %s for (m, k, n) = %s",
             (tm, tk, tn), (m, k, n))
    return tm, tk, tn


def grouped_matmul(impl: str, xs, w, sizes, interpret: bool = False):
    """Row r of xs [M, k], in group g of the consecutive `sizes` [G], times
    w[g] ([G, k, n]) -> [M, n]; rows past sum(sizes) come back as whatever.
    With "pallas", M is a multiple of GMM_TILING[0]."""
    if impl != "pallas":
        return jax.lax.ragged_dot(xs, w, sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    tiling = gmm_tiling(xs.shape[0], *w.shape[1:], w.dtype.itemsize)
    return gmm(xs, w, sizes, xs.dtype, tiling, interpret=interpret)


def _expert_ffn(impl, xs, sizes, w_gate, w_up, w_down, layer=None):
    """SwiGLU of each expert over its own rows. xs [M, D] sorted by expert,
    sizes [E] rows per expert (rows past their sum belong to no expert and
    come back as whatever: the caller masks them). With `layer` the weights
    are whole stacks [L, E, ...]."""
    m = xs.shape[0]
    if layer is not None and impl == "pallas":
        # Every layer's experts as groups; all but this layer's are empty.
        n_e = sizes.shape[0]
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros(w_gate.shape[0] * n_e, sizes.dtype), sizes,
            (layer * n_e,))
        w_gate, w_up, w_down = (w.reshape(-1, *w.shape[2:])
                                for w in (w_gate, w_up, w_down))
    elif layer is not None:
        w_gate, w_up, w_down = w_gate[layer], w_up[layer], w_down[layer]
    if impl == "pallas":  # whole row tiles; the added rows are no group's
        xs = jnp.pad(xs, ((0, -m % GMM_TILING[0]), (0, 0)))
    gate = grouped_matmul(impl, xs, w_gate, sizes)
    up = grouped_matmul(impl, xs, w_up, sizes)
    return grouped_matmul(impl, jax.nn.silu(gate) * up, w_down, sizes)[:m]


def _expert_ffn_sharded(mesh, impl, xs, sizes, w_gate, w_up, w_down, layer):
    """_expert_ffn over a mesh, on the whole [L, E, ...] stacks: each shard
    runs the experts (and the FFN columns) it stores over all of the step's
    rows; a psum over "expert" and "tensor" joins the parts."""
    ep = mesh.shape[AXIS_EXPERT]

    def local(xs, sizes, w_gate, w_up, w_down, layer):
        if ep == 1:
            y = _expert_ffn(impl, xs, sizes, w_gate, w_up, w_down, layer)
        else:
            # This shard's experts own one contiguous run of the sorted
            # rows; the grouped matmul wants its first group at row 0.
            n_loc = sizes.shape[0] // ep
            first = jax.lax.axis_index(AXIS_EXPERT) * n_loc
            mine = jax.lax.dynamic_slice(sizes, (first,), (n_loc,))
            start = jnp.sum(jnp.where(jnp.arange(sizes.shape[0]) < first,
                                      sizes, 0))
            y = _expert_ffn(impl, jnp.roll(xs, -start, axis=0), mine,
                            w_gate, w_up, w_down, layer)
            rows = jnp.arange(xs.shape[0])[:, None]
            y = jnp.roll(jnp.where(rows < jnp.sum(mine), y, 0), start, axis=0)
        return jax.lax.psum(y, (AXIS_EXPERT, AXIS_TENSOR))

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(PS(), PS(), PS(None, AXIS_EXPERT, None, AXIS_TENSOR),
                  PS(None, AXIS_EXPERT, None, AXIS_TENSOR),
                  PS(None, AXIS_EXPERT, AXIS_TENSOR, None), PS()),
        out_specs=PS(), check_vma=False,
    )(xs, sizes, w_gate, w_up, w_down, layer)


def route(cfg: ModelConfig, lp: dict, x: jnp.ndarray):
    """x [N, D] -> (weights [N, K] float32, experts [N, K] int32): the
    config's router (the module docstring's first point). In float32: the
    scores feed multiplicative gates — bf16 here costs real quality for
    no speed."""
    logits = jnp.dot(x.astype(jnp.float32),
                     lp["w_router"].astype(jnp.float32))
    scores = (jax.nn.sigmoid(logits) if cfg.router_score == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    choice = scores
    if cfg.use_expert_bias:  # the bias selects; it weights nothing
        choice = scores + lp["router_bias"]
    if cfg.n_group:
        # Group-limited: a group scores the sum of its best two, and the
        # top k are taken inside the `topk_group` best groups.
        N, R = choice.shape
        grouped = choice.reshape(N, cfg.n_group, R // cfg.n_group)
        best2, _ = jax.lax.top_k(grouped, 2)
        _, groups = jax.lax.top_k(jnp.sum(best2, axis=-1), cfg.topk_group)
        open_ = jnp.any(groups[:, :, None]
                        == jnp.arange(cfg.n_group)[None, None, :], axis=1)
        choice = jnp.where(open_[:, :, None], grouped, -jnp.inf
                           ).reshape(N, R)
    if choice is scores:
        gates, experts = jax.lax.top_k(scores, cfg.num_experts_per_tok)
    else:
        _, experts = jax.lax.top_k(choice, cfg.num_experts_per_tok)
        gates = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg.norm_topk_prob:
        total = jnp.sum(gates, axis=-1, keepdims=True)
        gates = gates / (total + cfg.norm_topk_eps if cfg.norm_topk_eps
                         else total)
    if cfg.routed_scaling_factor != 1.0:
        gates = gates * cfg.routed_scaling_factor
    return gates, experts


def moe_mlp(cfg: ModelConfig, lp: dict, h: jnp.ndarray, valid=None,
            mesh=None, impl: str = "jnp", layer=None):
    """Top-k routed expert FFN over [B, T, D] hiddens; returns ([B, T, D],
    load [E] int32: the rows each expert got).

    Same contract as llama._mlp (the residual add happens in the caller).
    `valid` ([B, T] bool, optional) marks real tokens: padding positions
    and inactive decode slots are routed to no expert. `mesh`: the mesh the
    caller's jit runs over when `we_*` are sharded on it (None on one
    device, or where the caller leaves the layout to GSPMD; with a mesh,
    `layer` is given too). `impl`: the grouped matmul, as the forward's
    `attn_impl`. `layer`: with it, `lp[STACKED]` are the whole [L, E, ...]
    stacks and this is the layer.
    """
    B, T, D = h.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    N = B * T
    x = h.reshape(N, D)

    with jax.named_scope("moe_router"):
        gates, experts = route(cfg, lp, x)
        if cfg.router_width != E:
            # The chip's share: the router chose among all its experts and
            # normalised over all it chose; the ones held here are E from
            # `expert_offset` on, the others are no expert of this layer.
            experts = experts - cfg.expert_offset
            experts = jnp.where((experts >= 0) & (experts < E), experts, E)

    with jax.named_scope("moe_dispatch"):
        # Assignment a = (token a // K, slot a % K). An invalid token's
        # assignments go to "expert E": behind every real group.
        if valid is not None:
            experts = jnp.where(valid.reshape(N, 1), experts, E)
        flat = experts.reshape(N * K)
        order = jnp.argsort(flat, stable=True)
        load = jnp.sum(flat[:, None] == jnp.arange(E)[None, :], axis=0,
                       dtype=jnp.int32)
        xs = x[order // K]  # [N*K, D], sorted by expert

    with jax.named_scope("moe_experts"):
        if mesh is None or mesh.size == 1:
            ys = _expert_ffn(impl, xs, load, lp["we_gate"], lp["we_up"],
                             lp["we_down"], layer)
        else:
            ys = _expert_ffn_sharded(mesh, impl, xs, load, lp["we_gate"],
                                     lp["we_up"], lp["we_down"], layer)

    with jax.named_scope("moe_combine"):
        rank = jnp.zeros_like(order).at[order].set(
            jnp.arange(N * K, dtype=order.dtype))  # row of assignment a
        y = ys[rank].reshape(N, K, D)
        w = jnp.where(experts < E, gates, 0.0)  # [N, K]
        y = jnp.where((experts < E)[..., None], y, 0)  # unowned rows: anything
        out = jnp.einsum("nkd,nk->nd", y.astype(jnp.float32), w)
    out = out.astype(h.dtype).reshape(B, T, D)
    if cfg.shared_width:
        with jax.named_scope("moe_shared"):
            gate = qeinsum("btd,df->btf", h, lp["ws_gate"])
            up = qeinsum("btd,df->btf", h, lp["ws_up"])
            shared = qeinsum("btf,fd->btd", jax.nn.silu(gate) * up,
                             lp["ws_down"])
            if cfg.shared_expert_gate:
                with jax.named_scope("moe_shared_gate"):
                    sg = jax.nn.sigmoid(jnp.einsum(
                        "btd,d->bt", h, lp["w_shared_gate"],
                        preferred_element_type=jnp.float32))
                    shared = (shared * sg[..., None]).astype(h.dtype)
            out = out + shared
    return out, load


def load_stats(load: jnp.ndarray) -> jnp.ndarray:
    """int32 [3] (LOAD_STATS) of one forward pass's [L, E] expert loads:
    assignments made (real tokens x k x layers), (layer, expert) pairs
    that got at least one row — each streams its weights once — and the
    most rows any one expert of any layer got."""
    return jnp.stack([jnp.sum(load), jnp.sum(load > 0),
                      jnp.max(load)]).astype(jnp.int32)
