"""Paged KV cache: device slot pool + host-side page allocator.

(With latent attention, `ModelConfig.kv_lora_rank`, the two arrays are not K
and V but the LATENT pool, [layers, slots, latent_lanes], and the INDEX-KEY
pool, [layers, slots, index_head_dim]: two kinds of paged state of different
row widths under one page table and one allocator, allocated, donated,
carried and written by page exactly as K and V are — ops/mla.py. A model
with no indexer has no second pool: the array is there with 0 lanes. A
model with a prediction module has one more layer in the pool, behind the
attention layers: the module's block's own rows, `ModelConfig.cache_layers`.)

Device side: two arrays per model, [attention layers, num_pages*page_size,
kv_heads*head_dim] for K and V (an int8 pool adds [attention layers, slots,
kv_heads] scale planes; a stack whose `layer_types` holds other operators
has fewer attention layers than layers, and their state is not pages:
ops/shortconv.py), the last axis split by kv head over the "tensor"
mesh axis. That is the layout the attention kernels DMA pages from
(ops/pallas/): a page of layer l is `pool[l, page*page_size : (page+1)*
page_size]`, [page_size, Hk*hd] rows, and nothing ever reshapes the pool.
The pool is allocated ONCE at engine start (static shape => no
recompiles, no fragmentation in HBM), donated to every step program, and
carried through its layer loop (models/llama.py:scan_layers), so a step
updates it in place: there is one copy of it on the device.

Host side: a free-list allocator of page indices. Page 0 is RESERVED as the
trash page: page-table rows are padded with it, and a step's padding
tokens (the ragged stream rounds up to a granule; an idle slot's row of
the decode scan) write their K/V into it, so every scatter is
static-shaped and lands harmlessly. The attention kernels READ it too —
a block's pages past a sequence's last are whatever the table holds
there, masked to a weight of 0 (ops/pallas/kv_contract.py) — and lean on
one invariant: page 0 holds finite values only (zeros at start, then
what padding rows wrote), since 0 times a NaN or an infinity is not 0.

Cancellation reclaims pages immediately — the TPU analogue of the
reference dropping a disconnected client's stream
(/root/reference/src/dispatcher.rs:537-551) plus freeing the backend slot.
"""

from __future__ import annotations

import io
import json
import logging
import struct
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ollamamq_tpu.config import (MAMBA, PARALLEL, SPARSE, STATE_KINDS, WINDOW,
                                 EngineConfig, ModelConfig)

log = logging.getLogger("ollamamq.engine")

TRASH_PAGE = 0


class PageAllocator:
    """Free-list allocator over page indices [1, num_pages).

    With the prefix cache enabled (engine/prefix_cache.py) every page is
    exactly one of FREE (on the free list), USED (private to a decode
    slot), or CACHED (owned by the radix tree, possibly pinned by live
    requests); `cached_pages` tracks the third bucket so
    free + used + cached == num_pages - 1 always holds.
    """

    def __init__(self, num_pages: int, page_size: int, max_pages_per_seq: int):
        self.num_pages = num_pages
        self.page_size = page_size
        self.max_pages_per_seq = max_pages_per_seq
        self._free: List[int] = list(range(num_pages - 1, 0, -1))  # page 0 reserved
        self.cached_pages = 0  # tree-owned (prefix cache accounting)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return (self.num_pages - 1) - len(self._free) - self.cached_pages

    def pages_needed(self, num_tokens: int) -> int:
        return max(1, -(-num_tokens // self.page_size))

    def alloc(self, num_tokens: int) -> Optional[List[int]]:
        """Allocate pages to hold num_tokens; None if pool exhausted or the
        request exceeds the per-sequence page cap."""
        return self.alloc_n(self.pages_needed(num_tokens))

    def alloc_n(self, n: int, held: int = 0) -> Optional[List[int]]:
        """Allocate exactly n pages for a sequence already holding `held`
        (cache-hit admission: shared prefix pages count against the
        per-sequence cap but come from the tree, not the free list)."""
        if n > len(self._free) or held + n > self.max_pages_per_seq:
            return None
        return [self._free.pop() for _ in range(n)]

    # -- prefix-cache ownership transfer -----------------------------------
    def adopt_cached(self, n: int = 1) -> None:
        """A slot's page(s) moved into the prefix-cache tree: no longer
        used, not free either."""
        self.cached_pages += n

    def reclaim_cached(self, page: int) -> None:
        """An evicted tree page returns to the free list."""
        self.cached_pages -= 1
        if page != TRASH_PAGE:
            self._free.append(page)

    def extend(self, pages: List[int], new_total_tokens: int) -> bool:
        """Grow an allocation to cover new_total_tokens. False if exhausted
        or per-seq page cap reached."""
        need = self.pages_needed(new_total_tokens)
        while len(pages) < need:
            if not self._free or len(pages) >= self.max_pages_per_seq:
                return False
            pages.append(self._free.pop())
        return True

    def rollback_to(self, pages: List[int], kv_len: int,
                    keep: int = 0) -> int:
        """Speculative rollback: shrink an allocation (in place) to the
        pages a sequence of `kv_len` WRITTEN tokens actually needs,
        returning the rejected tail pages to the free list. `keep` floors
        the truncation at the sequence's shared prefix-tree pages (they
        lead the list and are owned by the tree, never this allocator's
        free list). Returns the number of pages freed."""
        target = max(self.pages_needed(max(1, kv_len)), keep)
        freed = 0
        while len(pages) > target:
            p = pages.pop()
            if p != TRASH_PAGE:
                self._free.append(p)
                freed += 1
        return freed

    def free(self, pages: List[int]) -> None:
        for p in pages:
            if p != TRASH_PAGE:
                self._free.append(p)
        pages.clear()


def make_page_table_row(pages: List[int], max_pages: int) -> np.ndarray:
    """Pad a page list with the trash page to the static table width."""
    row = np.full((max_pages,), TRASH_PAGE, dtype=np.int32)
    row[: len(pages)] = pages
    return row


def alloc_kv_pool(
    model_cfg: ModelConfig,
    engine_cfg: EngineConfig,
    sharding=None,
    dtype=jnp.bfloat16,
    kv_dtype: str = "bfloat16",
):
    """Allocate the device K/V slot pools (zeros), [L, S, Hk*hd] — the
    stored layout IS the kernels' DMA layout. Returns (k_cache, v_cache)
    — plain arrays, or QuantKV pairs when kv_dtype="int8": an int8
    payload pool plus fp32 per-slot per-head scale rows [L, S, Hk] stored
    page-aligned alongside it (slot = page * page_size + offset), so the
    page allocator, prefix tree, preemption, and rollback machinery are
    untouched while every page shrinks ~2x. `sharding`
    (parallel/sharding.kv_cache_spec) places payload and scales alike:
    both split their last axis by kv head."""
    from ollamamq_tpu.ops.quant import QuantKV

    S = engine_cfg.num_pages * engine_cfg.page_size
    shape = (model_cfg.cache_layers, S,
             model_cfg.num_kv_heads * model_cfg.head_dim)

    def filled(value, shp, dt):
        if sharding is not None:
            return jax.jit(lambda: jnp.full(shp, value, dt),
                           out_shardings=sharding)()
        return jnp.full(shp, value, dt)

    if kv_dtype == "int8" and not model_cfg.kv_lora_rank:
        sshape = shape[:2] + (model_cfg.num_kv_heads,)  # [L, S, Hk]
        return tuple(QuantKV(filled(0, shape, jnp.int8),
                             filled(1, sshape, jnp.float32))
                     for _ in range(2))
    # K rows and V rows, each at its own width (`kv_row_dims`: alike for
    # every model but one whose value heads are narrower than its key heads).
    # Latent attention: the latent pool and the index-key pool where K and V
    # were — same pages, same table, rows of another width each (no indexer:
    # the second has no lanes, and no bytes); one more layer where the model
    # has a prediction module (its block's rows).
    return tuple(filled(0, shape[:2] + (lanes,), dtype)
                 for lanes in model_cfg.kv_row_dims)


# ---------------------------------------------------------------------------
# KV page migration: extract a sequence's page run from the pool into a
# portable host-side blob (and write one back at new page indices), plus
# a self-describing wire format so the blob can cross a process boundary
# (fleet HttpMember /admin/migrate). int8 pools move the int8 payload +
# fp32 scale rows — ~2x cheaper on the wire than bf16 pages.
# ---------------------------------------------------------------------------

_WIRE_MAGIC = b"OMQMIG1\n"


def _page_index(pages: List[int], page_size: int) -> np.ndarray:
    """Slot-pool row indices covering `pages` in run order."""
    idx = np.empty((len(pages) * page_size,), np.int32)
    for i, p in enumerate(pages):
        idx[i * page_size:(i + 1) * page_size] = np.arange(
            p * page_size, (p + 1) * page_size, dtype=np.int32)
    return idx


def gather_page_run(kc, vc, pages: List[int], page_size: int,
                    head_dim: int) -> dict:
    """Copy a page run's K/V data to host numpy arrays. Returns
    {"k_pages", "v_pages"} in the blob's wire format, [L, n_pages*
    page_size, Hk, hd] (the pool's rows viewed per head HERE, at the
    boundary — the pool itself stays [L, S, Hk*hd]), plus {"k_scale",
    "v_scale"} [L, n, Hk] for quantized pools. Read-only with respect to
    the pool."""
    from ollamamq_tpu.ops.quant import QuantKV

    idx = jnp.asarray(_page_index(pages, page_size))

    def rows(pool):  # [L, n, Hk*hd] -> wire [L, n, Hk, hd]
        r = np.asarray(jnp.take(pool, idx, axis=1))
        return r.reshape(r.shape[:2] + (-1, head_dim))

    if isinstance(kc, QuantKV):
        return {
            "k_pages": rows(kc.q),
            "v_pages": rows(vc.q),
            "k_scale": np.asarray(jnp.take(kc.s, idx, axis=1)),
            "v_scale": np.asarray(jnp.take(vc.s, idx, axis=1)),
        }
    return {"k_pages": rows(kc), "v_pages": rows(vc)}


def scatter_page_run(kc, vc, pages: List[int], page_size: int, data: dict):
    """Write a gathered page run (wire format, see gather_page_run) back
    into a (possibly different) pool at `pages`. Returns the updated
    (kc, vc) — functional update, caller reassigns."""
    from ollamamq_tpu.ops.quant import QuantKV

    idx = jnp.asarray(_page_index(pages, page_size))

    def put(pool, rows):  # wire [L, n, Hk, hd] -> the pool's [L, n, Hk*hd]
        rows = jnp.asarray(rows, dtype=pool.dtype)
        return pool.at[:, idx].set(rows.reshape(rows.shape[:2] + (-1,)))

    if isinstance(kc, QuantKV):
        k = QuantKV(put(kc.q, data["k_pages"]),
                    kc.s.at[:, idx].set(jnp.asarray(data["k_scale"])))
        v = QuantKV(put(vc.q, data["v_pages"]),
                    vc.s.at[:, idx].set(jnp.asarray(data["v_scale"])))
        return k, v
    return put(kc, data["k_pages"]), put(vc, data["v_pages"])


def migration_blob_bytes(blob: dict) -> int:
    """Approximate wire size of a blob (the payload arrays dominate) —
    the ollamamq_fleet_migrate_bytes_total accounting unit."""
    return sum(v.nbytes for v in blob.values()
               if isinstance(v, np.ndarray))


def pack_migration_blob(blob: dict) -> bytes:
    """Serialize a migration blob for the wire: magic + length-prefixed
    JSON header (scalars/lists) + an npz of the numpy arrays. Keys
    starting with "_" are in-process-only state (e.g. a live incremental
    detokenizer) and are dropped — the unpacker reconstructs them.

    Non-native dtypes (bfloat16 and friends come from ml_dtypes, which
    npz cannot round-trip) ship as raw uint8 byte views with the true
    dtype name recorded in the header."""
    header, arrays, exotic = {}, {}, {}
    for key, val in blob.items():
        if key.startswith("_"):
            continue
        if isinstance(val, np.ndarray):
            if val.dtype.kind not in "biufc":
                exotic[key] = val.dtype.name
                val = np.ascontiguousarray(val).view(np.uint8)
            arrays[key] = val
        else:
            header[key] = val
    if exotic:
        header["wire_dtypes"] = exotic
    hdr = json.dumps(header).encode()
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    return _WIRE_MAGIC + struct.pack(">I", len(hdr)) + hdr + buf.getvalue()


def unpack_migration_blob(raw: bytes) -> dict:
    """Inverse of pack_migration_blob. Raises ValueError on a foreign or
    truncated payload (the import endpoint turns that into a 400)."""
    if not raw.startswith(_WIRE_MAGIC):
        raise ValueError("not a migration blob (bad magic)")
    off = len(_WIRE_MAGIC)
    if len(raw) < off + 4:
        raise ValueError("truncated migration blob header")
    (hlen,) = struct.unpack(">I", raw[off:off + 4])
    off += 4
    try:
        blob = json.loads(raw[off:off + hlen])
    except json.JSONDecodeError as e:
        raise ValueError(f"corrupt migration blob header: {e}")
    with np.load(io.BytesIO(raw[off + hlen:]), allow_pickle=False) as npz:
        for key in npz.files:
            blob[key] = npz[key]
    for key, name in (blob.pop("wire_dtypes", None) or {}).items():
        try:
            import ml_dtypes

            dt = np.dtype(getattr(ml_dtypes, name))
        except (AttributeError, ImportError, TypeError) as e:
            raise ValueError(f"unknown wire dtype {name!r}: {e}")
        if key in blob:
            blob[key] = blob[key].view(dt)
    return blob


def kv_page_bytes(model_cfg: ModelConfig, page_size: int,
                  bytes_per_el=2, kv_dtype: str = "bfloat16") -> int:
    """Bytes ONE page costs (K and V, all attention layers) — the density math's
    unit: equal-HBM pool sizing divides a byte budget by this. With
    latent attention: the latent rows and the index keys."""
    if model_cfg.kv_lora_rank:
        return (model_cfg.cache_layers * page_size
                * sum(model_cfg.kv_row_dims) * bytes_per_el)
    if kv_dtype != "int8":
        return (model_cfg.paged_layers * page_size
                * sum(model_cfg.kv_row_dims) * bytes_per_el)
    return (2 * model_cfg.paged_layers * page_size
            * model_cfg.num_kv_heads * (model_cfg.head_dim + 4))


# ---------------------------------------------------------------------------
# What a kind of per-sequence state cannot be served with yet: ONE table, a
# row a kind of state a model holds, a cell a feature that knows pages of K
# and V only (absent: served — where ROADMAP B-M2 … B-M12 will write).
# `refusal` reads the cells a start's flags turn on, in the rows' and the
# cells' order; "share" (the prefix cache is switched off with that warning)
# and "migrate" (an export answers None, an import raises that line) are met
# at run time. `{held}` / `{kinds}`: the model's STATE_KINDS.
# ---------------------------------------------------------------------------

_LAYERS = "{held} layers (layer_types)"
_NO_SPECS = ("--tp / --ep: the {held} layers' weights and state have no "
             "partition specs{rings}")
_NO_SHARE = ("prefix cache off: its {kinds} layers' per-slot state is not "
             "cached with the pages")
_NO_STATE = ("a migrated stream carries KV pages, not the {kinds} layers' "
             "state; replay the request instead")

STATE_REFUSES = {
    "window": (_LAYERS, {
        "spec": "--spec: a verify span writes the window layers' rings, and "
            "neither the draft cap nor the rollback has been written for them "
            "(ROADMAP B-M2)",
        "mesh": _NO_SPECS, "share": _NO_SHARE, "migrate": _NO_STATE}),
    "conv / linear": (_LAYERS, {
        "spec": "--spec: a rejected draft has already advanced the per-slot "
            "conv / recurrent state, and rollback restores pages only",
        "mesh": _NO_SPECS, "share": _NO_SHARE, "migrate": _NO_STATE}),
    "rings": (_LAYERS, {
        "kv_int8": "--kv-dtype int8: the window layers' rings hold bfloat16 "
            "rows and no scale planes (ROADMAP B-M2)"}),
    "parallel": (_LAYERS, {
        "spec": "--spec: a rejected draft has already advanced the mixer's "
            "convolution window and recurrent state, and rollback restores "
            "pages only (ROADMAP B-M5)",
        "mesh": "--tp / --ep: the mixer's weights (heads and B/C groups) and "
            "its per-slot state have no partition specs (ROADMAP B-M5)",
        "kv_int8": "--kv-dtype int8: the layer's K/V pages could be scaled, "
            "the mixer's float32 state beside them has no such form and the "
            "pair has not been measured (ROADMAP B-M5)",
        "prefix_cache": "--prefix-cache: a cached page holds K and V of its "
            "tokens, not the mixer's state at its boundary (ROADMAP B-M5)",
        "share": _NO_SHARE, "migrate": _NO_STATE}),
    "sparse": (_LAYERS, {
        "spec": "--spec: a rejected draft has already advanced the lightning "
            "layers' state and may have written a pooled key, and rollback "
            "restores pages only (ROADMAP B-M10)",
        "mesh": "--tp / --ep: the pooled-key pool, the block lists (one a kv "
            "group) and the lightning state have no partition specs (ROADMAP "
            "B-M10)",
        "kv_int8": "--kv-dtype int8: a pooled key is the mean of bfloat16 K "
            "rows, and the sparse walk has not been measured over scale "
            "planes (ROADMAP B-M10)",
        "prefix_cache": "--prefix-cache: a cached page holds K and V of its "
            "tokens, not its pooled keys nor the lightning state at its "
            "boundary (ROADMAP B-M10)",
        "share": _NO_SHARE, "migrate": _NO_STATE}),
    "hybrid": (_LAYERS, {
        "spec": "--spec: a verify span reads a logit at every draft position, "
            "and this stack's upper layers run one sampled row a sequence; a "
            "rejected draft has also advanced the scan state and the rings "
            "(ROADMAP B-M9)",
        "mesh": "--tp / --ep: the scan's per-channel state and weights and "
            "the rings have no partition specs (ROADMAP B-M9)",
        "kv_int8": "--kv-dtype int8: the one pool layer is read by every "
            "cross layer and the rings hold bfloat16 rows; neither has been "
            "measured with scale planes (ROADMAP B-M9)",
        "prefix_cache": "--prefix-cache: a cached page holds the full layer's "
            "K and V, not the scan state or the rings at its boundary "
            "(ROADMAP B-M9)",
        "share": _NO_SHARE, "migrate": _NO_STATE}),
    "latent": ("latent attention (kv_lora_rank)", {
        "kv_int8": "--kv-dtype int8: the page writer's scales are one a kv "
            "head and a latent row has no heads",
        "weights_int8": "--weights-dtype int8: the low-rank projections are "
            "absorbed into q and the output in bfloat16",
        "prefix_cache": "--prefix-cache: the radix tree shares K and V pages, "
            "not latent and index-key pages",
        "mesh": "--tp / --ep: the latent and index-key pools and the low-rank "
            "projections have no partition specs",
        "migrate": "a migrated stream carries K and V pages, not latent and "
            "index-key pages; replay the request instead"}),
    "streams": ("a residual path of {streams} streams (hc_mult)", {
        "spec": "--spec: a verify span's logits are read through the streams' "
            "read-out at every draft position, which has not been held to the "
            "reference (ROADMAP B-M12)",
        "mesh": "--tp / --ep: how [tokens, streams, hidden] and the mapping "
            "product's weights are sharded is not decided (ROADMAP B-M12)"}),
}


def state_held(cfg: ModelConfig) -> List[str]:
    """The rows of STATE_REFUSES `cfg` falls under, in the order a start asks
    them: its per-slot state by family (sparse, parallel or a mamba stack
    whatever else it holds; else its rings too where it has window layers),
    the latent pools, several streams. []: K and V pages only."""
    held = [k for k in STATE_KINDS if cfg.count(k)]
    if cfg.count(SPARSE):
        rows = ["sparse"]
    elif held == [PARALLEL] or MAMBA in held:
        rows = ["hybrid" if MAMBA in held else "parallel"]
    else:
        rows = (["window" if held == [WINDOW] else "conv / linear"]
                + ["rings"] * (WINDOW in held)) * bool(held)
    return (rows + ["latent"] * bool(cfg.kv_lora_rank)
            + ["streams"] * bool(cfg.streams))


def _cells(cfg: ModelConfig):
    """(feature, what the model has, why it is not served with it): every
    cell of the rows `cfg` falls under, in the order a start asks them."""
    held = [k for k in STATE_KINDS if cfg.count(k)]
    words = dict(held=" and ".join(held), kinds=" / ".join(held),
                 streams=cfg.streams,
                 rings=" (the rings: ROADMAP B-M2)" * (WINDOW in held))
    for row in state_held(cfg):
        has, cells = STATE_REFUSES[row]
        for feature, why in cells.items():
            yield feature, has.format(**words), why.format(**words)


def unserved(cfg: ModelConfig, feature: str) -> Optional[str]:
    """Why `feature` is not served with what `cfg` holds (its first cell
    among the model's rows); None: it is."""
    return next((why for f, _, why in _cells(cfg) if f == feature), None)


def refusal(cfg: ModelConfig, *, spec: bool = False, mesh_shape=None,
            kv_dtype: str = "bfloat16", weights_dtype: str = "bfloat16",
            prefix_cache: bool = False) -> Optional[str]:
    """What `cfg` cannot be served with among these flags, told BEFORE any
    device work: one line (None: it can). Each refused feature knows pages
    of K and V only; run on such a model it would serve them without the
    state beside them. The CLI and every ModelRuntime ask this function."""
    shape = dict(mesh_shape or {})
    on = {"spec": spec, "kv_int8": kv_dtype != "bfloat16",
          "weights_int8": weights_dtype != "bfloat16",
          "prefix_cache": prefix_cache,
          "mesh": shape.get("tensor", 1) > 1 or shape.get("expert", 1) > 1}
    return next((f"model {cfg.name} has {has} and cannot be served with {why}"
                 for feature, has, why in _cells(cfg) if on.get(feature)),
                None)


class SeqCache:
    """The one owner of a runtime's per-sequence device state and of the host
    books kept on it: the paged pool (`kc`, `vc`: K and V, or the latent and
    index-key pools), the per-slot state of the layers that keep one
    (`slot_state`), the page allocator and the radix tree over it (`alloc`,
    `prefix_cache`), the pages a slot holds (`slot_pages`; the tree's pinned
    nodes LEAD them, `slot_pins`) and the row decode writes through
    (`page_table`). A ModelRuntime builds one and hands `kc`, `vc` and
    `slot_state` to every step program, donated, and takes them back. What
    is decided about pages is journalled here, once, through `record` (the
    runtime's `_jrec`); `blocked` is the fault plan's allocation seam."""

    def __init__(self, name: str, model_cfg: ModelConfig,
                 engine_cfg: EngineConfig, *, max_span: int,
                 dtype=jnp.bfloat16, sharding=None, device=None,
                 record: Optional[Callable] = None,
                 blocked: Optional[Callable] = None):
        from ollamamq_tpu.engine import step_work
        from ollamamq_tpu.models import llama
        from ollamamq_tpu.telemetry import schema as tm

        self.name, self.cfg, self.ecfg = name, model_cfg, engine_cfg
        self.record = record or (lambda kind, req=None, **fields: None)
        self.blocked = blocked or (lambda site: False)
        self.kc, self.vc = alloc_kv_pool(
            model_cfg, engine_cfg, sharding, dtype,
            kv_dtype=engine_cfg.kv_dtype)
        # The per-slot state (fixed size, no pages: a llama.SlotState — a
        # window layer's ring is `ring_rows` rows a slot whatever the context,
        # and the pool then holds the FULL layers only), None for a model
        # without such layers. Never reset from the host: a request's first
        # span opens its slot's rows at zero inside the program (`is_first`).
        self.slot_state = llama.alloc_slot_state(
            model_cfg, engine_cfg.max_slots, dtype,
            ring_rows=model_cfg.ring_rows(max_span, engine_cfg.page_size),
            pooled_rows=model_cfg.pooled_rows(engine_cfg.num_pages,
                                              engine_cfg.page_size))
        if device is not None:  # where the weights are held: ModelRuntime
            self.kc, self.vc, self.slot_state = jax.device_put(
                (self.kc, self.vc, self.slot_state), device)
        self.alloc = PageAllocator(
            engine_cfg.num_pages, engine_cfg.page_size,
            engine_cfg.max_pages_per_seq)
        # Automatic prefix caching: host-side radix tree of finished prompts'
        # full KV pages (engine/prefix_cache.py; under SPMD only the primary's
        # admission walks it). A cached page holds K and V of its tokens, not
        # the per-slot state at its boundary: a model with such state has no
        # prefix cache yet (a preempted request replays from token 0).
        self.prefix_cache = None
        unshared = unserved(model_cfg, "share")
        if engine_cfg.prefix_cache and unshared:
            log.warning("%s: %s", name, unshared)
        elif engine_cfg.prefix_cache:
            from ollamamq_tpu.engine.prefix_cache import PrefixCache

            self.prefix_cache = PrefixCache(
                engine_cfg.page_size, self.alloc, model=name,
                min_pages=engine_cfg.prefix_cache_min_pages)
        S, MP = engine_cfg.max_slots, engine_cfg.max_pages_per_seq
        self.slot_pages: List[List[int]] = [[] for _ in range(S)]
        self.slot_pins: List[list] = [[] for _ in range(S)]
        self.page_table = np.full((S, MP), TRASH_PAGE, np.int32)

        # What a deployment is sized by: the pool (the HBM density
        # scoreboard's KV side), the fixed per-slot state, and what each
        # token of context adds to the pool.
        self.kv_bytes = sum(
            x.size * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves((self.kc, self.vc)))
        tm.HBM_KV_BYTES.labels(model=name).set(self.kv_bytes)
        self.state_bytes = step_work.state_bytes(self.slot_state, name)
        if self.slot_state is not None:
            log.info("%s: per-slot state %.1f MB for %d slots, whatever the "
                     "context (%s) beside the pool's %.1f MB (%d paged "
                     "layers)", name, sum(self.state_bytes.values()) / 1e6, S,
                     ", ".join(f"{k} {n / 1e6:.1f} MB"
                               for k, n in self.state_bytes.items() if n),
                     self.kv_bytes / 1e6, model_cfg.cache_layers)
        tm.KV_BYTES_PER_TOKEN.labels(model=name).set(
            kv_page_bytes(model_cfg, 1, jnp.dtype(dtype).itemsize,
                          engine_cfg.kv_dtype))
        if model_cfg.kv_lora_rank:  # the two pools (both are in kv_bytes)
            for gauge, pool in ((tm.HBM_LATENT_POOL_BYTES, self.kc),
                                (tm.HBM_INDEX_POOL_BYTES, self.vc)):
                gauge.labels(model=name).set(pool.nbytes)
            log.info("%s: latent pool %s %.1f MB, index-key pool %s %.1f MB",
                     name, self.kc.shape, self.kc.nbytes / 1e6,
                     self.vc.shape, self.vc.nbytes / 1e6)

    def drop(self) -> None:
        """Give the pool's HBM back (a failed runtime, before its
        replacement loads)."""
        self.kc = self.vc = None

    # -- pages of a slot ---------------------------------------------------
    def page_state(self) -> dict:
        """Allocator post-state for page events: the inputs the
        pages-conserved invariant (free+used+cached==pool) checks."""
        a = self.alloc
        return {"free": a.free_pages, "used": a.used_pages,
                "cached": a.cached_pages, "pool": a.num_pages - 1}

    def _evict(self, short: int) -> bool:
        """The eviction backstop: the free list came `short` pages short, so
        reclaim that many unreferenced cached pages (LRU). True: some came."""
        if self.prefix_cache is None or short <= 0:
            return False
        freed = self.prefix_cache.evict(short)
        if freed > 0:
            self.record("page_evict", n=freed, **self.page_state())
        return freed > 0

    def alloc_pages(self, num_tokens: int,
                    held: Optional[int] = None) -> Optional[List[int]]:
        """Pages to hold `num_tokens`, with the eviction backstop; None when
        the pool (or the per-sequence cap) cannot give them. `held` None: a
        fresh run, the one allocation the fault plan's "alloc" seam is asked
        about; a number: the private tail behind that many shared pages of a
        cache hit, or a migrated stream's run (0)."""
        if held is None and self.blocked("alloc"):
            return None  # injected allocation pressure
        held = held or 0
        need = self.alloc.pages_needed(num_tokens) - held
        pages = self.alloc.alloc_n(need, held=held)
        if pages is None and self._evict(need - self.alloc.free_pages):
            pages = self.alloc.alloc_n(need, held=held)
        if pages is not None:
            self.record("page_alloc", n=len(pages), **self.page_state())
        return pages

    def extend(self, slot: int, new_total_tokens: int) -> bool:
        """Decode-time growth of `slot`'s run to cover `new_total_tokens`,
        with the eviction backstop (past the per-sequence cap it cannot
        help). A run that could not grow whole keeps the pages it got."""
        if self.blocked("extend"):
            return False  # injected allocation pressure
        pages, a = self.slot_pages[slot], self.alloc
        before = len(pages)
        grown = a.extend(pages, new_total_tokens)
        if not grown:
            need = a.pages_needed(new_total_tokens) - len(pages)
            grown = (0 < need <= a.max_pages_per_seq - len(pages)
                     and self._evict(need - a.free_pages)
                     and a.extend(pages, new_total_tokens))
        if grown and len(pages) > before:
            self.record("page_alloc", n=len(pages) - before,
                        **self.page_state())
        return grown

    def publish(self, slot: int) -> None:
        """Write `slot`'s pages into the row decode writes through."""
        self.page_table[slot, :] = make_page_table_row(
            self.slot_pages[slot], self.ecfg.max_pages_per_seq)

    def admit(self, slot: int, tokens: List[int]) -> Optional[int]:
        """Give `slot` the whole run of an admitted prompt — its tokens and
        the first it samples — behind its longest cached prefix (at the reuse
        threshold or over it): the tokens already cached (0: a miss), or
        None while the pool cannot give the rest. The row stays OFF the page
        table until `publish`: a reserved slot's points at the trash page."""
        pc = self.prefix_cache
        nodes, shared = pc.match(tokens) if pc is not None else ([], [])
        if pc is not None and len(nodes) < pc.min_pages:
            nodes, shared = [], []
        if nodes:
            # Pin BEFORE the tail allocation: its eviction backstop must
            # never reclaim the very pages we matched.
            pc.pin(nodes)
        pages = self.alloc_pages(len(tokens) + 1,
                                 held=len(shared) if nodes else None)
        if pages is None:
            if nodes:
                pc.release(nodes)
            return None
        self.slot_pins[slot] = list(nodes)
        self.slot_pages[slot] = list(shared) + pages
        cached = len(shared) * self.ecfg.page_size
        if nodes:
            pc.note_hit(cached)
        elif pc is not None:
            pc.note_miss()
        return cached

    def release(self, slot: int, req=None) -> None:
        """Free a slot's pages and reset its page-table row. With the prefix
        cache on the slot's pins are released, and when the finishing
        request is known (`req`: the slot was installed, so its prompt's KV
        is fully written) its full prompt pages MERGE into the tree instead.
        Without one (mid-prefill cancel, a failure) every private page goes
        back to the free list."""
        pages, pc = self.slot_pages[slot], self.prefix_cache
        keep = len(self.slot_pins[slot])  # shared tree pages lead the run
        if pc is not None and req is not None and req.prompt_tokens:
            full = min(len(req.prompt_tokens) // self.ecfg.page_size,
                       len(pages))
            if full > keep:
                pc.insert(req.prompt_tokens, pages[:full])
                keep = full
        n_freed = len(pages) - keep
        self.alloc.free(pages[keep:])
        if pc is not None:
            pc.release(self.slot_pins[slot])
        self.slot_pages[slot], self.slot_pins[slot] = [], []
        if n_freed > 0:
            self.record("page_free", n=n_freed, slot=slot,
                        **self.page_state())
        self.page_table[slot, :] = TRASH_PAGE

    def rollback(self, slot: int, req, kv_before: int, kv_after: int,
                 source: str) -> int:
        """Release the page claim of rejected drafts: the slot keeps the
        pages its ACCEPTED context needs, never fewer than the tree's shared
        ones that lead its run. Nothing is un-written on the device: the
        positions sit past the rolled-back length, masked and overwritten."""
        freed = self.alloc.rollback_to(self.slot_pages[slot], kv_after,
                                       keep=len(self.slot_pins[slot]))
        if freed:
            self.publish(slot)
        self.record("spec_rollback", req, slot=slot, kv_before=kv_before,
                    kv_after=kv_after, freed=freed, source=source,
                    **self.page_state())
        return freed

    # -- wire --------------------------------------------------------------
    def header(self, kind: str) -> dict:
        """What a blob of `kind` ("stream", "prefix") says of its pool."""
        return {"version": 1, "kind": kind, "model": self.name,
                "kv_dtype": self.ecfg.kv_dtype,
                "page_size": self.ecfg.page_size,
                "num_layers": self.cfg.paged_layers,
                "num_kv_heads": self.cfg.num_kv_heads,
                "head_dim": self.cfg.head_dim}

    def accepts(self, blob: dict, kind: str) -> bool:
        """Were `blob`'s pages gathered from a pool shaped as this one?"""
        mine = self.header(kind)
        return (blob.get("kind") == kind
                and blob.get("kv_dtype") == mine["kv_dtype"]
                and all(int(blob.get(f, -1)) == mine[f] for f in (
                    "page_size", "num_layers", "num_kv_heads", "head_dim")))

    def gather(self, pages: List[int]) -> dict:
        return gather_page_run(self.kc, self.vc, pages, self.ecfg.page_size,
                               self.cfg.head_dim)

    def scatter(self, pages: List[int], blob: dict) -> None:
        self.kc, self.vc = scatter_page_run(
            self.kc, self.vc, pages, self.ecfg.page_size, blob)

    def install(self, slot: int, blob: dict) -> bool:
        """Land a migrated stream's pages in a same-length run of this pool,
        `slot`'s, row published; False when the pool cannot give the run."""
        n = int(blob["n_pages"])
        if n <= 0 or n > self.alloc.max_pages_per_seq:
            return False
        pages = self.alloc_pages(n * self.ecfg.page_size, held=0)
        if pages is None:
            return False
        self.scatter(pages, blob)
        self.slot_pages[slot], self.slot_pins[slot] = pages, []
        self.publish(slot)
        return True

    def export_prefix(self, tokens: List[int]):
        """Affinity-miss prefix shipping, source side: the longest cached
        full-page prefix of `tokens` as a wire blob (pages pinned only
        for the device->host copy). None when nothing caches."""
        pc = self.prefix_cache
        nodes, pages = pc.match(list(tokens)) if pc is not None else ([], [])
        if not pages:
            return None
        pc.pin(nodes)
        try:
            data = self.gather(pages)
        finally:
            pc.release(nodes)
        n = len(pages) * self.ecfg.page_size
        return {**self.header("prefix"), "n_pages": len(pages),
                "prefix_tokens": [int(t) for t in tokens[:n]], **data}

    def import_prefix(self, blob: dict) -> int:
        """...target side: land shipped pages in this pool and merge them
        into the radix tree, so the request admitted next prefills only the
        tail. Plain alloc_n (no eviction backstop): a shipped prefix never
        evicts locally-earned cache. Returns pages adopted (0 = no-op)."""
        pc = self.prefix_cache
        if pc is None or not self.accepts(blob, "prefix"):
            return 0
        n = int(blob["n_pages"])
        pages = self.alloc.alloc_n(n) if n > 0 else None
        if pages is None:
            return 0
        self.record("page_alloc", n=n, **self.page_state())
        self.scatter(pages, blob)
        return pc.insert([int(t) for t in blob["prefix_tokens"]], pages)
