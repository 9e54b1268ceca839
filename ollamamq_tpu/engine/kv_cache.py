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
import struct
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ollamamq_tpu.config import EngineConfig, ModelConfig

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
        free list). Returns the number of pages freed.

        The device-side "un-write" is free: rejected draft positions sit
        past the rolled-back kv_len, so attention masks them out and the
        next real decode step overwrites them — only the host-side page
        claim needs releasing."""
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

    if model_cfg.kv_lora_rank:
        # Latent attention: the latent pool and the index-key pool where K
        # and V were — same pages, same table, rows of another width each
        # (no indexer: the second has no lanes, and no bytes); one more
        # layer where the model has a prediction module (its block's rows).
        return tuple(filled(0, shape[:2] + (lanes,), dtype)
                     for lanes in model_cfg.kv_row_dims)

    if kv_dtype == "int8":
        sshape = shape[:2] + (model_cfg.num_kv_heads,)  # [L, S, Hk]
        return tuple(QuantKV(filled(0, shape, jnp.int8),
                             filled(1, sshape, jnp.float32))
                     for _ in range(2))
    # K rows and V rows, each at its own width (`kv_row_dims`: alike for
    # every model but one whose value heads are narrower than its key heads)
    return tuple(filled(0, shape[:2] + (lanes,), dtype)
                 for lanes in model_cfg.kv_row_dims)


def kv_pool_bytes(model_cfg: ModelConfig, engine_cfg: EngineConfig,
                  bytes_per_el=2, kv_dtype: str = "bfloat16") -> int:
    """Planning-time pool size; int8 pools count 1 payload byte plus the
    4-byte fp32 scale each (slot, head) row carries."""
    return engine_cfg.num_pages * kv_page_bytes(
        model_cfg, engine_cfg.page_size, bytes_per_el, kv_dtype)


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
