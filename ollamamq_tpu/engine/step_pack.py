"""One upload a step: a step program's host inputs as ONE int32 array.

A step program (`mq_ragged_step`, `mq_decode_scan`) takes two dozen
small host-made arrays — the token stream, per-row span bookkeeping,
page-table rows, sampling parameters — plus an RNG key.
Handing each to the jitted call separately costs a host→device transfer
apiece (and, for the key, two eager device programs) in every step of
every model. Here they are fields of one buffer instead:

  * a `StepLayout` is a static table `name -> (offset, shape, dtype)`
    over one flat int32 array, fixed by the shapes the engine already
    keys its jits on (`ragged_layout`, `decode_layout`);
  * on the host, `new()` gives a fresh buffer holding each field's
    padding value and `views()` / `view()` give numpy views into it,
    which the composition writes directly — float fields through a
    float32 view of the same words;
  * inside the program, `unpack()` opens the one argument with static
    slices, reshapes and `lax.bitcast_convert_type`: no arithmetic, so
    every value arrives bit for bit.

A buffer is written once, between `new()` and its launch, and never
after: the transfer may alias host memory, and the step may still be
running when the host composes the next one.

The last field of every layout, `rng`, is the step's RNG counter; the
program builds its key from it with `jax.random.PRNGKey` — the same key
bits the eager call gave, with nothing dispatched ahead of the step.
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

import jax.numpy as jnp
from jax import lax

from ollamamq_tpu.engine import kv_cache as kvc

# The per-row sampling parameters, in the order `sampling_flags` and the
# sampling epilogue take them (every layout has them under these names).
SAMPLING = ("temp", "top_k", "top_p", "pen", "pres", "freq")


class StepLayout:
    """Field table of one step program's packed input. `fields` is a
    sequence of `(name, shape, dtype, fill)` with dtype int32 or float32;
    `fill` is what a row or token nobody writes holds (the padding)."""

    def __init__(self, fields):
        self.names: Tuple[str, ...] = tuple(f[0] for f in fields)
        self._table = {}
        off = 0
        for name, shape, dtype, _fill in fields:
            dtype = np.dtype(dtype)
            if dtype not in (np.dtype(np.int32), np.dtype(np.float32)):
                raise ValueError(f"{name}: only 32-bit fields pack ({dtype})")
            n = int(np.prod(shape, dtype=np.int64))
            self._table[name] = (off, n, tuple(shape), dtype)
            off += n
        self.size = off
        self._template = np.zeros(off, np.int32)
        for name, _shape, _dtype, fill in fields:
            if fill:
                self.view(self._template, name)[...] = fill

    def new(self) -> np.ndarray:
        """A fresh buffer, every field at its padding value."""
        return self._template.copy()

    def view(self, buf: np.ndarray, name: str) -> np.ndarray:
        """Field `name` of a host buffer, as a view of its words."""
        off, n, shape, dtype = self._table[name]
        return buf[off:off + n].view(dtype).reshape(shape)

    def views(self, buf: np.ndarray) -> Tuple[np.ndarray, ...]:
        """Every field of a host buffer, in table order."""
        return tuple(self.view(buf, name) for name in self.names)

    def sampling(self, buf: np.ndarray) -> Tuple[np.ndarray, ...]:
        """The SAMPLING fields of a host buffer (`sampling_flags`' input)."""
        return tuple(self.view(buf, name) for name in SAMPLING)

    def unpack(self, buf) -> Tuple[jnp.ndarray, ...]:
        """Every field of a TRACED buffer, in table order: static slices,
        reshapes and bitcasts only."""
        out = []
        for name in self.names:
            off, n, shape, dtype = self._table[name]
            x = lax.slice(buf, (off,), (off + n,))
            if dtype != np.dtype(np.int32):
                x = lax.bitcast_convert_type(x, dtype)
            out.append(x.reshape(shape))
        return tuple(out)


def _sampling(n: int) -> list:
    """temp, top_k, top_p, repeat / presence / frequency penalty, seed."""
    return [("temp", (n,), np.float32, 0.0), ("top_k", (n,), np.int32, 0),
            ("top_p", (n,), np.float32, 1.0), ("pen", (n,), np.float32, 1.0),
            ("pres", (n,), np.float32, 0.0), ("freq", (n,), np.float32, 0.0),
            ("seeds", (n,), np.int32, 0)]


_RNG = [("rng", (1,), np.int32, 0)]


@functools.lru_cache(maxsize=64)
def ragged_layout(T_pad: int, S: int, MP: int, W: int) -> StepLayout:
    """`mq_ragged_step`: the flattened token stream, then the per-row
    fields. Padding tokens sit at position -1 and write the trash page's
    slot 0; a padding row has no tokens (`q_start` past the stream), the
    trash ring row `S` and trash pages. The draft cap is not part of it:
    a plain step carries `is_spec` all zero. `next_tok` is the prompt token
    that FOLLOWS a span which ends inside its prompt (a model with a
    prediction module reads it; every other row's successor is sampled in
    the program)."""
    i32 = np.int32
    return StepLayout(
        [("tokens", (T_pad,), i32, 0), ("tok_seq", (T_pad,), i32, 0),
         ("tok_pos", (T_pad,), i32, -1), ("write_slots", (T_pad,), i32, 0),
         ("q_start", (S,), i32, T_pad), ("q_len", (S,), i32, 0),
         ("kv_len", (S,), i32, 0), ("ring_len", (S,), i32, 0),
         ("is_first", (S,), i32, 0), ("append", (S,), i32, 0),
         ("is_spec", (S,), i32, 0), ("next_tok", (S,), i32, 0),
         ("seed_rows", (S, W), i32, -1),
         ("slot_ids", (S,), i32, S), ("pt", (S, MP), i32, kvc.TRASH_PAGE)]
        + _sampling(S) + _RNG)


@functools.lru_cache(maxsize=8)
def decode_layout(S: int, MP: int) -> StepLayout:
    """`mq_decode_scan`: a row a slot, whatever k."""
    i32 = np.int32
    return StepLayout(
        [("tokens", (S,), i32, 0), ("positions", (S,), i32, 0),
         ("active", (S,), i32, 0), ("pt", (S, MP), i32, kvc.TRASH_PAGE)]
        + _sampling(S) + _RNG)

