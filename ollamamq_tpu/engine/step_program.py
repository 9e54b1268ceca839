"""The pipelined loop's two step programs, built from what they read.

`ragged_step` and `decode_scan` each return the `jax.jit` object of one
program — `mq_ragged_step`, `mq_decode_scan`: the names a trace, the
benchmark's readers and `window_compiles` find them by — for a model
configuration, the four numbers of the engine's configuration a program's
shapes depend on (`StepDims`), the step's static shape and its trace-time
sampling flags. Nothing here reads a runtime: `ModelRuntime._get_ragged_jit`
/ `_get_decode_jit` keep the per-runtime compile ledger and call these, and
a tool or a test that wants a program lowered calls them too
(`scripts/step_hlo_copies.py:step_args` builds the abstract arguments).

Both builders are memoised on their whole argument tuple: a second runtime
of one configuration on the same devices is handed the SAME jit object, so
jax's own cache serves it. `fresh=True` builds a program anew (a fired
`compile` fault: the next launch re-traces), `forget_config` drops a
configuration's (`TPUEngine.evict_model`: its executables go with it).
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

from ollamamq_tpu.engine import step_pack
from ollamamq_tpu.models import llama, moe
from ollamamq_tpu.ops.sampling import (accept_prefix, maybe_apply_penalties,
                                       per_row_keys, sample_tokens_rowwise)


class StepDims(NamedTuple):
    """What of an `EngineConfig` a step program's shapes depend on."""
    page_size: int
    max_slots: int
    max_pages_per_seq: int
    repeat_last_n: int

    @classmethod
    def of(cls, engine_cfg) -> "StepDims":
        return cls(*(getattr(engine_cfg, f) for f in cls._fields))

    def ragged_layout(self, T_pad: int) -> step_pack.StepLayout:
        return step_pack.ragged_layout(T_pad, self.max_slots,
                                       self.max_pages_per_seq,
                                       self.repeat_last_n)

    def decode_layout(self) -> step_pack.StepLayout:
        return step_pack.decode_layout(self.max_slots,
                                       self.max_pages_per_seq)


# (builder's name, its arguments) -> the jit object it returned.
_BUILT: Dict[tuple, object] = {}


def _memoised(build):
    @functools.wraps(build)
    def builder(*args, fresh: bool = False, **kw):
        key = (build.__name__, *args, *sorted(kw.items()))
        if fresh or key not in _BUILT:
            _BUILT[key] = build(*args, **kw)
        return _BUILT[key]

    return builder


def forget_config(cfg) -> None:
    """Drop every program built for `cfg`."""
    for key in [k for k in _BUILT if k[1] == cfg]:
        del _BUILT[key]


def _carried_ids(tokens, last_ids):
    """A token < 0 is -1 - r: "the id row r of the step before this one
    sampled", read from the `last_ids` carry (that step may still be
    running; the host has not seen the id)."""
    return jnp.where(
        tokens < 0,
        last_ids[jnp.clip(-1 - tokens, 0, last_ids.shape[0] - 1)], tokens)


def _sample(flags, logits, ring, key, seeds, pos, temp, tk, tp, pen, pres,
            freq, ahead: int = 0):
    """One id a row: the penalties over the row's `ring` of recent ids,
    then the row's own key — seeded streams fold in the position of the
    token being SAMPLED, `pos + ahead` — and the sampler."""
    need_pen, need_mask, need_sample = flags
    pen_logits = maybe_apply_penalties(logits, ring, pen, pres, freq,
                                       need_pen)
    row_keys = per_row_keys(key, seeds, pos + ahead if ahead else pos)
    return sample_tokens_rowwise(pen_logits, row_keys, temp, tk, tp,
                                 need_mask, need_sample)


@_memoised
def ragged_step(cfg, dims: StepDims, T_pad: int, k_cap: int, flags, *,
                attn_impl: str, mesh=None, mtp: bool = False):
    """ONE mixed-batch step: forward the flattened [T_pad] token
    stream (prefill spans + decode tokens + speculative verify
    spans) through forward_ragged, then per-sequence penalty-ring
    maintenance and sampling. Compiles once per
    (padded token total, draft cap, sampling flags); the engine pads
    totals to the token granule and uses only k_cap in {0, spec_k},
    so the variant count stays small.

    Speculative rows (is_spec=1) carry a (d+1)-token span
    [last_token, draft_1..draft_d]: the forward reads a logit at
    EVERY span position, greedy verification accepts the longest
    prefix where draft == argmax (ops/sampling.accept_prefix), the
    model's own next token caps the emission, and the penalty ring
    advances by the ACCEPTED count — never by k — so ring state is
    byte-identical to emitting the same tokens one step at a time.
    Returns (toks [S, k_cap+1], n_emit [S], caches', recent',
    last_ids', conv'): row i emits toks[i, :n_emit[i]], the carry is
    now this step's last id of every row, and each row's slot of the
    per-slot state (`conv`: a llama.SlotState, None for a model that keeps
    none) holds its span's last positions and, for linear-attention layers,
    the rule's state after the span (opened at zero where the span is
    its request's first: models/llama.py:forward_ragged). An MoE model's `toks` has three more
    rows: the pass's expert-load counters (moe.LOAD_STATS) ride back
    with the ids, in the transfer the collect makes anyway.

    A runtime whose proposer is the model's prediction module (`mtp`)
    takes and returns two more carries, [S + 1] by slot. `drafts`: a
    spec row's draft is read from it into the stream (the host wrote a
    placeholder), and after the trunk the module runs over the whole
    stream — each position with the token that follows it: the next of
    its span, what the trunk chose at a verify span's positions, the id
    just sampled at a row's last, `next_tok` where a span ends inside
    its prompt — and leaves at each row's slot its prediction of the
    token after next, read at the row's last ACCEPTED position.
    `lens`: each slot's length, the position of its next input token.
    The host does not know it while a verify span is unsettled, so a
    decode or verify row comes marked "from the carry" — `kv_len` < 0,
    and `tok_pos` -2 - j at the span's j-th token — and the program
    derives its positions `lens[slot] + j`, its write slots through the
    row's page-table row and `kv_len = lens[slot] + q_len`; a prompt's
    span comes host-written as ever. Every row leaves its slot's new
    length: a span its end, a decode row one more, a verify span
    `n_emit` more."""
    ps = dims.page_size
    O = k_cap + 1
    lay = dims.ragged_layout(T_pad)

    def mq_ragged_step(params, buf, kc, vc, recent, last_ids, conv,
                       drafts=None, lens=None):
        (tokens, tok_seq, tok_pos, write_slots, q_start, q_len,
         kv_len, ring_len, is_first, append, is_spec, next_tok,
         seed_rows, slot_ids, pt, temp, tk, tp, pen, pres, freq,
         seeds, rng) = lay.unpack(buf)
        if mtp:
            # Rows from the carry: where they are is the device's
            # to say (the step before may still be running).
            start = lens[slot_ids]
            kv_len = jnp.where(kv_len < 0, start + q_len, kv_len)
            pos = start[tok_seq] - 2 - tok_pos
            MP = pt.shape[1]
            page = pt.reshape(-1)[
                tok_seq * MP + jnp.clip(pos // ps, 0, MP - 1)]
            carried = tok_pos < -1
            write_slots = jnp.where(carried, page * ps + pos % ps,
                                    write_slots)
            tok_pos = jnp.where(carried, pos, tok_pos)
        key = jax.random.PRNGKey(rng[0])
        tokens = _carried_ids(tokens, last_ids)
        spec = is_spec > 0
        if mtp:
            # A spec row's one draft: the module's, from the carry
            # (a row that is no spec row writes past the stream).
            tokens = tokens.at[
                jnp.where(spec, q_start + 1, T_pad)
            ].set(drafts[slot_ids], mode="drop")
        # Logit read positions: non-spec rows read only their
        # last valid token (every column aliases it — prefill
        # spans can be longer than O); spec rows read every span
        # position, so column j holds the argmax that verifies
        # draft j+1 (and column `accepted` the bonus token).
        j = jnp.arange(O)[None, :]
        col = jnp.where(spec[:, None],
                        jnp.minimum(j, q_len[:, None] - 1),
                        q_len[:, None] - 1)
        out_idx = jnp.clip(q_start[:, None] + col, 0, T_pad - 1)
        logits, kc, vc, *rest = llama.forward_ragged(
            params, cfg, tokens, tok_seq, tok_pos, write_slots,
            out_idx, kc, vc, pt, q_start, q_len, kv_len, ps,
            attn_impl=attn_impl, mesh=mesh,
            moe_load=bool(cfg.num_experts), conv_state=conv,
            slot_ids=slot_ids, is_first=is_first, hidden=mtp,
            emits=append,
        )  # [S, O, V]
        if mtp:
            *rest, hidden = rest
        if conv is not None:
            conv, *rest = rest
        load = rest
        greedy_all = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        last_logits = logits[:, -1, :]
        if k_cap > 0:
            # Draft token j+1 sits in the stream right after the
            # span's input token; its verifier is greedy column j.
            jj = jnp.arange(k_cap)[None, :]
            draft_idx = jnp.clip(q_start[:, None] + 1 + jj, 0,
                                 T_pad - 1)
            accepted = accept_prefix(tokens[draft_idx],
                                     greedy_all[:, :k_cap],
                                     q_len - 1)
            accepted = jnp.where(spec, accepted, 0)
        else:
            accepted = jnp.zeros(q_start.shape[0], jnp.int32)
        W = recent.shape[1]
        rows = recent[slot_ids]  # [B, W]
        # First span of a request: the ring opens from seed_rows
        # (all -1 fresh, the cached prefix's last W tokens on a
        # prefix-cache hit).
        rows = jnp.where(is_first[:, None] > 0, seed_rows, rows)
        # Slide each ring by roll_n tokens taken from the row's
        # own stream span: span length for prefill rows, 0 for
        # plain decode rows (their input token already rolled in
        # when it was sampled), and the ACCEPTED count for spec
        # rows — whose rolled tokens start one past the span's
        # input token (the accepted drafts). new[j] is
        # (rows ++ rolled)[roll_n + j] kept to the last W.
        roll_n = jnp.where(spec, accepted, ring_len)
        base = q_start + spec.astype(jnp.int32)
        j_w = jnp.arange(W)[None, :]
        cidx = roll_n[:, None] + j_w - W  # offset into the span
        stream_idx = jnp.clip(base[:, None] + cidx, 0, T_pad - 1)
        from_stream = tokens[stream_idx]  # [B, W]
        row_idx = jnp.clip(roll_n[:, None] + j_w, 0, W - 1)
        from_row = jnp.take_along_axis(rows, row_idx, axis=1)
        new_rows = jnp.where(cidx >= 0, from_stream, from_row)
        # kv_len IS the position being sampled in both shapes:
        # n for a span ending a prompt of n tokens (prefill
        # folded seq_lens) and positions+1 for a decode row.
        tok = _sample(flags, last_logits, new_rows, key, seeds, kv_len,
                      temp, tk, tp, pen, pres, freq)
        if k_cap > 0:
            # Spec rows take the model's own token at the first
            # rejected position (or past the last accepted draft)
            # — exactly the token non-speculative greedy would
            # sample next. Speculation is host-gated to greedy
            # no-penalty rows, so raw argmax IS that token.
            spec_next = jnp.take_along_axis(
                greedy_all, accepted[:, None], axis=1)[:, 0]
            tok = jnp.where(spec, spec_next, tok)
        # Rows that EMIT (decode/spec rows, final prefill spans)
        # roll the final token in; mid-prefill spans do not.
        appended = jnp.concatenate([new_rows[:, 1:], tok[:, None]],
                                   axis=1)
        final_rows = jnp.where(append[:, None] > 0, appended,
                               new_rows)
        recent = recent.at[slot_ids].set(final_rows)
        # Emitted tokens, row-major: spec rows emit the accepted
        # drafts (greedy columns 0..accepted-1 — accepted drafts
        # ARE their verifying argmaxes) plus the bonus token at
        # column `accepted`; every other row emits column 0.
        n_emit = jnp.where(spec, accepted + 1, 1)
        col0 = jnp.where(spec, greedy_all[:, 0], tok)
        toks = jnp.concatenate([col0[:, None], greedy_all[:, 1:]],
                               axis=1)
        if load:
            toks = jnp.concatenate([toks, jnp.broadcast_to(
                moe.load_stats(load[0])[:, None], (3, O))])
        if not mtp:
            return toks, n_emit, kc, vc, recent, tok, conv
        # The token that follows each stream position: the next of
        # its span; at a verify span's positions what the trunk chose
        # there (column j: the true successor while the drafts before
        # it were accepted, and nothing reads the others); at a
        # row's last position the id it just sampled, or the next
        # prompt token where the span ends inside its prompt.
        follows = jnp.roll(tokens, -1)
        last = jnp.where(append > 0, tok, next_tok)
        follows = follows.at[q_start + q_len - 1].set(
            last, mode="drop")
        jj = jnp.arange(O)[None, :]
        follows = follows.at[jnp.where(
            spec[:, None] & (jj < q_len[:, None]),
            q_start[:, None] + jj, T_pad)].set(
                greedy_all, mode="drop")
        # ...and the draft is read at the row's last ACCEPTED
        # position: the module's view of the token after the one
        # this step emitted last.
        at = jnp.clip(q_start + jnp.where(spec, accepted, q_len - 1),
                      0, T_pad - 1)
        draft_logits, kc, _ = llama.forward_mtp(
            params, cfg, hidden, follows, tok_seq, tok_pos,
            write_slots, at, kc, pt, q_start, q_len, kv_len, ps,
            attn_impl=attn_impl, mesh=mesh)
        drafts = drafts.at[slot_ids].set(
            jnp.argmax(draft_logits, axis=-1).astype(jnp.int32))
        lens = lens.at[slot_ids].set(
            kv_len - q_len + jnp.where(spec, n_emit, q_len))
        return toks, n_emit, kc, vc, recent, tok, conv, drafts, lens

    return jax.jit(mq_ragged_step,
                   donate_argnums=(2, 3, 4, 5, 6, 7, 8) if mtp
                   else (2, 3, 4, 5, 6))


@_memoised
def decode_scan(cfg, dims: StepDims, k_steps: int, flags, *, attn_impl: str,
                mesh=None):
    """`k_steps` decode steps of every slot (row b is slot b) inside ONE
    `lax.scan`, to amortise the host's dispatch. Returns (toks [K, S] — an
    MoE model's [K, S + 3]: each pass's expert-load counters behind its ids
    —, caches', recent', last_ids', conv'): the carry is each slot's last
    id (a scan's rows are the slots)."""
    ps = dims.page_size
    lay = dims.decode_layout()

    def mq_decode_scan(params, buf, kc, vc, recent, last_ids, conv):
        (tokens, positions, active, pt, temp, tk, tp, pen, pres,
         freq, seeds, rng) = lay.unpack(buf)
        key = jax.random.PRNGKey(rng[0])
        S = tokens.shape[0]
        tokens = _carried_ids(tokens, last_ids)

        def step(carry, _):
            tokens, positions, kc, vc, recent, key, conv = carry
            logits, kc, vc, *rest = llama.forward_decode(
                params, cfg, tokens, positions, kc, vc, pt, ps,
                attn_impl=attn_impl, active=active, mesh=mesh,
                moe_load=bool(cfg.num_experts), conv_state=conv,
            )
            if conv is not None:
                conv, *rest = rest
            load = rest
            key, sub = jax.random.split(key)
            # `positions` holds the incoming token's slot: prefill folded
            # n for the token at n, so the first decode step must fold
            # n+1, not n, or the two consecutive sampling decisions share
            # a key.
            nxt = _sample(flags, logits, recent[:S], sub, seeds, positions,
                          temp, tk, tp, pen, pres, freq, ahead=1)
            # Roll the sampled token into ACTIVE slots' rings only —
            # reserved (mid-chunked-prefill) slots must not collect
            # garbage tokens.
            rolled = jnp.concatenate(
                [recent[:S, 1:], nxt[:, None]], axis=1
            )
            new_rows = jnp.where(active[:, None] > 0, rolled, recent[:S])
            recent = recent.at[:S].set(new_rows)
            out = nxt
            if load:  # the pass's counters, behind the ids
                out = jnp.concatenate([nxt, moe.load_stats(load[0])])
            return (nxt, positions + 1, kc, vc, recent, key,
                    conv), out

        (tokens, positions, kc, vc, recent, key, conv), toks = \
            jax.lax.scan(
                step, (tokens, positions, kc, vc, recent, key, conv),
                None, length=k_steps)
        return toks, kc, vc, recent, tokens, conv

    return jax.jit(mq_decode_scan, donate_argnums=(2, 3, 4, 5, 6))
