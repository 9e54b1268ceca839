"""A step's work account (`engine/step_work.py`): one table, a row a kind of
layer. The counts themselves are held where each kind's model is
(`test_deepseek_v32_wide.py`, `test_ragged_attention_tall.py`,
`test_window_cache.py`, the models' own engine waves); here, what the table
is for: a model WITHOUT a kind has none of its fields on a step's sample,
and every field is some row's."""

import types

import pytest

from ollamamq_tpu.config import MODEL_CONFIGS
from ollamamq_tpu.engine.step_work import KINDS, StepWork

# A model that has each kind's layers, and one that has not.
WITH = {"conv": "test-tiny-lfm2", "lin": "test-tiny-olmo-hybrid",
        "ssm": "test-tiny-falcon-h1", "s6": "test-tiny-phi4-flash",
        "latent": "test-tiny-deepseek-v32",
        "dense_latent": "test-tiny-openpangu", "attn": "test-tiny",
        "swa": "test-tiny-k-exaone", "exit": "test-tiny-phi4-flash",
        "lightning": "test-tiny-minicpm-sala", "bsa": "test-tiny-minicpm-sala",
        "kv_rows": "test-tiny-mimo-v2-flash", "mhc": "test-tiny-xing4"}
WITHOUT = {kind: "test-tiny-deepseek-v32" if kind == "attn" else "test-tiny"
           for kind in KINDS}


def _noted(model: str, scan: bool) -> dict:
    noted = {}
    sp = types.SimpleNamespace(note=noted.update)
    work = StepWork(MODEL_CONFIGS[model], 8, "step-work-" + model)
    if scan:
        skipped = work.note(sp, [4, 4], [10, 30], scan=True)
    else:
        skipped = work.note(sp, [1, 5, 16], [9, 5, 40], [True, True, False],
                            stream_len=32, opened=1)
    return noted, skipped


def test_the_table_names_every_kind_once():
    assert set(WITH) == set(WITHOUT) == set(KINDS)
    fields = [f for kind in KINDS.values() for f in kind.fields]
    # `mla_rows` and the expanded form's two counts are both latent rows'
    # (a model is one or the other)
    assert len(fields) - 3 == len(set(fields))
    assert set(KINDS["latent"].fields) & set(KINDS["dense_latent"].fields) \
        == {"mla_rows", "mla_wide_tokens", "mla_absorbed_rows"}
    assert all(len(k.fields) == len(k.series) for k in KINDS.values()
               if k.counts is not KINDS["conv"].counts)


@pytest.mark.parametrize("scan", [False, True], ids=["ragged", "scan"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_model_without_a_kind_notes_none_of_its_fields(kind, scan):
    """ONE `present` a row where each `_note_*` had a `return` of its own:
    a model with the kind's layers carries every one of its fields on a
    ragged step's sample and on a scan's, a model without carries none."""
    fields = set(KINDS[kind].fields)
    has, _ = _noted(WITH[kind], scan)
    assert fields <= set(has), (kind, sorted(has))
    assert all(isinstance(has[f], int) and has[f] >= 0 for f in fields)
    lacks, skipped = _noted(WITHOUT[kind], scan)
    if kind == "dense_latent":  # `mla_rows` is the indexer's row's too
        fields -= set(KINDS["latent"].fields)
    assert not fields & set(lacks), (kind, sorted(lacks))
    assert skipped == 0


def test_note_returns_the_tokens_that_stopped_below_the_exit_layer():
    """...which is what the FLOPs model takes off a step (`h.exited`): the
    stream's tokens less the sampled rows; 0 for a scan, whose every pass is
    sampled."""
    noted, skipped = _noted("test-tiny-phi4-flash", scan=False)
    assert skipped == noted["exit_skipped_tokens"] == 1 + 5 + 16 - 2
    assert noted["xattn_rows"] == 2 and noted["xattn_ctx_rows"] == 9 + 5
    noted, skipped = _noted("test-tiny-phi4-flash", scan=True)
    assert skipped == noted["exit_skipped_tokens"] == 0
    assert noted["xattn_rows"] == 8
    # the per-slot state: one row opened, two carried; a 1-token row through
    # the step form, 21 tokens of spans through the chunked one — two spans
    # inside the stream's first 64-token window: two (row, window) pairs, and
    # the one window of the 32-token stream solved
    noted, _ = _noted("test-tiny-olmo-hybrid", scan=False)
    assert [noted[f] for f in KINDS["lin"].fields] == [1, 2, 1, 21, 2, 1]
    # ...and the same beside latent attention as a layer KIND, whose rows
    # are counted as the latent families' are (no `attn_*`, no `dsa_*`)
    noted, _ = _noted("test-tiny-kimi-linear", scan=False)
    assert [noted[f] for f in KINDS["lin"].fields] == [1, 2, 1, 21, 2, 1]
    assert (noted["mla_rows"], noted["mla_pairs"], noted["mla_ctx_rows"]) \
        == (22, 9 + 15 + sum(range(25, 41)), 9 + 5 + 40)
    assert not any(f.startswith(("attn_", "dsa_")) for f in noted)
    assert _noted("test-tiny-kimi-linear", scan=True)[0][
        "lin_prepare_windows"] == 0
    noted, _ = _noted("test-tiny-lfm2", scan=True)
    assert (noted["conv_state_resets"], noted["conv_state_carried"]) == (0, 2)
