"""engine/kv_cache.py:SeqCache, the one owner of a sequence's device state,
and `refusal`, the one function that says what a model's state cannot be
served with.

The refusal sentences are held, letter for letter, to what the six
validators of config.py returned before they became one table
(tests/data/refusals_parent.json: every `test-tiny*` preset x feature,
captured from the parent commit in the order a ModelRuntime asked them).
The page methods are held to their pages, their journal records and the
allocator's counts in every state of the pool they can meet."""

import json
import os
import types

import numpy as np
import pytest

from ollamamq_tpu.config import MODEL_CONFIGS, EngineConfig
from ollamamq_tpu.engine.kv_cache import (STATE_REFUSES, SeqCache, refusal,
                                          state_held, unserved)

with open(os.path.join(os.path.dirname(__file__), "data",
                       "refusals_parent.json")) as f:
    AT_THE_PARENT = json.load(f)

FLAGS = {
    "spec": dict(spec=True),
    "tp": dict(mesh_shape={"tensor": 2}),
    "ep": dict(mesh_shape={"expert": 2}),
    "kv_int8": dict(kv_dtype="int8"),
    "weights_int8": dict(weights_dtype="int8"),
    "prefix_cache": dict(prefix_cache=True),
}


@pytest.mark.parametrize("case", sorted(AT_THE_PARENT))
def test_a_refusal_is_the_parents_sentence_letter_for_letter(case):
    name, flag = case.split("|")
    assert refusal(MODEL_CONFIGS[name], **FLAGS[flag]) == AT_THE_PARENT[case]


def test_the_table_covers_every_preset_and_names_every_row():
    tiny = sorted(n for n in MODEL_CONFIGS if n.startswith("test-tiny"))
    assert sorted(AT_THE_PARENT) == sorted(
        f"{n}|{f}" for n in tiny for f in FLAGS)
    rows = {r for n in MODEL_CONFIGS for r in state_held(MODEL_CONFIGS[n])}
    assert rows == set(STATE_REFUSES)  # no row without a model, none unnamed
    # a model that holds K and V pages only is served with everything
    assert state_held(MODEL_CONFIGS["test-tiny"]) == []
    assert unserved(MODEL_CONFIGS["test-tiny"], "migrate") is None
    # what is met at run time reads the same table
    assert "conv layers' state" in unserved(MODEL_CONFIGS["test-tiny-lfm2"],
                                           "migrate")
    assert "prefix cache off" in unserved(MODEL_CONFIGS["test-tiny-lfm2"],
                                          "share")


# ----------------------------------------------------- the pages of a slot

PS, POOL = 4, 9  # tokens a page; pages but the trash page


def _cache(state, **kw):
    """A cache whose slot 1 holds one page, over a pool with room (8 pages
    free), or with 6 pages in the radix tree and 2 free — unreferenced
    ("evict") or pinned ("pinned")."""
    records, asked = [], []
    c = SeqCache(
        "test-tiny", MODEL_CONFIGS["test-tiny"],
        EngineConfig(max_slots=2, num_pages=POOL + 1, page_size=PS,
                     max_pages_per_seq=8, prefix_cache=True, **kw),
        max_span=8,
        record=lambda kind, req=None, **f: records.append((kind, f)),
        blocked=lambda site: asked.append(site) or False)
    if state != "room":
        prompt = list(range(6 * PS))
        assert c.admit(0, prompt[:-1]) == 0
        c.release(0, types.SimpleNamespace(prompt_tokens=prompt))
        assert c.alloc.cached_pages == 6
        if state == "pinned":
            nodes, _ = c.prefix_cache.match(prompt + [0])
            c.prefix_cache.pin(nodes)
    c.slot_pages[1] = c.alloc_pages(PS)
    del records[:], asked[:]
    return c, records, asked


OPS = {  # each wants 4 pages more: (call, the fault plan's seam it asks)
    "alloc": (lambda c: c.alloc_pages(4 * PS), ["alloc"]),
    "alloc_held": (lambda c: c.alloc_pages(5 * PS, held=1), []),
    "extend": (lambda c: c.extend(1, 5 * PS) and c.slot_pages[1][1:],
               ["extend"]),
}


@pytest.mark.parametrize("state", ["room", "evict", "pinned"])
@pytest.mark.parametrize("op", sorted(OPS))
def test_pages_records_and_counts(op, state):
    call, seam = OPS[op]
    c, records, asked = _cache(state)
    a = c.alloc
    got = call(c)
    assert asked == seam
    after = dict(free=a.free_pages, used=a.used_pages, cached=a.cached_pages,
                 pool=POOL)
    assert after == c.page_state() and sum(after.values()) == 2 * POOL
    if state == "pinned":  # nothing to evict: refused, nothing journalled
        assert not got and records == []
        # (a run that could not grow whole keeps the pages it got)
        assert after == dict(free=0 if op == "extend" else 2,
                             used=3 if op == "extend" else 1, cached=6,
                             pool=POOL)
        return
    assert len(got) == 4 and len(set(got)) == 4 and 0 not in got
    assert after == dict(free=4 if state == "room" else 0, used=5,
                         cached=0 if state == "room" else 4, pool=POOL)
    want = [("page_alloc", dict(n=4, **after))]
    if state == "evict":  # the tree gave the shortfall back first (a run
        # that grows has taken the free list's two by then)
        took = 2 if op == "extend" else 0
        want.insert(0, ("page_evict", dict(n=2, free=4 - took, used=1 + took,
                                           cached=4, pool=POOL)))
    assert records == want
    c.prefix_cache.check()


def test_release_rollback_and_the_row_decode_writes_through():
    c, records, _ = _cache("room")
    assert not c.page_table.any()  # slot 1's row stays off until published
    c.publish(1)
    assert c.page_table[1].tolist() == c.slot_pages[1] + [0] * 7
    assert c.extend(1, 4 * PS)
    freed = c.rollback(1, None, kv_before=4 * PS, kv_after=PS + 1,
                       source="ngram")
    assert freed == 2 and len(c.slot_pages[1]) == 2
    assert c.page_table[1].tolist() == c.slot_pages[1] + [0] * 6
    req = types.SimpleNamespace(prompt_tokens=list(range(PS + 1)))
    c.release(1, req)  # the prompt's one full page merges into the tree
    assert c.slot_pages[1] == [] and not c.page_table.any()
    assert [k for k, _ in records] == ["page_alloc", "spec_rollback",
                                       "page_free"]
    assert records[1][1]["freed"] == 2 and records[1][1]["source"] == "ngram"
    assert c.page_state() == dict(free=POOL - 1, used=0, cached=1, pool=POOL)


# ------------------------------------------------------------------ wire

def test_a_prefix_crosses_between_two_pools_of_one_shape():
    src, _, _ = _cache("evict")
    dst, records, _ = _cache("room")
    prompt = list(range(6 * PS)) + [0]
    blob = src.export_prefix(prompt)
    assert blob["kind"] == "prefix" and blob["n_pages"] == 6
    assert src.accepts(blob, "prefix") and not src.accepts(blob, "stream")
    assert {k: blob[k] for k in src.header("prefix")} == src.header("prefix")
    assert dst.import_prefix(dict(blob, page_size=PS * 2)) == 0
    assert dst.import_prefix(blob) == 6
    assert [k for k, _ in records] == ["page_alloc"]
    nodes, pages = dst.prefix_cache.match(prompt)
    assert len(pages) == 6
    np.testing.assert_array_equal(dst.gather(pages)["k_pages"],
                                  blob["k_pages"])
    # a shipped prefix never evicts locally earned cache: no backstop
    full, _, _ = _cache("evict")
    assert full.import_prefix(blob) == 0 and full.alloc.cached_pages == 6
