"""Native scheduler core: policy parity with the reference dispatcher.

Each test names the reference behavior it checks (file:line into
/root/reference/src/dispatcher.rs unless noted).
"""

import json
import os

import pytest

from ollamamq_tpu.core import MQCore, Family, Fairness
from ollamamq_tpu.core.mqcore import BlockedError, StuckQueue


@pytest.fixture
def core(tmp_path):
    c = MQCore(str(tmp_path / "blocked_items.json"))
    yield c
    c.close()


def drain_users(core, eligible=None, n=100):
    out = []
    for _ in range(n):
        try:
            item = core.next(eligible)
        except StuckQueue:
            out.append("<stuck>")
            continue
        if item is None:
            break
        out.append(item[1])
    return out


def test_fifo_per_user(core):
    ids = [core.enqueue("alice") for _ in range(3)]
    got = []
    while (item := core.next()) is not None:
        got.append(item[0])
    assert got == ids  # FIFO order preserved (queues push_back/pop_front)


def test_kind_aware_eligibility_gate(core):
    """Embed and generate tasks gate on SEPARATE eligibility lists: a
    saturated decode batch (model absent from the generate list) must not
    park embeds, and vice versa."""
    a = core.enqueue("alice", model="m1")  # generate kind
    b = core.enqueue("bob", model="m1", kind="embed")
    # Decode full: alice's generate pick is STUCK...
    with pytest.raises(StuckQueue):
        core.next(eligible_models=[], eligible_embed=["m1"])
    # ...but bob's embed pops through the embed list.
    rid, user, _ = core.next(eligible_models=[], eligible_embed=["m1"])
    assert rid == b and user == "bob"
    # Mirror image: embed backlog full, generates still flow.
    rid, user, _ = core.next(eligible_models=["m1"], eligible_embed=[])
    assert rid == a and user == "alice"
    # Requeued tasks keep their kind: a requeued embed still gates on the
    # embed list.
    e2 = core.requeue_front("bob", model="m1", kind="embed")
    with pytest.raises(StuckQueue):
        core.next(eligible_models=["m1"], eligible_embed=[])
    rid, _, _ = core.next(eligible_models=[], eligible_embed=["m1"])
    assert rid == e2


def test_requeue_front_preserves_fifo(core):
    """A popped-but-unplaceable task returns to the FRONT of its user's
    queue: the user's later request must never overtake it (the reference
    peeks and never pops until dispatchable, dispatcher.rs:427-431)."""
    a1 = core.enqueue("alice", model="m1")
    a2 = core.enqueue("alice", model="m2")
    rid, user, model = core.next()
    assert rid == a1 and model == "m1"
    back = core.requeue_front("alice", model="m1")
    assert back != a1  # fresh id
    rid2, _, model2 = core.next()
    assert rid2 == back and model2 == "m1"  # A again, NOT a2
    rid3, _, _ = core.next()
    assert rid3 == a2


def test_round_robin_cursor_persists(core):
    """dispatcher.rs:421-424: persistent cursor, not least-served-first."""
    for u in ("a", "b", "c"):
        for _ in range(3):
            core.enqueue(u)
    # Equal processed counts: sort is lexicographic; the persistent cursor
    # indexes into the CURRENT active list, so once 'a' drains (after pop 7)
    # the cursor lands on 'c', then wraps to 'b' — exactly what the
    # reference's current_idx does as active_users shrinks.
    assert drain_users(core) == ["a", "b", "c", "a", "b", "c", "a", "c", "b"]


def test_fairness_sort_by_processed(core):
    """dispatcher.rs:408-412: sort by lifetime processed asc, tie lexicographic."""
    core.mark_done("a", 10)
    core.mark_done("a", 10)
    core.mark_done("b", 10)
    core.enqueue("a")
    core.enqueue("b")
    core.enqueue("c")
    # Round 1: sorted [c(0), b(1), a(2)], cursor 0 -> c, cursor=1.
    # Round 2: sorted [b(1), a(2)], cursor 1 -> a (!), cursor=2.
    # Round 3: [b], cursor wraps -> b.
    # The persistent cursor means this is NOT strict least-served-first —
    # matching the reference exactly (dispatcher.rs:421-424).
    assert drain_users(core) == ["c", "a", "b"]


def test_vip_absolute_priority(core):
    """dispatcher.rs:415: VIP wins regardless of counts/cursor."""
    for u in ("a", "b", "v"):
        for _ in range(2):
            core.enqueue(u)
    core.mark_done("v", 0)  # worst fairness count — VIP still wins
    core.set_vip("v")
    assert drain_users(core)[:2] == ["v", "v"]


def test_boost_every_second(core):
    """dispatcher.rs:416-419: boost wins only when global_counter is even;
    counter increments on each pop."""
    for _ in range(4):
        core.enqueue("boosted")
        core.enqueue("other")
    core.set_boost("boosted")
    users = drain_users(core)
    # Even counter ticks go to boost; odd ticks go to the RR cursor (which
    # also reaches "boosted" on its own rotation since the boost path does
    # not advance the cursor — same as the reference, where boost roughly
    # doubles a user's share rather than strictly alternating).
    assert users == ["boosted", "boosted", "boosted", "other",
                     "boosted", "other", "other", "other"]


def test_vip_and_boost_coexist(core):
    """tui.rs:169-206: VIP and boost are independent slots — user A can be
    VIP while user B holds boost."""
    core.set_vip("a")
    core.set_boost("b")
    for u in ("a", "b", "c"):
        core.enqueue(u)
        core.enqueue(u)
    users = drain_users(core)
    # VIP drains fully first; then boost takes even ticks.
    assert users[:2] == ["a", "a"]
    assert users[2] == "b"  # counter=2, even -> boost


def test_stuck_queue_model_gate(core):
    """dispatcher.rs:444-473: policy pick's model unavailable => nothing
    popped; cursor advanced so the next round serves the next user."""
    core.enqueue("a", model="missing-model")
    core.enqueue("b", model="llama3:8b")
    with pytest.raises(StuckQueue):
        core.next(eligible_models=["llama3:8b"])
    # Next round: cursor moved past 'a', b gets served.
    rid, user, model = core.next(eligible_models=["llama3:8b"])
    assert user == "b" and model == "llama3:8b"


def test_smart_model_match_in_gate(core):
    """dispatcher.rs:231-252 semantics inside the eligibility gate."""
    core.enqueue("u", model="LLAMA3")
    rid, user, model = core.next(eligible_models=["llama3:latest"])
    assert user == "u"
    core.enqueue("u", model="qwen2.5:7b")
    with pytest.raises(StuckQueue):
        core.next(eligible_models=["llama3:latest"])


def test_no_model_passes_gate(core):
    """dispatcher.rs:453-461: no model requested => family check only
    (engine serves any family)."""
    core.enqueue("u", model=None, family=Family.OLLAMA)
    assert core.next(eligible_models=["whatever"]) is not None


def test_blocklist_and_403(core):
    """dispatcher.rs:602-610 ingress check; 184-228 persistence."""
    core.block_user("bad")
    with pytest.raises(BlockedError):
        core.enqueue("bad")
    core.block_ip("1.2.3.4")
    with pytest.raises(BlockedError):
        core.enqueue("ok-user", ip="1.2.3.4")
    core.enqueue("ok-user", ip="5.6.7.8")  # fine


def test_block_version_and_combined_check(core):
    """block_version bumps on every block mutation (the engine's late
    re-check sweep gate); is_user_or_ip_blocked covers both sets via the
    user's last recorded IP (dispatcher.rs:503-512)."""
    v0 = core.block_version()
    core.enqueue("ipuser", ip="6.6.6.6")
    assert not core.is_user_or_ip_blocked("ipuser")
    core.block_ip("6.6.6.6")
    assert core.block_version() == v0 + 1
    assert core.is_user_or_ip_blocked("ipuser")  # via IP
    assert not core.is_user_blocked("ipuser")
    core.block_user("directuser")
    assert core.block_version() == v0 + 2
    assert core.is_user_or_ip_blocked("directuser")
    core.unblock_ip("6.6.6.6")
    core.unblock_user("directuser")
    assert not core.is_user_or_ip_blocked("ipuser")


def test_queued_matching_scopes_by_model(core):
    """mq_queued_matching counts only tasks a model could serve (smart
    match or no model requested) — the decode-chunk policy's gate."""
    core.enqueue("qm1", model="llama3:8b")
    core.enqueue("qm2", model="LLAMA3")  # smart-matches llama3:8b
    core.enqueue("qm3", model="qwen2.5:7b")
    core.enqueue("qm4", model=None)  # servable by anyone
    assert core.queued_matching("llama3:8b") == 3
    assert core.queued_matching("qwen2.5:7b") == 2
    assert core.queued_matching("nomic-embed-text") == 1
    # Drain for other tests.
    while core.next(eligible_models=["llama3:8b", "qwen2.5:7b",
                                     "nomic-embed-text"]):
        pass


def test_blocklist_persistence(tmp_path):
    """blocked_items.json round-trip, reference-compatible schema
    (dispatcher.rs:19-25,165-182)."""
    path = str(tmp_path / "blocked_items.json")
    c1 = MQCore(path)
    c1.block_user("mallory")
    c1.block_ip("9.9.9.9")
    c1.close()

    data = json.loads(open(path).read())
    assert data["blocked_users"] == ["mallory"]
    assert data["blocked_ips"] == ["9.9.9.9"]

    c2 = MQCore(path)
    assert c2.is_user_blocked("mallory")
    assert c2.is_ip_blocked("9.9.9.9")
    assert c2.unblock_item("mallory")
    assert not c2.is_user_blocked("mallory")
    c2.close()
    assert json.loads(open(path).read())["blocked_users"] == []


def test_cancel_queued(core):
    """Client cancel before dispatch: request removed, counted dropped
    (dispatcher.rs:503-512 analogue)."""
    rid = core.enqueue("alice")
    assert core.cancel(rid)
    assert core.next() is None
    snap = core.snapshot()
    assert snap["users"]["alice"]["dropped"] == 1
    assert not core.cancel(rid)  # idempotent


def test_token_fairness_mode(core):
    """TPU-era fairness: sort by served tokens instead of request count."""
    core.set_fairness(Fairness.TOKENS)
    core.mark_done("a", tokens=1000)
    core.mark_done("b", tokens=10)
    core.mark_done("b", tokens=10)  # b: 2 requests but only 20 tokens
    core.enqueue("a")
    core.enqueue("b")
    assert drain_users(core) == ["b", "a"]


def test_snapshot_counters(core):
    core.enqueue("alice", ip="1.1.1.1")
    core.enqueue("alice")
    core.next()
    core.mark_started("alice")
    core.mark_done("alice", tokens=42)
    snap = core.snapshot()
    a = snap["users"]["alice"]
    assert a == {
        "queued": 1, "processing": 0, "processed": 1,
        "dropped": 0, "tokens": 42, "ip": "1.1.1.1",
    }
    assert snap["vip"] is None and snap["boost"] is None
    assert snap["global_counter"] == 1


def test_unicode_and_escaping(core):
    user = 'wéird"user\nname'
    core.enqueue(user, ip="::1")
    snap = core.snapshot()
    assert user in snap["users"]


def test_concurrent_enqueue_drain(core):
    """Thread-safety smoke: concurrent enqueues and drains lose nothing."""
    import threading

    N = 200
    def producer(u):
        for _ in range(N):
            core.enqueue(u)

    threads = [threading.Thread(target=producer, args=(f"u{i}",)) for i in range(4)]
    for t in threads:
        t.start()
    popped = []
    done = threading.Event()

    def consumer():
        while not done.is_set() or core.total_queued():
            item = core.next()
            if item:
                popped.append(item[0])

    ct = threading.Thread(target=consumer)
    ct.start()
    for t in threads:
        t.join()
    done.set()
    ct.join()
    assert len(popped) == 4 * N
    assert len(set(popped)) == 4 * N  # unique req ids, no double-pop


def test_processes_started_at_once_all_load_a_fresh_build(tmp_path):
    """Six processes (the tier-1 run's xdist workers on a fresh checkout)
    meet a cpp/ without the library at the same moment: each loads a whole
    one. Before the build ran under an inter-process lock each started a
    `make` of its own, and one could dlopen what another was still writing
    (`OSError: ... file too short`: 11 set-up errors in the driver's run)."""
    import shutil
    import subprocess
    import sys

    from ollamamq_tpu.core import mqcore

    cpp = tmp_path / "cpp"
    cpp.mkdir()
    for name in os.listdir(mqcore._CPP_DIR):
        if name.endswith((".cpp", ".h")) or name == "Makefile":
            shutil.copy(os.path.join(mqcore._CPP_DIR, name), cpp / name)
    code = ("import ctypes, sys; from ollamamq_tpu.core import mqcore; "
            "lib = ctypes.CDLL(mqcore._ensure_built(sys.argv[1])); "
            "lib.mq_new; print('loaded')")
    procs = [subprocess.Popen([sys.executable, "-c", code, str(cpp)],
                              cwd=mqcore._REPO_ROOT, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for _ in range(6)]
    outs = [p.communicate(timeout=300) for p in procs]
    assert [p.returncode for p in procs] == [0] * 6, [e[-300:] for _, e in outs]
    assert all(out.strip() == "loaded" for out, _ in outs)
    assert (cpp / "libmqcore.so").exists()
    assert not [n for n in os.listdir(cpp) if ".tmp." in n]
