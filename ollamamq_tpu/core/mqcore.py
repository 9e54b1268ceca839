"""ctypes binding to the native mqcore serving core (cpp/mqcore.cpp).

The shared library is built on demand with `make` the first time it's
imported (the native toolchain is a hard dependency of the framework, like
the reference's cargo build). All policy logic lives in C++; this wrapper
only marshals strings and exposes a pythonic facade.
"""

from __future__ import annotations

import ctypes
import enum
import fcntl
import json
import os
import subprocess
import threading
from typing import Iterable, Optional, Tuple

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_CPP_DIR = os.path.join(_REPO_ROOT, "cpp")
_BUILD_LOCK = threading.Lock()


class Family(enum.IntEnum):
    UNKNOWN = 0
    OLLAMA = 1
    OPENAI = 2


class Fairness(enum.IntEnum):
    REQUESTS = 0
    TOKENS = 1


def _ensure_built(cpp_dir: str = _CPP_DIR) -> str:
    """Path of the built library, building it first where it is missing
    or older than a source. The check and `make` run under an exclusive
    `flock` on `<cpp_dir>/.build.lock` (and the Makefile links to a
    temporary name it then renames): test workers are PROCESSES, and on a
    fresh checkout each of them used to find no library and start a `make`
    of its own, so that one could `dlopen` the file another was still
    writing ("file too short"). The thread lock alone held only the
    threads of one process apart."""
    lib_path = os.path.join(cpp_dir, "libmqcore.so")
    with _BUILD_LOCK:
        if not os.path.isdir(cpp_dir):
            # A plain `pip install .` copies only the python package to
            # site-packages; the native core's sources stay in the repo.
            raise RuntimeError(
                "native scheduler core sources not found at "
                f"{cpp_dir}: ollamamq-tpu must run from a checkout "
                "(`pip install -e .`) or the Docker image, which builds "
                "cpp/libmqcore.so in stage 1"
            )
        with open(os.path.join(cpp_dir, ".build.lock"), "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)  # released when it closes
            sources = [
                os.path.join(cpp_dir, f)
                for f in os.listdir(cpp_dir)
                if f.endswith((".cpp", ".h"))
            ]
            stale = not os.path.exists(lib_path) or any(
                os.path.getmtime(s) > os.path.getmtime(lib_path)
                for s in sources
            )
            if stale:
                subprocess.run(
                    ["make", "-C", cpp_dir], check=True, capture_output=True,
                    text=True
                )
    return lib_path


def _load() -> ctypes.CDLL:
    lib = ctypes.CDLL(_ensure_built())
    lib.mq_new.restype = ctypes.c_void_p
    lib.mq_new.argtypes = [ctypes.c_char_p]
    lib.mq_destroy.argtypes = [ctypes.c_void_p]
    lib.mq_enqueue.restype = ctypes.c_int64
    lib.mq_enqueue.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
                               ctypes.c_char_p, ctypes.c_int]
    lib.mq_enqueue_kind.restype = ctypes.c_int64
    lib.mq_enqueue_kind.argtypes = lib.mq_enqueue.argtypes + [ctypes.c_int]
    lib.mq_requeue_front.restype = ctypes.c_int64
    lib.mq_requeue_front.argtypes = lib.mq_enqueue_kind.argtypes
    lib.mq_next2.restype = ctypes.c_int64
    lib.mq_next2.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                             ctypes.c_char_p,
                             ctypes.c_char_p, ctypes.c_int,
                             ctypes.c_char_p, ctypes.c_int]
    lib.mq_next.restype = ctypes.c_int64
    lib.mq_next.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                            ctypes.c_char_p, ctypes.c_int,
                            ctypes.c_char_p, ctypes.c_int]
    lib.mq_cancel.restype = ctypes.c_int
    lib.mq_cancel.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.mq_reserve_req_ids.restype = None
    lib.mq_reserve_req_ids.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    for name in ("mq_mark_started", "mq_block_user",
                 "mq_unblock_user", "mq_block_ip", "mq_unblock_ip",
                 "mq_set_vip", "mq_set_boost"):
        fn = getattr(lib, name)
        fn.restype = None
        fn.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.mq_mark_dropped.restype = None
    lib.mq_mark_dropped.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int]
    lib.mq_mark_done.restype = None
    lib.mq_mark_done.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64]
    lib.mq_is_user_blocked.restype = ctypes.c_int
    lib.mq_is_user_blocked.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.mq_is_ip_blocked.restype = ctypes.c_int
    lib.mq_is_ip_blocked.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.mq_unblock_item.restype = ctypes.c_int
    lib.mq_unblock_item.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.mq_block_version.restype = ctypes.c_int64
    lib.mq_block_version.argtypes = [ctypes.c_void_p]
    lib.mq_is_user_or_ip_blocked.restype = ctypes.c_int
    lib.mq_is_user_or_ip_blocked.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.mq_set_fairness_mode.restype = None
    lib.mq_set_fairness_mode.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.mq_queue_len.restype = ctypes.c_int64
    lib.mq_queue_len.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.mq_total_queued.restype = ctypes.c_int64
    lib.mq_total_queued.argtypes = [ctypes.c_void_p]
    lib.mq_queued_matching.restype = ctypes.c_int64
    lib.mq_queued_matching.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.mq_snapshot_json.restype = ctypes.c_int64
    lib.mq_snapshot_json.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64]
    return lib


_lib: Optional[ctypes.CDLL] = None


def _get_lib() -> ctypes.CDLL:
    global _lib
    if _lib is None:
        _lib = _load()
    return _lib


EMPTY = 0
STUCK = -1
BLOCKED_USER = -1
BLOCKED_IP = -2


class MQCore:
    """Per-user fair-share queue core (native)."""

    def __init__(self, blocklist_path: Optional[str] = None):
        self._lib = _get_lib()
        self._h = ctypes.c_void_p(
            self._lib.mq_new(blocklist_path.encode() if blocklist_path else None)
        )

    def close(self) -> None:
        if self._h:
            self._lib.mq_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    # -- queue ops ---------------------------------------------------------
    def enqueue(
        self,
        user: str,
        ip: str = "",
        model: Optional[str] = None,
        family: Family = Family.UNKNOWN,
        kind: str = "generate",
    ) -> int:
        """Returns req_id > 0, or raises BlockedError. `kind` selects the
        capacity pool the scheduler gate checks for this task (embed vs
        generate are independent engine resources)."""
        rid = self._lib.mq_enqueue_kind(
            self._h, user.encode(), ip.encode(),
            model.encode() if model else None, int(family),
            1 if kind == "embed" else 0,
        )
        if rid == BLOCKED_USER:
            raise BlockedError("user", user)
        if rid == BLOCKED_IP:
            raise BlockedError("ip", ip)
        return rid

    def requeue_front(
        self,
        user: str,
        ip: str = "",
        model: Optional[str] = None,
        family: Family = Family.UNKNOWN,
        kind: str = "generate",
    ) -> int:
        """Undo a pop whose placement raced away: the task returns to the
        FRONT of its user's queue (per-user FIFO preserved — the reference
        peeks and never pops until dispatchable, dispatcher.rs:427-431).
        Returns the fresh req_id, or raises BlockedError."""
        rid = self._lib.mq_requeue_front(
            self._h, user.encode(), ip.encode(),
            model.encode() if model else None, int(family),
            1 if kind == "embed" else 0,
        )
        if rid == BLOCKED_USER:
            raise BlockedError("user", user)
        if rid == BLOCKED_IP:
            raise BlockedError("ip", ip)
        return rid

    def next(
        self, eligible_models: Optional[Iterable[str]] = None,
        eligible_embed: Optional[Iterable[str]] = None,
    ) -> Optional[Tuple[int, str, str]]:
        """Pop per policy. Returns (req_id, user, model) or None (empty).
        Raises StuckQueue if the policy pick's model isn't servable.
        `eligible_embed`, when given, gates embed-kind tasks instead of
        `eligible_models` — the two capacity pools are independent (a
        full decode batch must not park embeds and vice versa); None
        keeps the kind-blind single-list behavior."""
        ubuf = ctypes.create_string_buffer(512)
        mbuf = ctypes.create_string_buffer(512)
        em = None
        if eligible_models is not None:
            em = "\n".join(eligible_models).encode()
        ee = None
        if eligible_embed is not None:
            ee = "\n".join(eligible_embed).encode()
        rid = self._lib.mq_next2(self._h, em, ee, ubuf, len(ubuf), mbuf,
                                 len(mbuf))
        if rid == EMPTY:
            return None
        if rid == STUCK:
            raise StuckQueue()
        return rid, ubuf.value.decode(), mbuf.value.decode()

    def next_window(
        self, k: int,
        eligible_models: Optional[Iterable[str]] = None,
        eligible_embed: Optional[Iterable[str]] = None,
    ) -> Tuple[list, bool]:
        """Pop up to k dispatchable tasks in fair-share order — the
        candidate window a SchedulerPolicy (engine/scheduler.py) may
        reorder before placement. The native core still decides WHICH
        tasks are released (per-user fair share, VIP/boost, blocklist,
        model eligibility); a policy only reorders within the released
        window, so k=1 is exactly the legacy pop-and-place flow.

        Returns (items, stuck): items is a list of (req_id, user, model)
        tuples, stuck=True means a later pop hit a policy-selected-but-
        unservable front (StuckQueue) AFTER the returned items — they
        were already dequeued and must still be placed."""
        eligible_models = (list(eligible_models)
                          if eligible_models is not None else None)
        eligible_embed = (list(eligible_embed)
                          if eligible_embed is not None else None)
        items: list = []
        stuck = False
        for _ in range(max(1, int(k))):
            try:
                item = self.next(eligible_models, eligible_embed)
            except StuckQueue:
                stuck = True
                break
            if item is None:
                break
            items.append(item)
        return items, stuck

    def cancel(self, req_id: int) -> bool:
        return bool(self._lib.mq_cancel(self._h, req_id))

    def reserve_req_ids(self, min_next: int) -> None:
        """Advance the request-id counter to at least `min_next` — crash
        recovery calls this with (max WAL rid + 1) BEFORE re-admitting,
        so a restarted process's fresh ids never collide with the ids
        pre-crash clients still hold (their resume handles)."""
        self._lib.mq_reserve_req_ids(self._h, int(min_next))

    # -- accounting --------------------------------------------------------
    def mark_started(self, user: str) -> None:
        self._lib.mq_mark_started(self._h, user.encode())

    def mark_done(self, user: str, tokens: int = 0) -> None:
        self._lib.mq_mark_done(self._h, user.encode(), tokens)

    def mark_dropped(self, user: str, started: bool = True) -> None:
        self._lib.mq_mark_dropped(self._h, user.encode(), int(started))

    # -- admin -------------------------------------------------------------
    def block_user(self, user: str) -> None:
        self._lib.mq_block_user(self._h, user.encode())

    def unblock_user(self, user: str) -> None:
        self._lib.mq_unblock_user(self._h, user.encode())

    def block_ip(self, ip: str) -> None:
        self._lib.mq_block_ip(self._h, ip.encode())

    def unblock_ip(self, ip: str) -> None:
        self._lib.mq_unblock_ip(self._h, ip.encode())

    def unblock_item(self, item: str) -> bool:
        return bool(self._lib.mq_unblock_item(self._h, item.encode()))

    def is_user_blocked(self, user: str) -> bool:
        return bool(self._lib.mq_is_user_blocked(self._h, user.encode()))

    def block_version(self) -> int:
        return int(self._lib.mq_block_version(self._h))

    def is_user_or_ip_blocked(self, user: str) -> bool:
        """Blocked directly or via the user's last recorded IP."""
        return bool(self._lib.mq_is_user_or_ip_blocked(self._h, user.encode()))

    def is_ip_blocked(self, ip: str) -> bool:
        return bool(self._lib.mq_is_ip_blocked(self._h, ip.encode()))

    def set_vip(self, user: Optional[str]) -> None:
        self._lib.mq_set_vip(self._h, user.encode() if user else None)

    def set_boost(self, user: Optional[str]) -> None:
        self._lib.mq_set_boost(self._h, user.encode() if user else None)

    def set_fairness(self, mode: Fairness) -> None:
        self._lib.mq_set_fairness_mode(self._h, int(mode))

    # -- introspection -----------------------------------------------------
    def queue_len(self, user: str) -> int:
        return self._lib.mq_queue_len(self._h, user.encode())

    def total_queued(self) -> int:
        return self._lib.mq_total_queued(self._h)

    def queued_matching(self, model: str) -> int:
        """Queued tasks `model` could serve (empty-model tasks count)."""
        return int(self._lib.mq_queued_matching(self._h, model.encode()))

    def snapshot(self) -> dict:
        need = self._lib.mq_snapshot_json(self._h, None, 0)
        buf = ctypes.create_string_buffer(need + 16)
        self._lib.mq_snapshot_json(self._h, buf, len(buf))
        return json.loads(buf.value.decode())


class BlockedError(Exception):
    def __init__(self, kind: str, item: str):
        self.kind = kind
        self.item = item
        super().__init__(f"blocked {kind}: {item}")


class StuckQueue(Exception):
    """Policy-selected user's front request can't be served right now
    (model not loaded / no capacity) — reference's 'stuck in queue'."""
