"""Drive the native C++ admin TUI (cpp/tui.cpp) through a real pty.

PARITY: the reference TUI's admin verbs (tui.rs:153-216) — VIP star on
the selected user, block persisting to blocked_items.json, unblock, and
clean quit — exercised against the actual rendered frames and the actual
key loop, not the snapshot functions in isolation.

Harness notes: the TUI writes full frames at the refresh cadence; a
stalled reader fills the pty buffer and blocks the frame write, wedging
the key loop — so a drain thread consumes the master side for the whole
run.
"""

import fcntl
import json
import os
import pty
import struct
import subprocess
import sys
import termios
import threading
import time

import pytest

_CHILD = r"""
import sys
from ollamamq_tpu.core.mqcore import MQCore
from ollamamq_tpu.admin import tui as admin_tui

# The stats callback's HBM refresh imports jax and touches its devices.
# The TUI test is about the key loop and persistence, not devices — pin
# the cache so the jax branch never runs.
admin_tui._hbm_cache.update(
    ts=float("inf"), used=0, total=0, device="test-device",
    # 8 chips across 2 simulated hosts: the chips panel must render one
    # row per chip (north star "per-chip HBM occupancy").
    chips=[{"device": f"cpu:{i}", "id": i, "process": i // 4,
            "hbm_used": (i + 1) << 20, "hbm_total": 16 << 20}
           for i in range(8)],
)

core = MQCore(sys.argv[1])
core.enqueue("alice", "10.0.0.1")
core.enqueue("bob", "10.0.0.2")


class Eng:
    pass


eng = Eng()
eng.core = core
eng.runtimes = {}
admin_tui.run_tui(eng, None, refresh_ms=50)
print("TUI_EXIT_OK", flush=True)
"""


class _PtyTui:
    def __init__(self, tmp_path, child_src=_CHILD):
        self.blockfile = str(tmp_path / "blocked_items.json")
        child = tmp_path / "tui_child.py"
        child.write_text(child_src)
        self.master, slave = pty.openpty()
        # A real terminal size so the 3-column layout renders.
        fcntl.ioctl(self.master, termios.TIOCSWINSZ,
                    struct.pack("HHHH", 40, 140, 0, 0))
        env = dict(os.environ)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        # Force CPU: the stats callback imports jax, and probing a remote
        # TPU platform from this child could hang the first frame.
        env["JAX_PLATFORMS"] = "cpu"
        # stderr to a FILE: an unread pipe would fill with library logging
        # and block the child mid-frame.
        self.errfile = tmp_path / "tui_stderr.log"
        self.proc = subprocess.Popen(
            [sys.executable, str(child), self.blockfile],
            stdin=slave, stdout=slave, stderr=open(self.errfile, "w"),
            env=env,
        )
        os.close(slave)
        self.buf = bytearray()
        self._lock = threading.Lock()
        self._drain = threading.Thread(target=self._drain_loop, daemon=True)
        self._drain.start()

    def _drain_loop(self):
        while True:
            try:
                chunk = os.read(self.master, 65536)
            except OSError:
                return
            if not chunk:
                return
            with self._lock:
                self.buf += chunk

    def wait_output(self, needle: bytes, budget: float = 60.0) -> bool:
        deadline = time.monotonic() + budget
        while time.monotonic() < deadline:
            with self._lock:
                if needle in self.buf:
                    return True
            time.sleep(0.05)
        return False

    def clear(self):
        with self._lock:
            self.buf.clear()

    def send(self, keys: str):
        os.write(self.master, keys.encode())

    def close(self):
        try:
            os.close(self.master)
        except OSError:
            pass
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait(timeout=10)


def _blocked_items(path, budget=30.0, want=None):
    """Poll blocked_items.json until it exists (and contains `want`)."""
    deadline = time.monotonic() + budget
    items = None
    while time.monotonic() < deadline:
        try:
            with open(path) as f:
                data = json.load(f)
            items = data.get("blocked_users", []) + data.get("blocked_ips", [])
        except (OSError, ValueError):
            items = None
        if items is not None and (want is None or want in items):
            return items
        time.sleep(0.1)
    return items


@pytest.mark.skipif(sys.platform != "linux", reason="pty/termios test")
def test_tui_admin_verbs_via_pty(tmp_path):
    t = _PtyTui(tmp_path)
    try:
        # Frame renders with both users queued.
        assert t.wait_output(b"USERS"), _stderr(t)
        assert t.wait_output(b"alice") and t.wait_output(b"bob")

        # Per-chip rows: one line per chip, both hosts represented.
        assert t.wait_output(b"chip 0 (host 0)"), "per-chip rows missing"
        assert t.wait_output(b"chip 7 (host 1)"), "per-chip rows missing"

        # No runtime caches here => the throughput line says "cache n/a"
        # (a caching runtime renders a hit percentage instead).
        assert t.wait_output(b"cache n/a"), "prefix-cache field missing"

        # Panel 1, first user (sorted: alice), VIP toggle => star glyph.
        t.send("\t")
        t.send("p")
        assert t.wait_output("★".encode()), "VIP star never rendered"

        # Block => persists to blocked_items.json (reference-compatible).
        t.send("x")
        items = _blocked_items(t.blockfile, want="alice")
        assert items is not None and "alice" in items, items
        assert t.wait_output("✖".encode())  # blocked glyph in frames

        # Unblock from the blocked panel (Tab Tab => panel 3).
        t.send("ll")
        t.send("u")
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            items = _blocked_items(t.blockfile)
            if items == []:
                break
            time.sleep(0.1)
        assert items == [], items

        # Quit: clean exit, like the reference (quit ends the app).
        t.clear()
        t.send("q")
        assert t.wait_output(b"TUI_EXIT_OK"), _stderr(t)
        assert t.proc.wait(timeout=30) == 0
    finally:
        t.close()


def _stderr(t):
    try:
        return t.errfile.read_text(errors="replace")[-2000:]
    except Exception:
        return "<no stderr>"


# Same harness, but the engine stub carries a live AlertManager with a
# firing SLO alert — the ALERTS panel must render it.
_CHILD_ALERTS = _CHILD.replace(
    'eng.runtimes = {}\nadmin_tui.run_tui(eng, None, refresh_ms=50)',
    '''eng.runtimes = {}
from ollamamq_tpu.telemetry.slo import AlertManager
eng.alerts = AlertManager()
eng.alerts.fire("slo_ttft_burn_fast", "page",
                "ttft SLO burning 20.0x budget over 300s", source="slo")
admin_tui.run_tui(eng, None, refresh_ms=50)''')
assert _CHILD_ALERTS != _CHILD, "alerts child patch failed to apply"


@pytest.mark.skipif(sys.platform != "linux", reason="pty/termios test")
def test_tui_alerts_panel_via_pty(tmp_path):
    """ISSUE 3 acceptance: a firing alert shows in the TUI alerts panel
    (rendered frames through a real pty, not the brief dict alone)."""
    t = _PtyTui(tmp_path, child_src=_CHILD_ALERTS)
    try:
        assert t.wait_output(b"ALERTS (1 firing)"), _stderr(t)
        assert t.wait_output(b"slo_ttft_burn_fast"), _stderr(t)
        assert t.wait_output(b"[page]")
        assert t.wait_output("⚠".encode())
        # Resolve -> the panel goes quiet ("(none)") on a later frame.
        # (The alert table is in the child process; quit instead.)
        t.clear()
        t.send("q")
        assert t.wait_output(b"TUI_EXIT_OK"), _stderr(t)
        assert t.proc.wait(timeout=30) == 0
    finally:
        t.close()


# Engine stub with a decision journal holding a preempt record: the
# chips panel must render the flight recorder's last-decision line.
_CHILD_JOURNAL = _CHILD.replace(
    'eng.runtimes = {}\nadmin_tui.run_tui(eng, None, refresh_ms=50)',
    '''eng.runtimes = {}
from ollamamq_tpu.telemetry.journal import Journal
eng.journal = Journal(capacity=32)
eng.journal.record("preempt", req_id=42, user="mallory", model="test-tiny",
                   slot=3, why="kv_pressure", n=1, free_pages=0,
                   victim_served=9, vip="alice")
admin_tui.run_tui(eng, None, refresh_ms=50)''')
assert _CHILD_JOURNAL != _CHILD, "journal child patch failed to apply"


@pytest.mark.skipif(sys.platform != "linux", reason="pty/termios test")
def test_tui_last_decision_line_via_pty(tmp_path):
    """ISSUE 5: the newest scheduler decision renders as a `last:` line
    in the chips panel, with the inputs that justified it."""
    t = _PtyTui(tmp_path, child_src=_CHILD_JOURNAL)
    try:
        assert t.wait_output(b"last: req 42 (mallory) preempted"), _stderr(t)
        assert t.wait_output(b"free_pages=0"), _stderr(t)
        t.send("q")
        assert t.wait_output(b"TUI_EXIT_OK"), _stderr(t)
        assert t.proc.wait(timeout=30) == 0
    finally:
        t.close()


# Engine stub shaped like a tiered fleet router: the chips panel must
# render the replicas line AND the tiers line (healthy/total per tier) —
# here with a starved interactive tier (0 healthy), the red case.
_CHILD_TIERS = _CHILD.replace(
    'eng.runtimes = {}\nadmin_tui.run_tui(eng, None, refresh_ms=50)',
    '''eng.runtimes = {}
class _Tiers:
    def counts(self):
        return {"interactive": {"healthy": 0, "total": 1},
                "bulk": {"healthy": 2, "total": 2}}
eng.tiers = _Tiers()
eng.fleet_counts = lambda: {"healthy": 2, "ejected": 1, "draining": 0}
admin_tui.run_tui(eng, None, refresh_ms=50)''')
assert _CHILD_TIERS != _CHILD, "tiers child patch failed to apply"


@pytest.mark.skipif(sys.platform != "linux", reason="pty/termios test")
def test_tui_tiers_line_via_pty(tmp_path):
    """Tiered-fleet TUI: the tiers line renders healthy/total per tier
    in the rendered frames (red when a tier has zero healthy members —
    asserted on content; the color is the C++ side's starved flag)."""
    t = _PtyTui(tmp_path, child_src=_CHILD_TIERS)
    try:
        assert t.wait_output(b"replicas 2 healthy / 1 ejected"), _stderr(t)
        assert t.wait_output(b"tiers"), _stderr(t)
        assert t.wait_output(b"interactive 0/1"), _stderr(t)
        assert t.wait_output(b"bulk 2/2"), _stderr(t)
        t.send("q")
        assert t.wait_output(b"TUI_EXIT_OK"), _stderr(t)
        assert t.proc.wait(timeout=30) == 0
    finally:
        t.close()


# Engine stub shaped like a fleet router with the overhead self-profiler:
# the replicas line must carry the `router p99` chip (the windowed
# placement-decision p99 the health monitor bounds against the budget).
_CHILD_OVERHEAD = _CHILD.replace(
    'eng.runtimes = {}\nadmin_tui.run_tui(eng, None, refresh_ms=50)',
    '''eng.runtimes = {}
class _Ecfg:
    router_overhead_budget_ms = 50.0
eng.ecfg = _Ecfg()
eng.router_overhead_p99_ms = lambda: 3.21
eng.fleet_counts = lambda: {"healthy": 2, "ejected": 0, "draining": 0}
admin_tui.run_tui(eng, None, refresh_ms=50)''')
assert _CHILD_OVERHEAD != _CHILD, "overhead child patch failed to apply"


@pytest.mark.skipif(sys.platform != "linux", reason="pty/termios test")
def test_tui_router_overhead_chip_via_pty(tmp_path):
    """Fleet-router TUI: the replicas line carries the router-overhead
    chip (windowed placement p99 in ms) in the rendered frames; red-
    over-budget is the C++ side's `over` flag, asserted on content."""
    t = _PtyTui(tmp_path, child_src=_CHILD_OVERHEAD)
    try:
        assert t.wait_output(b"replicas 2 healthy"), _stderr(t)
        assert t.wait_output(b"router p99 3.21ms"), _stderr(t)
        t.send("q")
        assert t.wait_output(b"TUI_EXIT_OK"), _stderr(t)
        assert t.proc.wait(timeout=30) == 0
    finally:
        t.close()


# Engine stub shaped like an elastic fleet router: the autoscaler's
# brief() feeds the fleet-size chip (`fleet N (+P preemptible)` with the
# scaler's [min..max] band).
_CHILD_FLEET_SIZE = _CHILD.replace(
    'eng.runtimes = {}\nadmin_tui.run_tui(eng, None, refresh_ms=50)',
    '''eng.runtimes = {}
class _Scaler:
    def brief(self):
        return {"n": 3, "preemptible": 1, "min": 1, "max": 4}
eng.autoscaler = _Scaler()
eng.fleet_counts = lambda: {"healthy": 3, "ejected": 0, "draining": 0}
admin_tui.run_tui(eng, None, refresh_ms=50)''')
assert _CHILD_FLEET_SIZE != _CHILD, "fleet-size child patch failed to apply"


@pytest.mark.skipif(sys.platform != "linux", reason="pty/termios test")
def test_tui_fleet_size_chip_via_pty(tmp_path):
    """Elastic-fleet TUI: the fleet-size chip renders the current size,
    the preemptible count, and the autoscaler's [min..max] band in the
    rendered frames."""
    t = _PtyTui(tmp_path, child_src=_CHILD_FLEET_SIZE)
    try:
        assert t.wait_output(b"replicas 3 healthy"), _stderr(t)
        assert t.wait_output(b"fleet 3 (+1 preemptible)  [1..4]"), _stderr(t)
        t.send("q")
        assert t.wait_output(b"TUI_EXIT_OK"), _stderr(t)
        assert t.proc.wait(timeout=30) == 0
    finally:
        t.close()


# Engine stub shaped like a warm standby (fleet/ha.py): ha_status()
# feeds the HA role chip — role + fencing epoch, standby-side with its
# replication lag in records.
_CHILD_HA = _CHILD.replace(
    'eng.runtimes = {}\nadmin_tui.run_tui(eng, None, refresh_ms=50)',
    '''eng.runtimes = {}
eng.ha_status = lambda: {"role": "standby", "epoch": 3,
                         "sync_lag_records": 12, "synced": True}
admin_tui.run_tui(eng, None, refresh_ms=50)''')
assert _CHILD_HA != _CHILD, "ha child patch failed to apply"


@pytest.mark.skipif(sys.platform != "linux", reason="pty/termios test")
def test_tui_ha_role_chip_via_pty(tmp_path):
    """Router-HA TUI: the role/epoch chip renders in the frames — a
    standby shows `ha standby/<epoch>` with its replication lag, so an
    operator can see at a glance which process owns the fleet."""
    t = _PtyTui(tmp_path, child_src=_CHILD_HA)
    try:
        assert t.wait_output(b"ha standby/3"), _stderr(t)
        assert t.wait_output(b"lag 12"), _stderr(t)
        t.send("q")
        assert t.wait_output(b"TUI_EXIT_OK"), _stderr(t)
        assert t.proc.wait(timeout=30) == 0
    finally:
        t.close()


# Engine stub with a seeded step profiler: the performance-plane chip
# (`compiles N · step p99 X ms`) reads the process-wide PROFILER, so
# the child seeds it with a deterministic sample + two compile events.
_CHILD_STEPPROF = _CHILD.replace(
    'eng.runtimes = {}\nadmin_tui.run_tui(eng, None, refresh_ms=50)',
    '''eng.runtimes = {}
from ollamamq_tpu.telemetry import stepprof
stepprof.PROFILER.reset()
tmr = stepprof.PROFILER.start("decode")
tmr.mark("dispatch")
tmr.phases["dispatch"] = 12.34     # pin the rendered p99 exactly (a
#                                     step's total is its phases' sum)
tmr.finish(T_pad=0, k_cap=2, n_prefill=0, n_decode=1, tokens=2,
           padded_tokens=4, compiled=True)
stepprof.PROFILER.record_compile("decode", "(2,)", 100.0)
hit = stepprof.Account()
hit.programs = hit.cache_hits = 1
stepprof.PROFILER.record_compile("ragged", "(16,)", 200.0, hit)
admin_tui.run_tui(eng, None, refresh_ms=50)''')
assert _CHILD_STEPPROF != _CHILD, "stepprof child patch failed to apply"


@pytest.mark.skipif(sys.platform != "linux", reason="pty/termios test")
def test_tui_stepprof_chip_via_pty(tmp_path):
    """Engine-performance-plane TUI: the chips panel renders the compile
    count and rolling step p99 off the step profiler's brief()."""
    t = _PtyTui(tmp_path, child_src=_CHILD_STEPPROF)
    try:
        assert t.wait_output(b"compiles 2 (1 hit / 0 miss)"), _stderr(t)
        assert t.wait_output(b"step p99 12.34ms"), _stderr(t)
        t.send("q")
        assert t.wait_output(b"TUI_EXIT_OK"), _stderr(t)
        assert t.proc.wait(timeout=30) == 0
    finally:
        t.close()


@pytest.mark.skipif(sys.platform != "linux", reason="pty/termios test")
def test_tui_no_alerts_renders_quiet_panel(tmp_path):
    """Without an alert table (or with it empty) the ALERTS section still
    renders, showing (none) — layout must not depend on alert state."""
    t = _PtyTui(tmp_path)
    try:
        assert t.wait_output(b"ALERTS"), _stderr(t)
        assert t.wait_output(b"(none)"), _stderr(t)
        t.send("q")
        assert t.wait_output(b"TUI_EXIT_OK"), _stderr(t)
    finally:
        t.close()
