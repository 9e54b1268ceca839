"""The parent's side of the server child: start it, ask it things over HTTP,
stop it. This process never imports jax — the child holds the chip."""

from __future__ import annotations

import contextlib
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

from benchmarks.lib.spec import BENCH_DIR, ROOT


class ServerError(Exception):
    pass


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    def __init__(self, config_file: str, out_dir: str, rehearse: bool,
                 collect_steps: bool):
        self.port = free_port()
        self.out_dir = out_dir
        self.base_url = f"http://127.0.0.1:{self.port}"
        env = dict(os.environ)
        # A sandbox pins jax to the CPU through the environment; a
        # measurement must find the chip or fail, so the pin goes.
        if not rehearse and env.get("JAX_PLATFORMS", "").lower() == "cpu":
            del env["JAX_PLATFORMS"]
        env.setdefault("TPU_LOG_DIR", "disabled")
        env["OLLAMAMQ_PROFILE_DIR"] = os.path.join(out_dir, "profile")
        argv = [sys.executable, os.path.join(BENCH_DIR, "serve.py"),
                "--config", config_file, "--out", out_dir,
                "--port", str(self.port)]
        argv += ["--rehearse-cpu"] if rehearse else []
        argv += ["--collect-steps"] if collect_steps else []
        self.log_path = os.path.join(out_dir, "server.log")
        with open(self.log_path, "w") as log:
            self.proc = subprocess.Popen(
                argv, cwd=ROOT, env=env, stdin=subprocess.DEVNULL,
                stdout=log, stderr=subprocess.STDOUT, start_new_session=True)

    def log_tail(self, n: int = 8) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return " | ".join(f.read().splitlines()[-n:])
        except OSError:
            return ""

    def http(self, path: str, body: dict | None = None,
             timeout: float = 60.0):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            self.base_url + path, data=data,
            headers={"X-User-ID": "bench", "Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            raw = r.read().decode()
            if "json" in r.headers.get("Content-Type", ""):
                return json.loads(raw)
            return raw

    def wait_health(self, timeout_s: float) -> float:
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout_s:
            rc = self.proc.poll()
            if rc is not None:
                raise ServerError(f"the server exited with code {rc} before "
                                  f"/health: {self.log_tail()}")
            with contextlib.suppress(urllib.error.URLError, OSError,
                                     ValueError):
                self.http("/health", timeout=5)
                return time.monotonic() - t0
            time.sleep(0.5)
        raise ServerError(f"no /health within {timeout_s:.0f} s: "
                          f"{self.log_tail()}")

    def ask(self, name: str, request: dict | None = None,
            timeout_s: float = 30.0) -> dict:
        """Ask the launcher's thread (see serve.py): it answers
        `<out>/<name>.request` with `<out>/<name>.json`."""
        path = os.path.join(self.out_dir, name + ".json")
        with contextlib.suppress(FileNotFoundError):
            os.remove(path)
        tmp = os.path.join(self.out_dir, name + ".request.tmp")
        with open(tmp, "w") as f:
            json.dump(request or {}, f)
        os.replace(tmp, os.path.join(self.out_dir, name + ".request"))
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout_s:
            if os.path.exists(path):
                with open(path) as f:
                    return json.load(f)
            if self.proc.poll() is not None:
                break
            time.sleep(0.1)
        raise ServerError(f"the launcher wrote no {name}.json: "
                          f"{self.log_tail()}")

    def stop(self, timeout_s: float = 60.0) -> int | None:
        """SIGTERM (the server drains and exits 0); the group is killed if
        it does not. Always waits until the child has ended."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                pass
        with contextlib.suppress(ProcessLookupError, PermissionError):
            os.killpg(self.proc.pid, signal.SIGKILL)
        self.proc.wait()
        return self.proc.returncode
