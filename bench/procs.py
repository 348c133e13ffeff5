"""Child processes, health waits and the sidecar's own account.

Copied from ``chip_smoke.py`` (PR 21), which works on the chip, so that a later
PR's edit to that script cannot change the yardstick. Stdlib only; nothing here
imports JAX, so the parent never holds the chip.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


class BenchFailure(Exception):
    """The run cannot produce a result line (exit code 1, nothing printed last)."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise BenchFailure(what)


class Children:
    """Every process the run starts, so that all are stopped and waited for."""

    def __init__(self, out_dir: Path) -> None:
        self.out_dir = out_dir
        self._procs: list[subprocess.Popen] = []

    def spawn(self, name: str, argv: list[str], env: dict[str, str]) -> subprocess.Popen:
        self.out_dir.mkdir(parents=True, exist_ok=True)
        with open(self.out_dir / f"{name}.log", "wb") as log:
            # Unbuffered: the parent reads the child's log lines while it runs.
            proc = subprocess.Popen(argv, cwd=ROOT, env={**os.environ, "PYTHONUNBUFFERED": "1", **env},
                                    stdout=log, stderr=subprocess.STDOUT, start_new_session=True)
        self._procs.append(proc)
        return proc

    @staticmethod
    def stop(proc: subprocess.Popen, grace: float = 20.0) -> None:
        """SIGTERM the child's process group, SIGKILL what outlives the grace."""
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGTERM)
                proc.wait(timeout=grace)
            except (ProcessLookupError, subprocess.TimeoutExpired):
                pass
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()

    def stop_all(self) -> None:
        for proc in reversed(self._procs):
            self.stop(proc, grace=10.0)

    def log_path(self, name: str) -> Path:
        return self.out_dir / f"{name}.log"

    def log_tail(self, name: str, n: int = 12) -> list[str]:
        try:
            return self.log_path(name).read_text(errors="replace").splitlines()[-n:]
        except OSError:
            return []

    def log_records(self, name: str) -> list[dict]:
        """The JSON lines of a child's log (its structured log and access log)."""
        out = []
        try:
            text = self.log_path(name).read_text(errors="replace")
        except OSError:
            return out
        for line in text.splitlines():
            if line.startswith("{"):
                try:
                    out.append(json.loads(line))
                except ValueError:
                    pass
        return out


def wait_log_record(children: Children, name: str, msg: str, limit: float = 10.0) -> dict:
    """The child's structured log line with this ``msg``; a line written just
    before the child answered /health may not be in the file yet."""
    t0 = time.monotonic()
    while True:
        rec = next((r for r in children.log_records(name) if r.get("msg") == msg), None)
        if rec is not None or time.monotonic() - t0 > limit:
            return rec or {}
        time.sleep(0.1)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(port: int, method: str, path: str, body: dict | None = None,
              timeout: float = 30.0) -> tuple[int, dict | None, bytes]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        payload = json.dumps(body).encode() if body is not None else None
        conn.request(method, path, body=payload,
                     headers={"Content-Type": "application/json"} if payload else {})
        resp = conn.getresponse()
        raw = resp.read()
    finally:
        conn.close()
    try:
        return resp.status, json.loads(raw), raw
    except ValueError:
        return resp.status, None, raw


def wait_healthy(children: Children, name: str, proc: subprocess.Popen, port: int,
                 limit: float) -> float:
    """Poll /health until 200; fail when the child dies or the limit passes."""
    t0 = time.monotonic()
    while True:
        if proc.poll() is not None:
            raise BenchFailure(f"{name} exited with code {proc.returncode} before it was "
                               f"healthy: {children.log_tail(name)}")
        try:
            status, _, _ = http_json(port, "GET", "/health", timeout=5.0)
            if status == 200:
                return time.monotonic() - t0
        except OSError:
            pass
        if time.monotonic() - t0 > limit:
            raise BenchFailure(f"{name} not healthy after {limit:.0f}s: {children.log_tail(name)}")
        time.sleep(0.25)


def compile_cache_dir() -> str:
    """utils/platform.configure_compile_cache's rule, restated: the variable if
    set, else a fixed directory in the checkout. The sidecar must name this one."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")


def start_sidecar(children: Children, model: str, platform: str, args: list[str],
                  env: dict[str, str]) -> tuple[subprocess.Popen, int]:
    port = free_port()
    argv = [sys.executable, "-m", "inference_gateway_tpu.serving", "--model", model,
            "--host", "127.0.0.1", "--port", str(port), "--platform", platform, *args]
    return children.spawn("sidecar", argv, env), port


def start_gateway(children: Children, sidecar_port: int,
                  env: dict[str, str]) -> tuple[subprocess.Popen, int]:
    port = free_port()
    fixed = {"TPU_API_URL": f"http://127.0.0.1:{sidecar_port}/v1", "SERVER_HOST": "127.0.0.1",
             "SERVER_PORT": str(port)}
    return children.spawn("gateway", [sys.executable, "-m", "inference_gateway_tpu.main"],
                          {**env, **fixed}), port


def sidecar_account(port: int, platform: str, want_devices: int) -> dict:
    """What the process that holds the chip says of itself, held to what the cell
    needs. Returns the status document; raises BenchFailure on a start-up fault
    (wrong platform or device count: such a run prints no result)."""
    status, doc, raw = http_json(port, "GET", "/debug/status")
    check(status == 200 and doc is not None, f"/debug/status: HTTP {status}: {raw[:200]!r}")
    acc = doc.get("accelerator") or {}
    check(acc.get("platform") == platform,
          f"sidecar runs on {acc.get('platform')!r}, not {platform!r}")
    check(acc.get("device_count") == want_devices,
          f"sidecar holds {acc.get('device_count')} devices, the cell asks for {want_devices}")
    return doc


def account_faults(doc: dict, platform: str, want_path: str) -> list[str]:
    """The conditions ``correct`` holds the sidecar's account to, as a list of
    what failed (empty = sound)."""
    faults = []
    path = doc.get("attention_path") or {}
    if path.get("path") != want_path or path.get("mixed_step") is not True:
        faults.append(f"attention_path {path} (want {want_path!r} with the mixed step on)")
    if doc.get("engine_restarts") != 0:
        faults.append(f"engine restarted {doc.get('engine_restarts')} times")
    watchdog = doc.get("engine_watchdog") or {}
    if watchdog.get("trips") != 0:
        faults.append(f"engine watchdog: {watchdog}")
    hbm = (doc.get("device") or {}).get("hbm") or {}
    if platform == "tpu" and hbm.get("measured") is not True:
        faults.append("HBM not measured on a TPU")
    return faults


def compile_count(port: int) -> int:
    """Compilations the sidecar's ledger has seen so far (warm-up and after)."""
    status, doc, raw = http_json(port, "GET", "/debug/compile")
    check(status == 200 and doc is not None, f"/debug/compile: HTTP {status}: {raw[:200]!r}")
    return int(doc.get("compiles", 0))
