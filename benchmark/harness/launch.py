"""Starting, probing and stopping the system under test.

Copied from `chip_smoke.py` (PR 21, proven on the chip): `child_env`,
`wait_for`, `device_line`, `sock_accepts`, `free_port`, `tail`, `stop`,
and the native build, which here runs plain `make` (a rebuild only when a
binary is missing or older than its source) instead of `make -B`.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import time
import urllib.error
import urllib.request
from pathlib import Path


class BenchFailure(Exception):
    """The run cannot produce a result; exit non-zero, print no result line."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise BenchFailure(what)


def build_native(repo: Path) -> None:
    for d in ("native/sidecar", "native/confirm"):
        check((repo / d / "Makefile").exists(),
              "%s/Makefile is missing: run from the root of a checkout" % d)
        subprocess.run(["make", "-C", str(repo / d)], check=True,
                       stdout=subprocess.DEVNULL, timeout=300)


def child_env(repo: Path, rehearsal: bool, **extra) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(repo) + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("IPT_NO_NATIVE_CONFIRM", None)
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tail(path: Path, n: int = 40) -> str:
    try:
        return "\n".join(path.read_text(errors="replace").splitlines()[-n:])
    except OSError:
        return "(no log)"


def wait_for(what: str, probe, proc, log: Path, timeout: float):
    """Poll `probe()` until truthy; the child dying, or the clock, fails
    the run with the child's last words."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = probe()
        if got:
            return got
        if proc.poll() is not None:
            raise BenchFailure("%s: process exited %d\n%s"
                               % (what, proc.returncode, tail(log)))
        time.sleep(0.1)
    raise BenchFailure("%s: not within %.0fs\n%s" % (what, timeout, tail(log)))


def sock_accepts(path: str) -> bool:
    if not os.path.exists(path):
        return False
    try:
        with socket.socket(socket.AF_UNIX) as s:
            s.connect(path)
        return True
    except OSError:
        return False


def device_line(log: Path):
    """The server's first statement: the device JAX gave it."""
    for line in log.read_text(errors="replace").splitlines():
        if line.startswith("device: "):
            return json.loads(
                line[len("device: "):].split("  compile_cache=")[0])
    return None


def log_line(log: Path, prefix: str):
    """The last line of `log` that starts with `prefix`, without it."""
    found = None
    for line in log.read_text(errors="replace").splitlines():
        if line.startswith(prefix):
            found = line[len(prefix):]
    return found


def http_get(port: int, path: str, timeout: float = 10.0) -> bytes:
    with urllib.request.urlopen(
            "http://127.0.0.1:%d%s" % (port, path), timeout=timeout) as r:
        return r.read()


def http_json(port: int, path: str):
    try:
        with urllib.request.urlopen(
                "http://127.0.0.1:%d%s" % (port, path), timeout=10) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def stop(proc, name: str, log: Path, timeout: float = 90.0) -> float:
    """SIGTERM, then wait: the chip is free only once the process is
    gone.  Returns the seconds it took."""
    t0 = time.monotonic()
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)
            raise BenchFailure("%s ignored SIGTERM for %.0fs\n%s"
                               % (name, timeout, tail(log)))
    return time.monotonic() - t0
