"""One `python -m dgraph_tpu alpha --p <dir>` child, driven over real HTTP.

The helpers are `chip_smoke.py`'s (PR 21), copied so that the yardstick
does not move with the program: spawn in its own session, wait for
/health, read the device the serving process itself reports, parse the
Prometheus exposition, kill. The parent that uses this never imports jax.
"""

from __future__ import annotations

import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time

_PROM = re.compile(r'^(\w+)(?:\{(.*)\})?\s+(\S+)$')
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')

# every counter `chip_smoke.py` holds to zero: a route that quietly left
# the device, or left the fast path
FALLBACK_SERIES = (
    ("fused_fallback_total", {}),
    ("fused_route_total", {"route": "fallback"}),
    ("pallas_fallback_total", {}),
    ("pallas_degraded", {}),
    ("batch_group_fallback_total", {}),
)


class HarnessError(Exception):
    pass


def parse_prom(text: str) -> list:
    """Prometheus exposition -> [(name, {label: value}, float)]."""
    out = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _PROM.match(line)
        if m is None:
            continue
        try:
            val = float(m.group(3))
        except ValueError:
            continue
        out.append((m.group(1), dict(_LABEL.findall(m.group(2) or "")),
                    val))
    return out


def msum(series: list, name: str, **labels) -> float:
    """Sum of `dgraph_tpu_<name>` over the series whose labels include
    `labels` (0.0 when none was ever emitted)."""
    full = "dgraph_tpu_" + name
    return sum(v for n, ls, v in series
               if n == full and all(ls.get(k) == x
                                    for k, x in labels.items()))


def device_of(series: list) -> dict:
    """The device the serving process reported in `build_info`."""
    for n, ls, _v in series:
        if n == "dgraph_tpu_build_info":
            return {"platform": ls.get("backend"),
                    "kind": ls.get("device_kind"),
                    "count": int(ls.get("devices", "0"))}
    raise HarnessError("the serving process exported no build_info")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def request(host: str, port: int, method: str, path: str,
            body: bytes | None = None, ctype: str | None = None,
            timeout: float = 600.0):
    """(status, body bytes) over a connection of its own (the server
    speaks HTTP/1.0: one request a connection)."""
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        headers = {"Content-Type": ctype} if ctype else {}
        conn.request(method, path, body=body, headers=headers)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def log_tail(path: str, n: int = 30) -> str:
    try:
        with open(path, "rb") as f:
            return b"".join(f.readlines()[-n:]).decode(errors="replace")
    except OSError:
        return ""


class Server:
    def __init__(self, root: str, p_dir: str, env: dict, log_path: str,
                 extra: tuple = ()):
        self.host, self.port = "127.0.0.1", free_port()
        self.log_path = log_path
        self._t_spawn = time.perf_counter()
        with open(log_path, "ab") as out:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "dgraph_tpu", "alpha", "--p", p_dir,
                 "--http_port", str(self.port),
                 "--grpc_port", str(free_port()), *extra],
                cwd=root, env=env, stdout=out, stderr=subprocess.STDOUT,
                start_new_session=True)

    def fail(self, msg: str):
        raise HarnessError(f"{msg}; server log tail:\n"
                           + log_tail(self.log_path))

    def get(self, path: str) -> bytes:
        status, body = request(self.host, self.port, "GET", path)
        if status != 200:
            self.fail(f"GET {path} answered {status}")
        return body

    def post(self, path: str, body: bytes, ctype: str):
        return request(self.host, self.port, "POST", path, body, ctype)

    def wait_healthy(self, deadline_s: float) -> float:
        """Seconds from spawn to the first 200 from /health."""
        while True:
            if self.proc.poll() is not None:
                self.fail(f"alpha exited {self.proc.returncode} before "
                          f"/health")
            if time.perf_counter() - self._t_spawn > deadline_s:
                self.fail("alpha never answered /health")
            try:
                status, _ = request(self.host, self.port, "GET", "/health",
                                    timeout=5.0)
                if status == 200:
                    return time.perf_counter() - self._t_spawn
            except OSError:
                pass
            time.sleep(0.1)

    def metrics(self) -> list:
        return parse_prom(self.get("/debug/prometheus_metrics").decode())

    def memory(self) -> dict:
        return json.loads(self.get("/debug/memory"))

    def profile(self, action: str, trace_dir: str) -> None:
        status, body = self.post(
            "/debug/profile",
            json.dumps({"action": action, "dir": trace_dir}).encode(),
            "application/json")
        if status != 200:
            self.fail(f"/debug/profile {action} answered {status}: "
                      f"{body[:200]!r}")

    def kill(self) -> None:
        """The traffic is read-only, so nothing is owed to a clean
        shutdown: kill the whole session and wait until it has ended."""
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        self.proc.wait()


def fallbacks(series: list, memory: dict) -> dict:
    out = {}
    for name, labels in FALLBACK_SERIES:
        key = name + "".join(f".{v}" for v in labels.values())
        out[key] = msum(series, name, **labels)
    out["oom_events"] = memory.get("oom", {}).get("events", 0)
    out["oom_degraded"] = len(memory.get("degraded", []))
    return out


def memory_peak_bytes(memory: dict) -> int:
    """Peak bytes in use on the fullest device, as the server reports."""
    return max((d.get("peak_bytes_in_use") or d.get("bytes_in_use") or 0
                for d in memory.get("devices", [])), default=0)
