"""One ``python -m pilosa_tpu server`` child: the process that holds the
chip.  The benchmark's own process stays off JAX and talks HTTP.
(Pattern of ``chip_smoke.py``'s ``ServerProcess``, copied, not
imported; the server runs with its shipped defaults: only ``data-dir``
and ``bind`` are set.)"""

from __future__ import annotations

import ctypes
import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class BenchFailure(Exception):
    """The run cannot produce a result line."""


class Conn:
    """A keep-alive HTTP connection of one client thread."""

    def __init__(self, host: str, port: int, timeout: float = 120.0):
        self.host, self.port, self.timeout = host, port, timeout
        self.c: http.client.HTTPConnection | None = None

    def request(self, method: str, path: str, body: bytes | None = None,
                ctype: str = "application/json") -> tuple[int, bytes]:
        """(status, body).  One reconnect on a dropped idle
        connection; any other transport error is the caller's."""
        for attempt in (0, 1):
            if self.c is None:
                self.c = http.client.HTTPConnection(
                    self.host, self.port, timeout=self.timeout)
                self.c.connect()
                self.c.sock.setsockopt(socket.IPPROTO_TCP,
                                       socket.TCP_NODELAY, 1)
            try:
                self.c.request(method, path, body=body,
                               headers={"Content-Type": ctype})
                resp = self.c.getresponse()
                return resp.status, resp.read()
            except (http.client.RemoteDisconnected, BrokenPipeError,
                    ConnectionResetError):
                self.close()
                if attempt:
                    raise
        raise AssertionError("unreachable")

    def close(self) -> None:
        if self.c is not None:
            self.c.close()
            self.c = None


def _die_with_parent() -> None:
    """In the child, before exec: SIGKILL for the server when the
    harness dies, however it dies (Linux's PR_SET_PDEATHSIG), so that a
    run killed from outside leaves no process behind."""
    ctypes.CDLL(None).prctl(1, signal.SIGKILL)


#: servers started and not yet ended, so that a failing run can end them
LIVE: list["ServerProcess"] = []


class ServerProcess:
    def __init__(self, work: str, rehearse: bool = False):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.host = "127.0.0.1"
        self.data_dir = os.path.join(work, "data")
        cfg = os.path.join(work, "server.toml")
        with open(cfg, "w") as f:
            f.write(f'data-dir = "{self.data_dir}"\n'
                    f'bind = "{self.host}:{self.port}"\n')
        env = dict(os.environ)
        env.pop("PILOSA_TPU_SHARD_WIDTH_EXP", None)  # tests pin 2^16
        env.pop("BENCH_RUN", None)
        if rehearse:
            env["JAX_PLATFORMS"] = "cpu"
        self.log_path = os.path.join(work, "server.log")
        self.log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu", "server", "-c", cfg],
            cwd=ROOT, env=env, stdout=self.log, stderr=subprocess.STDOUT,
            preexec_fn=_die_with_parent)
        self._conn = Conn(self.host, self.port, timeout=600)
        LIVE.append(self)

    def conn(self, timeout: float = 120.0) -> Conn:
        return Conn(self.host, self.port, timeout)

    def call(self, method: str, path: str, obj=None):
        """A control-plane JSON call on the harness's own connection;
        anything but 200 fails the run."""
        body = None if obj is None else json.dumps(obj).encode()
        status, data = self._conn.request(method, path, body)
        if status != 200:
            raise BenchFailure(f"{method} {path} -> HTTP {status}: "
                               f"{data[:400]!r}")
        return json.loads(data or b"null")

    def wait_ready(self, limit_s: float = 420.0) -> dict:
        """Block until /status says NORMAL; returns the server's own
        description of its backend."""
        deadline = time.monotonic() + limit_s
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise BenchFailure(
                    f"server exited with {self.proc.returncode} before "
                    f"it served:\n{self.log_tail()}")
            try:
                st = self.call("GET", "/status")
                if st.get("state") == "NORMAL":
                    return st["backend"]
            except (BenchFailure, OSError, http.client.HTTPException):
                self._conn.close()
            time.sleep(0.2)
        raise BenchFailure(f"server did not serve in {limit_s:.0f} s:\n"
                           + self.log_tail())

    def stop(self) -> None:
        """SIGTERM, wait, and fail the run on a bad exit."""
        self._conn.close()
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                self.kill()
                raise BenchFailure("server ignored SIGTERM for 120 s") \
                    from None
        self.log.close()
        if self in LIVE:
            LIVE.remove(self)
        if self.proc.returncode != 0:
            raise BenchFailure(f"server exited with "
                               f"{self.proc.returncode}:\n{self.log_tail()}")

    def kill(self) -> None:
        self._conn.close()
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        if not self.log.closed:
            self.log.close()
        if self in LIVE:
            LIVE.remove(self)

    def log_tail(self, n: int = 40) -> str:
        if not self.log.closed:
            self.log.flush()
        with open(self.log_path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
