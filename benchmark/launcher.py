"""Process launcher: discd + worker(s) + frontend as child processes.

Copied from ``chip_smoke.py`` (``Child`` / ``Cluster`` / ``one_chip_env``),
which PR 21 proved on the chip, so that a later PR may change the smoke
without changing the yardstick. This module NEVER imports JAX, nor a module
that does: a chip belongs to one process at a time, and a parent that had
touched JAX would hold it while its children fail or hang. Every child has a
file-backed log (a PIPE nobody drains blocks the child) and dies with its
parent (``PR_SET_PDEATHSIG``).
"""

from __future__ import annotations

import ctypes
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.realpath(__file__))
ROOT = os.path.dirname(HERE)  # the checkout: children run from here
HOST = "127.0.0.1"


class BenchFailure(Exception):
    """The run cannot produce a result: no chip, a child died, a route
    failed. Never used for a slow or failed *request* — that is data."""


def _die_with_parent() -> None:
    PR_SET_PDEATHSIG = 1
    ctypes.CDLL("libc.so.6", use_errno=True).prctl(
        PR_SET_PDEATHSIG, signal.SIGKILL
    )


class Child:
    """One child process with a file-backed log."""

    live: List["Child"] = []

    def __init__(self, name: str, argv: List[str], env: Dict[str, str],
                 logdir: str) -> None:
        self.name = name
        self.log_path = os.path.join(logdir, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(
            argv, env=env, cwd=ROOT, stdout=self._log,
            stderr=subprocess.STDOUT, preexec_fn=_die_with_parent,
        )
        Child.live.append(self)

    def log_text(self) -> str:
        with open(self.log_path, "rb") as f:
            return f.read().decode("utf-8", "replace")

    def tail(self, nbytes: int = 3000) -> str:
        return self.log_text()[-nbytes:]

    def running(self) -> bool:
        return self.proc.poll() is None

    def stop(self, sig: int = signal.SIGTERM, timeout: float = 20.0) -> int:
        """Signal, wait ``timeout``, then SIGKILL. A benchmark run does not
        pay for a graceful drain: the streams have ended already."""
        if self.running():
            self.proc.send_signal(sig)
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
        self._close()
        return self.proc.returncode

    def run_to_end(self, timeout: float) -> int:
        try:
            self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait(timeout=30)
        self._close()
        return self.proc.returncode

    def _close(self) -> None:
        if self in Child.live:
            Child.live.remove(self)
        if not self._log.closed:
            self._log.close()


def kill_all_children() -> None:
    for child in list(Child.live):
        if child.running():
            child.proc.kill()
        try:
            child.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass
        child._close()


def check_alive(children: List[Child]) -> None:
    for child in children:
        if not child.running():
            raise BenchFailure(
                f"{child.name} exited early with code "
                f"{child.proc.returncode}\n--- {child.name} log tail ---\n"
                f"{child.tail()}"
            )


def wait_for_line(child: Child, needle: str, timeout: float,
                  others: List[Child]) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if needle in child.log_text():
            return
        check_alive([child] + others)
        time.sleep(0.2)
    raise BenchFailure(
        f"{child.name}: {needle!r} not seen within {timeout:.0f}s\n"
        f"--- {child.name} log tail ---\n{child.tail()}"
    )


def free_port() -> int:
    with socket.socket() as s:
        s.bind((HOST, 0))
        return s.getsockname()[1]


def http_json(url: str, body: Optional[dict] = None,
              timeout: float = 60.0) -> Any:
    """GET, or POST when there is a body; the decoded JSON reply."""
    req = urllib.request.Request(
        url, data=json.dumps(body).encode() if body is not None else None,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read().decode())
    except urllib.error.HTTPError as exc:
        raise BenchFailure(
            f"{url} -> HTTP {exc.code}: {exc.read().decode()[:2000]}"
        ) from exc


# One process for each chip (established on a four-chip v5e host, chip run
# of PR 21): libtpu gives a process exactly the chip these name, and such
# processes run side by side. Each then sees ONE device, renumbered to id 0.
def one_chip_env(chip: int) -> Dict[str, str]:
    return {
        "TPU_VISIBLE_CHIPS": str(chip),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_MESH_CONTROLLER_ADDRESS": f"{HOST}:{8476 + chip}",
        "TPU_MESH_CONTROLLER_PORT": str(8476 + chip),
    }


class Cluster:
    """discd + worker(s) + frontend over discd/ZMQ/TCP, the README quick
    start. The wiring variables below are the transport's addresses, not
    tuning knobs."""

    def __init__(self, logdir: str, base_env: Dict[str, str]) -> None:
        self.logdir = logdir
        self.disc_port, self.xsub, self.xpub = free_port(), free_port(), free_port()
        self.http_port = free_port()
        self.env = dict(base_env)
        self.env.update({
            "DYN_TPU_DISCOVERY": "discd",
            "DYN_TPU_DISCOVERY_ADDR": f"{HOST}:{self.disc_port}",
            "DYN_TPU_EVENT_PLANE": "zmq",
            "DYN_TPU_EVENT_PLANE_ADDR": f"{HOST}:{self.xsub}:{self.xpub}",
            "DYN_TPU_REQUEST_PLANE": "tcp",
            "PYTHONUNBUFFERED": "1",
        })
        self.discd: Optional[Child] = None
        self.frontend: Optional[Child] = None
        self.workers: List[Tuple[Child, int]] = []  # (child, system port)

    @property
    def base(self) -> str:
        return f"http://{HOST}:{self.http_port}"

    def worker_url(self, idx: int) -> str:
        return f"http://{HOST}:{self.workers[idx][1]}"

    def children(self) -> List[Child]:
        out = [w for w, _ in self.workers]
        if self.discd:
            out.append(self.discd)
        if self.frontend:
            out.append(self.frontend)
        return out

    def start_discd(self) -> None:
        self.discd = Child(
            "discd",
            [sys.executable, "-m", "dynamo_tpu.discd", "--port",
             str(self.disc_port), "--xsub", str(self.xsub), "--xpub",
             str(self.xpub)],
            self.env, self.logdir,
        )
        wait_for_line(self.discd, "discd ready", 60, [])

    def spawn_worker(self, name: str, args: List[str],
                     env_extra: Optional[Dict[str, str]] = None) -> Child:
        port = free_port()
        child = Child(
            name,
            [sys.executable, "-m", "dynamo_tpu.worker", "--system-port",
             str(port), *args],
            dict(self.env, **(env_extra or {})), self.logdir,
        )
        self.workers.append((child, port))
        return child

    def wait_workers(self, timeout: float) -> None:
        for child, _ in self.workers:
            wait_for_line(child, "worker serving", timeout, self.children())

    def start_frontend(self, model: str, args: List[str]) -> None:
        self.frontend = Child(
            "frontend",
            [sys.executable, "-m", "dynamo_tpu.frontend", "--host", HOST,
             "--http-port", str(self.http_port), *args],
            self.env, self.logdir,
        )
        wait_for_line(self.frontend, "frontend listening", 60, self.children())
        deadline = time.monotonic() + 60
        while True:
            ids = [m["id"] for m in http_json(self.base + "/v1/models")["data"]]
            if model in ids:
                return
            if time.monotonic() > deadline:
                raise BenchFailure(f"model {model!r} never appeared: {ids}")
            check_alive(self.children())
            time.sleep(0.25)

    def stop(self) -> None:
        """SIGTERM frontend, workers, discd; a short wait, then SIGKILL.
        Nothing is read from the logs: what a log says is not a result."""
        if self.frontend:
            self.frontend.stop(timeout=10)
        for child, _ in self.workers:  # signal all, then wait for each
            if child.running():
                child.proc.send_signal(signal.SIGTERM)
        for child, _ in self.workers:
            child.stop(timeout=20)
        if self.discd:
            self.discd.stop(timeout=10)
