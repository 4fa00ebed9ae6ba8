"""Spawn and supervise replica PROCESSES (bench, smoke, chaos). Each
replica is a fresh interpreter running `replica_main` with a JSON
config; the launcher waits for the `FLEET_REPLICA_READY port=...`
rendezvous line and hands back a ReplicaProcess whose pid the chaos
harness's ReplicaKill can target. Stdout/stderr stream to a log file
so a dead replica leaves evidence."""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time
from typing import Optional


class ReplicaLaunchError(RuntimeError):
    """The replica process died or never reported ready in time."""


class ReplicaProcess:
    """Handle on one spawned replica: name/role/url for the router,
    pid for the chaos harness, terminate() for clean teardown."""

    def __init__(self, name: str, role: str, port: int,
                 proc: subprocess.Popen, log_path: str):
        self.name = name
        self.role = role
        self.port = port
        self.proc = proc
        self.log_path = log_path

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    @property
    def pid(self) -> int:
        return self.proc.pid

    def alive(self) -> bool:
        return self.proc.poll() is None

    def handle(self):
        """Router-side record for this process."""
        from deeplearning4j_tpu.serving.fleet.router import ReplicaHandle
        return ReplicaHandle(self.name, self.url, self.role)

    def terminate(self, timeout: float = 10.0) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=timeout)

    def tail(self, n: int = 40) -> str:
        try:
            with open(self.log_path, "r", errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""


def launch_replica(config: dict, *, timeout_s: float = 120.0,
                   env: Optional[dict] = None,
                   log_dir: Optional[str] = None) -> ReplicaProcess:
    """Start one replica process from a declarative config and block
    until its HTTP server is up. The child inherits this interpreter
    (no install assumptions) and this environment plus `env`, and runs
    on the platform JAX resolves from it: a caller that wants CPU
    replicas passes `env={"JAX_PLATFORMS": "cpu"}`. A chip belongs to
    one process, so replicas on chips need one chip each."""
    name = config.get("name", "replica")
    log_dir = log_dir or tempfile.mkdtemp(prefix="fleet_")
    log_path = os.path.join(log_dir, f"{name}.log")
    child_env = dict(os.environ)
    child_env.update(env or {})
    child_env["FLEET_REPLICA_CONFIG"] = json.dumps(config)
    log = open(log_path, "w")
    proc = subprocess.Popen(
        [sys.executable, "-m",
         "deeplearning4j_tpu.serving.fleet.replica_main"],
        stdout=subprocess.PIPE, stderr=log, env=child_env, text=True)
    deadline = time.monotonic() + timeout_s
    port = None
    try:
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if not line:
                if proc.poll() is not None:
                    break
                continue
            line = line.strip()
            if line.startswith("FLEET_REPLICA_READY"):
                port = int(line.split("port=", 1)[1])
                break
    finally:
        log.close()
    if port is None:
        rc = proc.poll()
        try:
            with open(log_path, "r", errors="replace") as f:
                tail = "".join(f.readlines()[-20:])
        except OSError:
            tail = ""
        proc.kill()
        raise ReplicaLaunchError(
            f"replica {name!r} never became ready "
            f"(exit={rc}); log tail:\n{tail}")
    return ReplicaProcess(name, config.get("role", "mixed"), port,
                          proc, log_path)
