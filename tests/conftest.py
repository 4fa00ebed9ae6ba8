"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax init.

Mirrors the reference's distributed-without-a-cluster strategy (Spark
`local[N]` — `BaseSparkTest.java:89`): multi-chip sharding is tested on
virtual CPU devices; the chip is measured by `benchmarks/run.py`.
float64 is enabled for gradient checks (reference runs them in double).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never run on the chip
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running scale tests (always on in CI; "
        "deselect locally with -m 'not slow')")
    config.addinivalue_line(
        "markers", "chaos: fault-injection / recovery tests "
        "(tools/ci_check.sh --chaos runs exactly these)")
    config.addinivalue_line(
        "markers", "rehearsal: a chip-smoke phase run whole at a tiny "
        "size; tens of seconds each, so they are collected last")


def pytest_collection_modifyitems(config, items):
    # the tier-1 command has a time limit: what it cuts should be a long
    # rehearsal, not the unit tests that sort after it
    last = [it for it in items if it.get_closest_marker("rehearsal")]
    if last:
        items[:] = [it for it in items
                    if not it.get_closest_marker("rehearsal")] + last


@pytest.fixture(scope="session")
def devices8():
    d = jax.devices()
    assert len(d) >= 8, f"expected 8 virtual devices, got {len(d)}"
    return d


# --------------------------------------------------------------- mp probe
_MP_PROBE = None

_MP_PROBE_WORKER = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.distributed.initialize(os.environ["PROBE_ADDR"],
                           int(os.environ["PROBE_N"]),
                           int(os.environ["PROBE_ID"]))
import jax.numpy as jnp
from jax.experimental import multihost_utils
out = multihost_utils.process_allgather(jnp.ones((2,)))
print("MP_PROBE_OK", out.shape)
"""


def multiprocess_pod_supported():
    """Probe (once per session) whether THIS jaxlib can run cross-process
    collectives on the CPU backend: spawn a minimal 2-process pod that
    does one allgather. Some jaxlib builds refuse with 'Multiprocess
    computations aren't implemented on the CPU backend' — on those, the
    multi-process pod tests are environmentally impossible and must skip
    with that reason rather than error."""
    global _MP_PROBE
    if _MP_PROBE is not None:
        return _MP_PROBE
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    procs = []
    for pid in range(2):
        env = dict(os.environ,
                   PROBE_ADDR=f"localhost:{port}", PROBE_N="2",
                   PROBE_ID=str(pid), JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _MP_PROBE_WORKER], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs, ok, reason = [], True, ""
    for p in procs:
        try:
            out, _ = p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            _MP_PROBE = (False, "2-process probe pod timed out")
            return _MP_PROBE
        outs.append(out)
        if p.returncode != 0 or "MP_PROBE_OK" not in out:
            ok = False
            tail = [ln for ln in out.splitlines() if ln.strip()]
            reason = tail[-1][:200] if tail else f"rc={p.returncode}"
    _MP_PROBE = (True, "") if ok else (False, reason)
    return _MP_PROBE


@pytest.fixture(scope="session")
def multiprocess_env():
    """Skip (with the probe's reason) when multi-process JAX pods cannot
    run in this environment — keeps tier-1 signal, not noise."""
    ok, reason = multiprocess_pod_supported()
    if not ok:
        pytest.skip(f"multi-process env absent: {reason}")
