"""Test configuration: the CPU backend with an 8-device virtual mesh.

JAX_PLATFORMS=cpu and xla_force_host_platform_device_count=8 are set
before jax is imported, so the multi-chip sharding paths run on virtual
CPU devices and no test ever takes the chip from a server. The check
below proves it held.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

_devs = {d.platform for d in jax.devices()}
if _devs != {"cpu"}:
    raise RuntimeError(f"tests must run on the CPU backend, found {_devs}")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_compaction_governor():
    """The compaction governor is a process singleton (one per node in
    real deployments); in-process sim clusters share it, so a cluster
    stagger grant issued in one test must not gate env-triggered
    compactions in the next."""
    yield
    try:
        from pegasus_tpu.storage.compact_governor import GOVERNOR
    except Exception:  # noqa: BLE001 - package not imported by this test
        return
    GOVERNOR._grant = None
    GOVERNOR._heavy_waiting = False
    GOVERNOR.heavy_running = 0
    GOVERNOR._throttle_mbps = 0.0
    GOVERNOR._engaged_at_mbps = 0.0
    GOVERNOR._pressure_last = None


@pytest.fixture(autouse=True)
def _reset_tenant_registry():
    """The tenant QoS registry is a process singleton too; a SimCluster
    pins its governor clock to the (dead, frozen) sim loop and a test's
    tenant budgets / brownout verdicts would leak into the next test."""
    yield
    try:
        from pegasus_tpu.server.tenancy import TENANTS
    except Exception:  # noqa: BLE001 - package not imported by this test
        return
    TENANTS.reset()


@pytest.fixture
def started_threads(monkeypatch):
    """Names of the threads started while the test runs, in order (a
    compaction's stage threads are `compact-read`, `compact-filter`)."""
    import threading

    started = []
    real_start = threading.Thread.start

    def start(thread):
        started.append(thread.name)
        real_start(thread)

    monkeypatch.setattr(threading.Thread, "start", start)
    return started
