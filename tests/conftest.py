"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding is exercised
without TPU hardware (the driver separately dry-runs the multi-chip path);
the env vars must be set before jax is first imported anywhere.
"""
import os

# arm the runtime thread-affinity asserts (utils/threadcheck) for every
# test run: a production thread crossing a `# dmlint: thread(...)` seam
# fails loudly here instead of racing silently in the field. Must be set
# before any package module imports threadcheck. An explicit DM_THREADCHECK
# value from the environment (e.g. =0 to bisect) wins.
os.environ.setdefault("DM_THREADCHECK", "1")

# tests force the CPU: they need the virtual 8-device mesh, must not take a
# chip a service on this host may hold, and must behave the same on a
# machine that has one
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import socket
import threading
import time
from pathlib import Path

import pytest

from detectmateservice_tpu.engine.socket import InprocQueueSocketFactory


_SLOW_FILES = {
    # XLA-compile-heavy: every test jit-compiles models (often over the
    # virtual 8-device mesh); together they dominate suite wall-time
    "test_models.py",
    "test_jax_scorer.py",
    "test_parallel.py",
    "test_flash.py",
    "test_distributed.py",
    "test_concurrency.py",
    "test_perf.py",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if (item.fspath.basename in _SLOW_FILES
                or "MeshServiceEndToEnd" in item.nodeid
                or "ServiceCheckpointLifecycle" in item.nodeid):
            item.add_marker(pytest.mark.slow)


@pytest.fixture()
def free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture(scope="session")
def tls_material(tmp_path_factory):
    """Throwaway CA + server cert via the openssl CLI (the reference's
    approach, tests/test_tls_transport.py:52-99). Session-scoped: one
    keypair serves every TLS test (transport, nng wire, chaos)."""
    import subprocess

    d = tmp_path_factory.mktemp("tls")
    ca_key, ca_crt = d / "ca.key", d / "ca.crt"
    srv_key, srv_csr, srv_crt = d / "srv.key", d / "srv.csr", d / "srv.crt"
    cert_key = d / "server_bundle.pem"

    def run(*cmd):
        subprocess.run(cmd, check=True, capture_output=True)

    run("openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
        "-keyout", str(ca_key), "-out", str(ca_crt), "-days", "1",
        "-subj", "/CN=testca")
    run("openssl", "req", "-newkey", "rsa:2048", "-nodes",
        "-keyout", str(srv_key), "-out", str(srv_csr), "-subj", "/CN=localhost")
    run("openssl", "x509", "-req", "-in", str(srv_csr), "-CA", str(ca_crt),
        "-CAkey", str(ca_key), "-CAcreateserial", "-out", str(srv_crt),
        "-days", "1")
    cert_key.write_text(srv_crt.read_text() + srv_key.read_text())
    return {"ca_file": str(ca_crt), "cert_key_file": str(cert_key)}


@pytest.fixture()
def inproc_factory() -> InprocQueueSocketFactory:
    return InprocQueueSocketFactory()


@pytest.fixture()
def ipc_addr(tmp_path: Path) -> str:
    return f"ipc://{tmp_path}/engine.ipc"


def wait_until(predicate, timeout: float = 5.0, interval: float = 0.02) -> bool:
    """Poll ``predicate`` until truthy or timeout."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


@pytest.fixture()
def run_service():
    """Run a Service.run() on a daemon thread; always shut down at teardown."""
    from detectmateservice_tpu.core import Service

    started = []

    def _run(service: Service) -> Service:
        thread = threading.Thread(target=service.run, daemon=True)
        thread.start()
        started.append((service, thread))
        # with http_port=0 the real port is only known once the server binds
        assert wait_until(lambda: service.web_server.port, 5.0)
        return service

    yield _run

    for service, thread in started:
        try:
            service.shutdown()
        except Exception:
            pass
        thread.join(timeout=5.0)
