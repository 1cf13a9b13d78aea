import os
import sys

# tests are run with PYTHONPATH=src; this makes bare `pytest` work too.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

# GP core enables x64 on import; keep the whole test session consistent.
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _close_open_remote_handles(monkeypatch):
    """Close every remote job handle a test left open, when it ends.

    An open handle keeps its lease renewer running. Once the test's
    replicas are shut down (which severs live connections), every renewal
    tick fails and counts ``client.heartbeat_error`` in the process-wide
    telemetry registry that later tests in the same worker read."""
    from repro.distributed import engine_client

    opened = []
    start = engine_client.RemoteJobHandle._start_heartbeats

    def recording_start(handle):
        opened.append(handle)
        start(handle)

    monkeypatch.setattr(
        engine_client.RemoteJobHandle, "_start_heartbeats", recording_start
    )
    yield
    for handle in opened:
        handle.close()
