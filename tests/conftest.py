"""Test configuration: run everything on a virtual 8-device CPU mesh.

Reference test strategy (SURVEY §4.2): CPU contexts impersonate devices so
multi-device semantics are tested without hardware.  The TPU equivalent is
XLA's forced host platform device count.  Must run before jax is imported.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running end-to-end test")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection chaos test (tools/chaos_run.py harness)")


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _no_stale_resume_state():
    """A test that loads a checkpoint stashes its iterator state for the
    next ``fit`` (``io_resume.note_loaded_state``); one that never fits
    would leave it for whatever test the worker runs next, whose own
    iterator then refuses it.  Which file follows which on a worker
    depends on timing, so clear it after every test."""
    yield
    from mxnet_tpu import io_resume
    io_resume.clear_pending()
