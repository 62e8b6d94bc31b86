"""Test configuration: fake an 8-device TPU topology on CPU.

Must run before JAX initializes its backends, hence the env mutation at
import time. This gives unit tests a real multi-device mesh to shard over —
the distributed-test simulation layer the reference never had (SURVEY.md §4).
"""

import os

# JAX_PLATFORMS in the environment is all it takes: nothing imports jax
# before this module, and every child a test starts inherits it.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest


@pytest.fixture()
def tmp_workdir(tmp_path, monkeypatch):
    """An isolated workdir (data/params/logs/db) for stack tests."""
    monkeypatch.setenv("RAFIKI_WORKDIR", str(tmp_path))
    for sub in ("data", "params", "logs"):
        (tmp_path / sub).mkdir()
    return tmp_path
