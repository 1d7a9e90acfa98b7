"""Test configuration.

Multi-chip sharding tests run on a virtual 8-device CPU mesh (the reference tests
multi-worker the same way — N local processes on loopback,
``integration_tests/wordcount/conftest.py``): set platform env BEFORE jax imports.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Something imported before this file may already have imported jax and read
# JAX_PLATFORMS: set the platform through the config API too, which works
# until the backend initialises.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_graph():
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    yield
    G.clear()
