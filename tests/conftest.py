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

import socket  # noqa: E402

import pytest  # noqa: E402

# The multi-process tests hand a cluster a run of consecutive ports that were
# free when looked at, and the cluster binds them a moment later. Every xdist
# worker scans a slice of its own (no two workers can be handed one range in
# that moment), successive calls in one process move on through the slice (a
# cluster still shutting down keeps its ports to itself), and the whole range
# lies below the kernel's ephemeral ports (32768 up), which any outgoing
# connection may take at any time.
_PORTS = range(23000, 32700)
_port_cursor = 0


def free_port_base(n: int) -> int:
    """A base port such that ``base .. base + n`` are free right now."""
    global _port_cursor
    worker = os.environ.get("PYTEST_XDIST_WORKER", "gw0")
    n_workers = max(int(os.environ.get("PYTEST_XDIST_WORKER_COUNT", "1")), 1)
    index = int(worker[2:]) % n_workers if worker[2:].isdigit() else 0
    width = len(_PORTS) // n_workers
    lo = _PORTS.start + index * width
    step = n + 3
    for _ in range(width // step):
        base = lo + _port_cursor % (width - step)
        _port_cursor += step
        socks = []
        try:
            for p in range(base, base + n + 1):
                s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
                s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                s.bind(("127.0.0.1", p))
                socks.append(s)
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError("no free port range found")


@pytest.fixture(autouse=True)
def _fresh_graph():
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    yield
    G.clear()
