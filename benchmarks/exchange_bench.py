"""Exchange-plane benchmark: host split-and-deliver vs the on-device
all_to_all plane, same blocks, same routing (VERDICT r4 #1 acceptance).

Runs standalone on an 8-device virtual CPU mesh (bench.py invokes it as a
subprocess with JAX_PLATFORMS=cpu + xla_force_host_platform_device_count:
the exchange is a multi-device collective and the parent holds the chip), so
its rates count host work and are not device numbers. Prints one JSON line:
``{"device_exchange_rows_per_s": ..., "host_exchange_rows_per_s": ...}``.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

# must precede any jax import
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# also through the config API, which works even if jax was imported before
# this file set the environment (tests/conftest.py)
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

N_WORKERS = 8
ROWS_PER_WORKER = 16384
N_COLS = 3  # int64 value columns
REPS = 12


def _make_blocks(rng):
    from pathway_tpu.engine.blocks import DeltaBatch

    blocks = []
    for w in range(N_WORKERS):
        n = ROWS_PER_WORKER
        keys = rng.integers(1, 2**63, n).astype(np.uint64)
        data = {
            f"c{j}": rng.integers(0, 10**9, n).astype(np.int64) for j in range(N_COLS)
        }
        blocks.append(DeltaBatch(keys, np.ones(n, dtype=np.int64), data, 0))
    return blocks


def bench_host(blocks) -> float:
    from pathway_tpu.parallel.mesh import shard_of_keys

    sink: list = []

    def once():
        sink.clear()
        for b in blocks:
            shards = shard_of_keys(b.keys, N_WORKERS)
            for w in np.unique(shards):
                sink.append(b.take(np.flatnonzero(shards == w)))

    once()  # warmup
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        once()
        times.append(time.perf_counter() - t0)
    total = N_WORKERS * ROWS_PER_WORKER
    return total / statistics.median(times)


def bench_device(blocks) -> float:
    import jax

    from pathway_tpu.parallel.device_plane import DeviceExchangePlane

    plane = DeviceExchangePlane(N_WORKERS, force=True)
    assert plane.available(), "virtual mesh missing"
    sink: list = []

    def deliver(w, ci, port, batch):
        sink.append(batch)

    def once():
        sink.clear()
        for w, b in enumerate(blocks):
            plane.stage(0, 0, w, b.keys, b)
        plane.flush(deliver, 0)

    once()  # warmup: pays the jit compile
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        once()
        times.append(time.perf_counter() - t0)
    total = N_WORKERS * ROWS_PER_WORKER
    n_out = sum(len(b) for b in sink)
    assert n_out == total, f"lost rows: {n_out} != {total}"
    return total / statistics.median(times)


def main() -> None:
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    rng = np.random.default_rng(0)
    blocks = _make_blocks(rng)
    host = bench_host(blocks)
    dev = bench_device(blocks)
    print(
        json.dumps(
            {
                "host_exchange_rows_per_s": round(host),
                "device_exchange_rows_per_s": round(dev),
                "device_vs_host_exchange": round(dev / host, 2),
                "exchange_workers": N_WORKERS,
                "exchange_rows_per_worker": ROWS_PER_WORKER,
            }
        )
    )


if __name__ == "__main__":
    main()
