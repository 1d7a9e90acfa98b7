"""Where the time of one ``knn.search`` launch goes: the device's operations
inside ``jit__search_kernel`` at the retrieve cell's index (4,194,304 slots,
3,016,384 live, float32 x 384, cosine) for each static k asked for.

    chiprun -- python3 benchmarks/knn_search_split.py --k 64,8 --queries 2

Prints, per k, the launch's device time and its operations in order of cost,
each with the head of its HLO instruction (the output shape tells the stage:
``[Q, 4194304]`` is the einsum or an elementwise pass over the scores,
``[Q, 16384, k]`` stage 1 of a ``_topk_rows``, ``[Q, k]`` out of ``[Q, 16384*k]``
its stage 2), and writes the compiled HLO to ``chiprun_out/knn_split_k<k>.hlo``
so a fusion's name can be looked up. A chip tool, not a benchmark: it refuses
to run off a TPU."""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import reduce_trace  # noqa: E402
from pathway_tpu.ops import knn  # noqa: E402

BLOCK = 262_144


def build(capacity: int, live: int, dim: int):
    """The index's four arrays, made on the device a block at a time (one
    6.4 GB draw would need its bits beside it)."""
    fill = jax.jit(
        lambda v, key, at: jax.lax.dynamic_update_slice(
            v, jax.random.normal(key, (BLOCK, dim), jnp.float32), (at, 0)
        ),
        donate_argnums=0,
    )
    vectors = jnp.zeros((capacity, dim), jnp.float32)
    for i, at in enumerate(range(0, capacity, BLOCK)):
        vectors = fill(vectors, jax.random.PRNGKey(i), at)
    norms_sq = jax.jit(lambda v: jnp.sum(v * v, axis=-1))(vectors)
    valid = jnp.arange(capacity) < live
    key_bits = jax.random.bits(jax.random.PRNGKey(7), (capacity,), jnp.uint32)
    return vectors, norms_sq, valid, key_bits


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--k", default="64,8")
    ap.add_argument("--queries", type=int, default=2)
    ap.add_argument("--capacity", type=int, default=4_194_304)
    ap.add_argument("--live", type=int, default=3_016_384)
    ap.add_argument("--dim", type=int, default=384)
    ap.add_argument("--launches", type=int, default=10)
    ap.add_argument("--top", type=int, default=24)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("knn_search_split: no TPU here; device times come only from the chip", file=sys.stderr)
        return 2
    index = build(args.capacity, args.live, args.dim)
    q = jax.random.normal(jax.random.PRNGKey(11), (args.queries, args.dim), jnp.float32)
    os.makedirs("chiprun_out", exist_ok=True)
    for k in [int(x) for x in args.k.split(",")]:
        call = lambda: knn._search_kernel(*index, q, k=k, metric="cos")  # noqa: E731
        with open(f"chiprun_out/knn_split_k{k}.hlo", "w", encoding="utf-8") as f:
            f.write(knn._search_kernel.fn.lower(*index, q, k=k, metric="cos").compile().as_text())
        for _ in range(3):
            jax.block_until_ready(call())
        trace_dir = tempfile.mkdtemp(prefix="knn_split_")
        jax.profiler.start_trace(trace_dir)
        for _ in range(args.launches):
            np.asarray(call()[0])
        jax.profiler.stop_trace()
        events = [e for e in reduce_trace.load(trace_dir) if e[0].startswith("/device:")]
        shutil.rmtree(trace_dir, ignore_errors=True)
        launches = sorted((s, s + d) for _p, line, n, s, d in events
                          if line == reduce_trace.MODULES and n.startswith("jit__search_kernel"))
        ops: dict[str, list] = {}
        for _p, line, n, s, d in events:
            if line == reduce_trace.OPS and any(a <= s < b for a, b in launches):
                ent = ops.setdefault(n.split(" = ")[0], [0, 0, n[:200]])
                ent[0] += d
                ent[1] += 1
        n_l = len(launches)
        print(f"k={k} queries={args.queries}: {n_l} launches, "
              f"{sum(b - a for a, b in launches) / n_l / 1e6:.3f} ms a launch, "
              f"ops {sum(v[0] for v in ops.values()) / n_l / 1e6:.3f} ms, {len(ops)} distinct")
        for dur, count, head in sorted(ops.values(), reverse=True)[: args.top]:
            print(f"  {dur / n_l / 1e6:8.3f} ms x{count // n_l} {head}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
