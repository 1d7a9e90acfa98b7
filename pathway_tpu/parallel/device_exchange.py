"""On-device key-shard exchange for relational blocks (SURVEY §5.8 end state).

The reference exchanges records between workers over timely's channels
(shared memory / TCP); the host plane here does the same with pickled blocks
(``parallel/cluster.py``). This module is the ICI/DCN data plane the north
star calls for: NUMERIC column blocks are re-sharded **on device** with one
``lax.all_to_all`` per tick — rows ride the interconnect as dense tensors,
with the shard function identical to the host plane — both go through the
ONE placement authority ``internals/keys.shard_of_keys`` (low key bits mod
worker count, ``shard.rs`` parity). The in-kernel modulo below is the
``dest=None`` fast path only; when a versioned shard map is active
(``PATHWAY_SHARDMAP``, ``internals/shardmap``), callers compute destinations
host-side via ``shard_of_keys(..., shard_map=...)`` and pass explicit
``dest`` so the kernel never re-derives ownership.

Shape discipline (XLA needs static shapes): every device holds a fixed
``capacity``-row block with a validity mask; the kernel buckets rows by
destination into an ``(n_shards, capacity)`` staging tensor and all-to-alls
it; the output stays padded at ``n_shards*capacity`` rows per device with a
validity mask (no dynamic-shape compaction on device — consumers apply the
mask). Per-destination capacity is the full block capacity, so no row can
overflow regardless of skew; the cost is an ``n_shards×`` staging buffer,
the standard static-shape trade.

Scope (r5): this kernel is the PRODUCTION exchange for numeric blocks —
``parallel/device_plane.py`` stages eligible batches from
``ShardedRuntime``/``ClusterRuntime`` routing and flushes them through
``exchange_by_key`` at sweep-round boundaries (``PATHWAY_DEVICE_EXCHANGE``
= off/auto/on). Object columns stay on the host plane. Byte-identity with
the host exchange is enforced by ``tests/test_device_plane.py`` (the full
multiworker suite runs with the plane forced) and the multichip dryrun.
Measured on the 8-device virtual CPU mesh the host plane is faster (its
"exchange" is an intra-process pointer move; see BASELINE.md §exchange) —
auto mode therefore keeps a row threshold, and the plane's win condition is
real multi-chip ICI with HBM-resident blocks.
"""

from __future__ import annotations

from functools import lru_cache, partial

import numpy as np

from pathway_tpu.internals.keys import SHARD_MASK


@lru_cache(maxsize=64)
def _jitted_exchange(
    mesh, axis: str, n_cols: int, with_dest: bool = False, fused: bool = False
):
    """One compiled exchange per (mesh, axis, column-count): jit caches on
    function identity, so the per-tick call must reuse one closure or every
    tick would pay a full retrace+compile. ``with_dest`` adds an explicit
    per-row destination input (cluster plane: global shard mapped to a local
    device index on host) instead of deriving it from the key bits.
    ``fused`` appends the post-collective cancellation pass (ISSUE-6): an
    extra (2, n) uint32 row-digest input rides along, and every (key, digest)
    group whose diffs sum to ZERO comes back invalidated — in-flight
    insert↔retract churn never reaches host memory. Groups with a nonzero
    net keep ALL their rows, original diffs, arrival positions (join
    arrangements carry multiplicity as physical rows; see the kernel
    comment). The output is NOT consolidated or key-sorted."""
    import jax
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    kern = _kernel(n, axis, with_dest, fused)
    in_specs = [P(None, axis), P(axis), P(axis), [P(axis)] * n_cols]
    if with_dest:
        in_specs.append(P(axis))
    if fused:
        in_specs.append(P(None, axis))
    from pathway_tpu.observability import device as _dev_prof

    label = "device_exchange.fused_consolidate" if fused else "device_exchange.all_to_all"
    return _dev_prof.traced_jit(
        label,
        jax.jit(
            jax.shard_map(
                kern,
                mesh=mesh,
                in_specs=tuple(in_specs),
                out_specs=(P(None, axis), P(axis), P(axis), [P(axis)] * n_cols),
                check_vma=True,
            )
        ),
    )


def _kernel(n_shards: int, axis: str, with_dest: bool = False, fused: bool = False):
    import jax
    import jax.numpy as jnp

    def local(keys, diffs, valid, cols, *rest):
        ri = 0
        dest = rest[ri] if with_dest else None
        ri += 1 if with_dest else 0
        dig = rest[ri] if fused else None
        # keys arrive as uint32 pairs (hi, lo) — x64 stays off
        cap = keys.shape[1]
        hi, lo = keys[0], keys[1]
        if with_dest:
            shard = dest.astype(jnp.int32)
        else:
            shard = (
                (lo & jnp.uint32(SHARD_MASK & 0xFFFFFFFF)) % jnp.uint32(n_shards)
            ).astype(jnp.int32)
        shard = jnp.where(valid, shard, n_shards)  # invalid rows go nowhere
        # position of each row within its destination bucket
        onehot = (shard[None, :] == jnp.arange(n_shards)[:, None]).astype(jnp.int32)
        pos_in_dest = jnp.cumsum(onehot, axis=1) - 1  # (n, cap)
        pos = jnp.take_along_axis(
            pos_in_dest, jnp.clip(shard, 0, n_shards - 1)[None, :], axis=0
        )[0]

        def stage(arr, fill):
            buf = jnp.full((n_shards, cap) + arr.shape[1:], fill, dtype=arr.dtype)
            # invalid rows carry dest == n_shards: out of bounds, dropped —
            # a dummy in-bounds write would clobber a real row's slot
            return buf.at[shard, pos].set(arr, mode="drop")

        s_hi = stage(hi, jnp.uint32(0))
        s_lo = stage(lo, jnp.uint32(0))
        s_diff = stage(diffs, jnp.int32(0))
        s_valid = stage(valid, False)
        s_cols = [stage(c, jnp.zeros((), c.dtype)) for c in cols]
        if fused:
            s_dhi = stage(dig[0], jnp.uint32(0))
            s_dlo = stage(dig[1], jnp.uint32(0))

        a2a = partial(jax.lax.all_to_all, axis_name=axis, split_axis=0, concat_axis=0)
        r_hi, r_lo = a2a(s_hi), a2a(s_lo)
        r_diff, r_valid = a2a(s_diff), a2a(s_valid)
        r_cols = [a2a(c) for c in s_cols]
        # received: (n_shards, cap) blocks → flat (n_shards*cap) rows + mask
        flat = lambda x: x.reshape((n_shards * cap,) + x.shape[2:])  # noqa: E731
        f_hi, f_lo = flat(r_hi), flat(r_lo)
        f_diff, f_valid = flat(r_diff), flat(r_valid)
        f_cols = [flat(c) for c in r_cols]
        if not fused:
            return jnp.stack([f_hi, f_lo]), f_diff, f_valid, f_cols
        # fused consolidation — same launch, no host round-trip: the rows of
        # one key only ever co-locate HERE (post-collective), so this is the
        # earliest point deltas can net. Group by (key, digest) on a sorted
        # VIEW, segment-sum the diffs, and invalidate every row of a group
        # whose net is ZERO — the in-flight insert↔retract churn this fusion
        # targets cancels before it ever reaches host memory. Groups with a
        # nonzero net keep ALL their rows with their original diffs: stateful
        # consumers (the join arrangement) carry multiplicity as physical
        # rows, so collapsing a +1,+1 group to one diff-2 row would lose a
        # copy of their state. Surviving rows stay in arrival order —
        # byte-for-byte what the plain exchange delivers, minus cancelled
        # pairs.
        f_dhi, f_dlo = flat(a2a(s_dhi)), flat(a2a(s_dlo))
        n_rows = f_hi.shape[0]
        inv = (~f_valid).astype(jnp.uint32)
        order = jnp.lexsort((f_dlo, f_dhi, f_lo, f_hi, inv))
        hi_s, lo_s = f_hi[order], f_lo[order]
        dhi_s, dlo_s = f_dhi[order], f_dlo[order]
        v_s, d_s = f_valid[order], f_diff[order]
        same_prev = jnp.concatenate(
            [
                jnp.zeros((1,), jnp.bool_),
                (hi_s[1:] == hi_s[:-1])
                & (lo_s[1:] == lo_s[:-1])
                & (dhi_s[1:] == dhi_s[:-1])
                & (dlo_s[1:] == dlo_s[:-1])
                & (v_s[1:] == v_s[:-1]),
            ]
        )
        newg = ~same_prev
        seg = jnp.cumsum(newg) - 1
        sums = jax.ops.segment_sum(d_s, seg, num_segments=n_rows)
        keep_s = v_s & (sums[seg] != 0)
        out_valid = jnp.zeros_like(f_valid).at[order].set(keep_s)
        out_diff = jnp.where(out_valid, f_diff, 0)
        return jnp.stack([f_hi, f_lo]), out_diff, out_valid, f_cols

    return local


def exchange_by_key(mesh, axis: str, keys, diffs, cols, valid, dest=None, dig=None):
    """Re-shard padded per-device blocks so every row lands on the device
    owning its key shard (host-plane parity: ``internals/keys.shard_of_keys``,
    re-exported as ``mesh.shard_of_keys``).

    Inputs are GLOBAL arrays sharded along ``axis`` on their first dim:
    ``keys`` uint32 (2, n_dev*cap) as (hi, lo) pairs, ``diffs`` int32,
    ``valid`` bool, ``cols`` list of numeric arrays. Returns the same
    structure with per-device row counts expanded to ``n_shards*cap`` (masked).

    ``dest`` (int32, optional) routes each row to an explicit device index
    instead of its key-shard — the cluster plane uses this to map GLOBAL
    worker shards onto the process-local mesh.

    ``dig`` (uint32 (2, n) row-digest pairs, optional) selects the FUSED
    consolidate+exchange kernel: (key, digest) groups whose diffs net to
    zero are invalidated in the same launch as the collective; surviving
    rows keep their original diffs and arrival positions (cancel-only — the
    output block is byte-identical to the plain exchange minus cancelled
    pairs, not consolidated or re-sorted).
    """
    fused = dig is not None
    fn = _jitted_exchange(
        mesh, axis, len(cols), with_dest=dest is not None, fused=fused
    )
    args = [keys, diffs, valid, cols]
    if dest is not None:
        args.append(dest)
    if fused:
        args.append(dig)
    return fn(*args)


def split_keys_u64(keys: np.ndarray) -> np.ndarray:
    """uint64 host keys → (2, n) uint32 (hi, lo) device representation."""
    k = keys.astype(np.uint64)
    return np.stack(
        [(k >> np.uint64(32)).astype(np.uint32), (k & np.uint64(0xFFFFFFFF)).astype(np.uint32)]
    )


def join_keys_u64(pairs: np.ndarray) -> np.ndarray:
    return (pairs[0].astype(np.uint64) << np.uint64(32)) | pairs[1].astype(np.uint64)
