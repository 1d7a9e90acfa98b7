"""Flag-gated jitted JAX kernels for the relational hot path.

SURVEY §7.1.1 bets that relational ops (map/filter/join/reduce) should become
jitted kernels over column blocks. This module makes that bet testable: it
holds device implementations of the two load-bearing kernels of the block
engine — the grouped segment-sum that powers ``GroupByNode`` and the sorted
probe that powers ``ColumnarMultimap``/``JoinNode`` — behind the
``PATHWAY_ENGINE_JAX`` flag. Integer results (keys, counts, int sums, probe
positions) are bit-identical to the numpy path (same stable ordering, same
dtypes); float sums match to accumulation order only (segment_sum does not
reduce strictly left-to-right the way ``np.add.reduceat`` does), which is one
more reason the groupby kernel stays opt-in while the integer-exact probe is
adopted by default.

Flag values:
  - unset / ``auto`` — adopt what measured faster: the **join probe runs on
    the XLA CPU backend** for large blocks (its multithreaded binary search
    beat numpy searchsorted 1.8-5.9x from 8k-row state up to 10M in
    ``benchmarks/jax_kernel_bench.py``); groupby stays numpy.
  - ``0`` — numpy everywhere.
  - ``1`` — both kernels on the default backend.
  - ``cpu`` / ``tpu`` — both kernels pinned to that backend.

These are HOST kernels: u64 keys under ``enable_x64``, fed from and read back
into numpy every tick. ``auto`` therefore pins them to the process's XLA CPU
device whatever the default backend is (:func:`host_device`), and a process
without a CPU backend (``JAX_PLATFORMS`` naming only an accelerator) fails
loudly instead of landing a 64-bit ``searchsorted`` on the accelerator.

Measured verdict (2026-07-30, CPU host — see
``benchmarks/jax_kernel_bench.py`` and BASELINE.md): the **probe kernel is a
win and is adopted by default**; the **groupby segment-sum is a measured
negative** — numpy argsort+reduceat runs 3.5M rows/s at 10M rows vs 1.9M
(XLA CPU); a device-resident TPU run is not measured on the current machine
(u64 sort is 32-bit-emulated there). The relational plane therefore stays
host-columnar by design, with the MXU path reserved for the FLOP-dense ops
(encoder, KNN, reranker). Reference counterpart: the per-row interpreted
expression VM + differential arrangements (``src/engine/expression.rs``,
``src/engine/dataflow.rs``) have no device analogue at all.
"""

from __future__ import annotations

import os
import threading
import weakref
from functools import partial
from typing import Any

import numpy as np

_MIN_ROWS = 32_768  # below this, dispatch overhead dominates any kernel win


def flag() -> str:
    return os.environ.get("PATHWAY_ENGINE_JAX", "auto").strip().lower() or "auto"


_AVAILABLE: bool | None = None


def available() -> bool:
    global _AVAILABLE
    if _AVAILABLE is None:
        try:
            import jax  # noqa: F401

            _AVAILABLE = True
        except Exception:  # pragma: no cover
            _AVAILABLE = False
    return _AVAILABLE


def enabled() -> bool:
    """Both kernels explicitly on (groupby included)."""
    return flag() not in ("auto", "0", "false") and available()


def host_device():
    """The process's XLA CPU device — where the relational kernels run
    unless a backend was asked for by name."""
    import jax

    try:
        return jax.local_devices(backend="cpu")[0]
    except RuntimeError as e:
        raise RuntimeError(
            "the relational JAX kernels (join probe, fused chains) run on the "
            "XLA CPU backend, which this process does not have "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}): add 'cpu' to "
            "JAX_PLATFORMS, or set PATHWAY_ENGINE_JAX=0 PATHWAY_FUSE_JAX=off"
        ) from e


def _device(force_cpu: bool = False):
    import jax

    f = "cpu" if force_cpu else flag()
    if f == "cpu":
        return host_device()
    if f in ("tpu", "gpu"):
        return jax.local_devices(backend=f)[0]  # asked for by name: absent = error
    return None  # "1": the default backend, as asked


# ------------------------------------------------------------------ groupby


def _donate_active(dev) -> bool:
    """Buffer donation on tick-loop jit entry points (PATHWAY_ARRANGE_DONATE):
    per-tick inputs (probe queries, grouped keys/diffs/columns) are dead after
    the call, so XLA may reuse their device memory for outputs — a realloc+copy
    saved every tick. ``auto`` donates on tpu/gpu only: the CPU backend
    ignores donation and warns."""
    from pathway_tpu.internals.config import get_pathway_config

    mode = get_pathway_config().arrange_donate
    if mode == "off":
        return False
    if mode == "on":
        return True
    import jax

    platform = dev.platform if dev is not None else jax.default_backend()
    return platform in ("tpu", "gpu")


def _jit_grouped(n_cols: int, donate: bool = False):
    import jax
    import jax.numpy as jnp

    def kernel(keys, diffs, cols):
        order = jnp.argsort(keys, stable=True)
        ks = keys[order]
        n = keys.shape[0]
        newg = jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), ks[1:] != ks[:-1]]
        )
        seg = jnp.cumsum(newg) - 1
        d = diffs[order]
        counts = jax.ops.segment_sum(d, seg, num_segments=n)
        sums = tuple(
            jax.ops.segment_sum(c[order] * d, seg, num_segments=n)
            for c in cols
        )
        return order, ks, newg, counts, sums

    jitted = (
        jax.jit(kernel, donate_argnums=(0, 1, 2)) if donate else jax.jit(kernel)
    )
    from pathway_tpu.observability import device as _dev_prof

    suffix = "/donated" if donate else ""
    return _dev_prof.traced_jit(f"engine.grouped/{n_cols}{suffix}", jitted)


_GROUPED_JIT: dict[tuple[int, bool], Any] = {}


def numpy_grouped_sums(
    gkeys: np.ndarray, diffs: np.ndarray, sum_cols: list[np.ndarray]
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, list[np.ndarray]]:
    """The numpy reference for :func:`grouped_sums` — the same
    argsort+reduceat recipe ``GroupByNode._process_columnar`` runs (shared
    here so benchmarks/tests compare against one implementation; the
    pipeline-level parity test in ``tests/test_jax_kernels.py`` guards the
    production path itself)."""
    from pathway_tpu.engine.blocks import group_starts

    order = np.argsort(gkeys, kind="stable")
    ks = gkeys[order]
    starts = group_starts(ks)
    counts = np.add.reduceat(diffs[order], starts) if len(ks) else np.empty(0, np.int64)
    sums = [np.add.reduceat(c[order] * diffs[order], starts) for c in sum_cols]
    return order, starts, ks[starts], counts, sums


def grouped_sums(
    gkeys: np.ndarray, diffs: np.ndarray, sum_cols: list[np.ndarray]
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, list[np.ndarray]]:
    """Device segment-sum groupby over one delta block.

    Returns ``(order, starts, u_gk, counts, partials)`` with the exact values
    (and stable first-occurrence ordering) of the numpy path:
    ``order = argsort(gkeys, stable)``, ``starts`` = sorted group boundaries,
    ``counts[i] = sum(diffs of group i)``, ``partials[c][i] = sum(col_c * diff)``.
    """
    import jax

    dev = _device()
    donate = _donate_active(dev)
    kern = _GROUPED_JIT.get((len(sum_cols), donate))
    if kern is None:
        kern = _GROUPED_JIT[(len(sum_cols), donate)] = _jit_grouped(
            len(sum_cols), donate
        )
    with jax.enable_x64():
        args = (gkeys, diffs, tuple(sum_cols))
        if dev is not None:
            args = jax.device_put(args, dev)
        order, ks, newg, counts, sums = kern(*args)
        order = np.asarray(order)
        newg = np.asarray(newg)
        starts = np.flatnonzero(newg)
        g = len(starts)
        u_gk = np.asarray(ks)[starts]
        counts_np = np.asarray(counts)[:g]
        partials = [np.asarray(s)[:g] for s in sums]
    return order, starts, u_gk, counts_np, partials


def try_grouped(
    gkeys: np.ndarray, diffs: np.ndarray, reducer_specs, data: dict[str, np.ndarray]
):
    """Route a GroupByNode columnar block to the device kernel when eligible.

    Eligible = flag on, block large enough, and every reducer is a
    count/weighted-sum over a numeric column (the semigroup reducers whose
    partials are exactly a segment-sum). Returns
    ``(order, starts, u_gk, counts, partials)`` or None for the numpy path.
    """
    if not enabled() or len(gkeys) < _MIN_ROWS:
        return None
    from pathway_tpu.engine.reducers_impl import CountReducer, SumReducer

    cols: list[np.ndarray] = []
    kinds: list[tuple[str, str | None]] = []
    for (_, impl, colnames) in reducer_specs:
        if isinstance(impl, CountReducer):
            kinds.append(("count", None))
        elif isinstance(impl, SumReducer):
            col = data[colnames[0]]
            if col.dtype.kind not in "iufb":
                return None
            # match numpy promotion of col * int64-diffs exactly
            cols.append(col.astype(np.result_type(col.dtype, np.int64), copy=False))
            kinds.append(("sum", impl.kind))
        else:
            return None
    order, starts, u_gk, counts, sums = grouped_sums(gkeys, diffs, cols)
    partials: list[np.ndarray] = []
    si = 0
    for kind, sumkind in kinds:
        if kind == "count":
            partials.append(counts)
        else:
            p = sums[si]
            si += 1
            if sumkind == "float" and p.dtype.kind != "f":
                p = p.astype(np.float64)
            partials.append(p)
    return order, starts, u_gk, counts, partials


# ------------------------------------------------------------------ join probe


def _jit_probe(donate: bool = False):
    import jax
    import jax.numpy as jnp

    def kernel(sorted_keys, q):
        lo = jnp.searchsorted(sorted_keys, q, side="left")
        hi = jnp.searchsorted(sorted_keys, q, side="right")
        return lo, hi - lo

    # the query block is dead after the call (padded fresh per tick) — donate
    # it on accelerator backends; the STATE side is never donated, it is the
    # persistent arrangement re-probed across ticks
    jitted = jax.jit(kernel, donate_argnums=(1,)) if donate else jax.jit(kernel)
    from pathway_tpu.observability import device as _dev_prof

    suffix = "/donated" if donate else ""
    return _dev_prof.traced_jit(f"engine.join_probe{suffix}", jitted)


_PROBE_JIT: dict[bool, Any] = {}


_PAD_KEY = np.uint64(0xFFFFFFFFFFFFFFFF)


def _bucket(n: int) -> int:
    b = 1024
    while b < n:
        b <<= 1
    return b


# Sorted state segments are immutable between compactions and probed many
# times; cache their padded copies so the pad memcpy is paid once, not per
# probe. Keyed by id() with a liveness weakref guard (ids recycle after GC).
# Locked: sharded-runtime worker threads probe concurrently.
_PAD_CACHE: dict[int, tuple[Any, np.ndarray]] = {}
_PAD_LOCK = threading.Lock()


def _padded_state(arr: np.ndarray, bs: int) -> np.ndarray:
    with _PAD_LOCK:
        ent = _PAD_CACHE.get(id(arr))
        if ent is not None and ent[0]() is arr and len(ent[1]) == bs:
            return ent[1]
    padded = np.concatenate([arr, np.full(bs - len(arr), _PAD_KEY, dtype=np.uint64)])
    with _PAD_LOCK:
        dead = [k for k, (r, _) in _PAD_CACHE.items() if r() is None]
        for k in dead:
            del _PAD_CACHE[k]
        try:
            _PAD_CACHE[id(arr)] = (weakref.ref(arr), padded)
        except TypeError:  # pragma: no cover - non-weakref-able array subclass
            pass
    return padded


# Persistent device-resident arrangements (PATHWAY_ARRANGE_CACHE): a sorted
# state segment is immutable between compactions, so its device copy is
# uploaded once per compaction generation and every later tick probes the
# SAME device buffer — the arrangement lives on device across ticks instead
# of riding PCIe every call. Keyed by id() of the (host) padded array with a
# liveness weakref (ids recycle after GC); one entry per (array, device).
_DEV_CACHE: dict[tuple[int, str], tuple[Any, Any]] = {}
_DEV_LOCK = threading.Lock()


def _device_state(arr: np.ndarray, dev) -> Any:
    from pathway_tpu.internals.config import get_pathway_config

    if not get_pathway_config().arrange_device_cache:
        import jax

        return jax.device_put(arr, dev) if dev is not None else arr
    import jax

    key = (id(arr), str(dev))
    with _DEV_LOCK:
        ent = _DEV_CACHE.get(key)
        if ent is not None and ent[0]() is arr:
            return ent[1]
    put = jax.device_put(arr, dev) if dev is not None else jax.device_put(arr)
    with _DEV_LOCK:
        dead = [k for k, (r, _) in _DEV_CACHE.items() if r() is None]
        for k in dead:
            del _DEV_CACHE[k]
        try:
            _DEV_CACHE[key] = (weakref.ref(arr), put)
        except TypeError:  # pragma: no cover - non-weakref-able array subclass
            pass
    return put


def join_probe(sorted_jk: np.ndarray, q_jk: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Masked sorted-array probe (the hash-join inner kernel): for each probe
    key, the ``(lo, count)`` range of matches in the sorted state array —
    identical to the numpy two-sided searchsorted.

    Streaming joins present a fresh ``(state_len, query_len)`` pair almost
    every tick, so both sides are padded to power-of-two buckets (state with
    the max key, which sorts after every real key and leaves lo/count of
    smaller probes untouched) to bound XLA recompiles at O(log² n) shapes.
    Probes equal to the pad key are corrected on the host (rare: one hash
    value in 2^64).
    """
    import jax

    n_state, n_q = len(sorted_jk), len(q_jk)
    bs, bq = _bucket(n_state), _bucket(n_q)
    if bs != n_state:
        sorted_jk = _padded_state(sorted_jk, bs)
    if bq != n_q:
        q_jk_padded = np.concatenate(
            [q_jk, np.zeros(bq - n_q, dtype=np.uint64)]
        )
    else:
        q_jk_padded = q_jk
    # auto mode adopts the probe on the CPU backend (the measured win);
    # explicit backends are honored as given
    dev = _device(force_cpu=flag() == "auto")
    donate = _donate_active(dev)
    kern = _PROBE_JIT.get(donate)
    if kern is None:
        kern = _PROBE_JIT[donate] = _jit_probe(donate)
    with jax.enable_x64():
        state_arg = _device_state(sorted_jk, dev)
        q_arg = q_jk_padded
        if dev is not None:
            q_arg = jax.device_put(q_arg, dev)
        elif donate:
            # donation only reaches XLA for device-committed args; the numpy
            # fast path would silently copy anyway
            q_arg = jax.device_put(q_arg)
        lo, cnt = kern(state_arg, q_arg)
        # np.array (not asarray): JAX outputs are read-only; the pad
        # correction below mutates
        lo = np.array(lo[:n_q])
        cnt = np.array(cnt[:n_q])
    if bs != n_state:
        hit_pad = q_jk == _PAD_KEY
        if hit_pad.any():
            idx = np.flatnonzero(hit_pad)
            real = sorted_jk[:n_state]
            lo[idx] = np.searchsorted(real, q_jk[idx], side="left")
            cnt[idx] = np.searchsorted(real, q_jk[idx], side="right") - lo[idx]
        lo = np.minimum(lo, n_state)
    return lo, cnt


#: auto-adoption thresholds. Isolated steady-shape microbenchmarks show wins
#: from 8k-row state, but in-engine the per-call dispatch overhead and the
#: per-shape-bucket XLA compiles only amortize on big blocks (measured:
#: static 1M-row load 895k→1051k rows/s, while 20k-row incremental ticks
#: regressed 488k→255k when routed) — so auto only routes big probes.
_PROBE_STATE, _PROBE_QUERY = 131072, 32768


def probe_eligible(n_state: int, n_query: int) -> bool:
    f = flag()
    if f in ("0", "false") or not available():
        return False
    if f == "auto":
        return n_state >= _PROBE_STATE and n_query >= _PROBE_QUERY
    return n_state >= _MIN_ROWS and n_query >= 1024
