"""Brute-force KNN index living in device HBM.

TPU-native redesign of the reference's ``BruteForceKNNIndex``
(``src/external_integration/brute_force_knn_integration.rs:22-236``): there, a dense
``Array2<f64>`` on CPU with swap-remove and chunked ``index.dot(queries)`` +
``k_smallest``. Here the matrix is a padded, capacity-doubling ``[N, d]`` array that
stays resident on device; add/remove are ``dynamic_update_slice`` on a slot free-list;
search is one jitted einsum riding the MXU plus ``jax.lax.top_k``, with masked
(invalid / deleted) slots scored ``-inf``.

Sharding: ``ShardedBruteForceKnnIndex`` splits slots across a 1-D mesh axis; a search
is ``shard_map``-ped — each device scores its local shard and emits its local top-k,
then a single all-gather of ``k`` candidates per device feeds a final top-k merge.
That keeps the ``[N, d]`` matrix partitioned in HBM across chips and moves only
``n_devices * k`` score/arg pairs over ICI (SURVEY §5.7: "sharded brute-force KNN —
an all-gathered or ring-scheduled einsum over an HBM-resident embedding matrix").

Determinism (SURVEY §7.3): scores accumulate in f32 and ties break by smaller slot id
(lax.top_k is stable over the packed score-major composite), so repeated runs give
byte-identical neighbour lists.
"""

from __future__ import annotations

import enum
import math
from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pathway_tpu import observability as _obs
from pathway_tpu.observability import device as _dev_prof


class KnnMetric(enum.Enum):
    L2SQ = "l2sq"
    COS = "cos"
    DOT = "dot"


_MIN_CAPACITY = 128


def _pad_to_capacity(n: int) -> int:
    return max(_MIN_CAPACITY, 1 << math.ceil(math.log2(max(n, 1))))


def _key_bits_one(k: Any) -> int:
    """Top 32 bits of the key's canonical tie order (``keys.tie_order`` =
    hash order) — a true order prefix for every key type, including small
    ints whose raw top bits would all be zero."""
    from pathway_tpu.internals.keys import tie_order

    return tie_order(k) >> 32


def _key_bits_of(keys: Sequence[Any]) -> np.ndarray:
    arr = np.asarray(keys)
    if arr.dtype.kind in ("i", "u", "b"):
        # vectorized: tie_order_u64 is bit-identical to tie_order on ints
        from pathway_tpu.internals.keys import tie_order_u64

        return (tie_order_u64(arr) >> np.uint64(32)).astype(np.uint32)
    return np.fromiter((_key_bits_one(k) for k in keys), dtype=np.uint32, count=len(keys))


def _search_body(
    vectors: jax.Array,      # [N, d]
    norms_sq: jax.Array,     # [N] f32 (precomputed row |v|^2)
    valid: jax.Array,        # [N] bool
    key_bits: jax.Array,     # [N] uint32 (top 32 bits of each slot's key)
    queries: jax.Array,      # [Q, d] f32
    k: int,
    metric: str,
) -> tuple[jax.Array, jax.Array]:
    """Shared scoring+selection body of ``_search_kernel`` and the tiered
    index's candidate-rescore kernel — ONE formula for dot/norm/score, so a
    row scores the same bits whichever launch touches it."""
    dots = jnp.einsum(
        "qd,nd->qn", queries, vectors, preferred_element_type=jnp.float32
    )
    if metric == KnnMetric.L2SQ.value:
        qn = jnp.sum(queries * queries, axis=-1, keepdims=True)
        # negative L2^2 so that "higher is better" uniformly
        scores = -(qn + norms_sq[None, :] - 2.0 * dots)
    elif metric == KnnMetric.COS.value:
        qn = jnp.sqrt(jnp.sum(queries * queries, axis=-1, keepdims=True))
        denom = jnp.maximum(qn * jnp.sqrt(norms_sq)[None, :], 1e-30)
        scores = dots / denom
    else:
        scores = dots
    scores = jnp.where(valid[None, :], scores, -jnp.inf)
    if k == 0:  # static: resolved at trace time
        q = queries.shape[0]
        return (
            jnp.zeros((q, 0), dtype=scores.dtype),
            jnp.zeros((q, 0), dtype=jnp.int32),
        )
    return _canonical_select(scores, key_bits, k)


@partial(_dev_prof.traced_jit, "knn.search")
@partial(jax.jit, static_argnames=("k", "metric"))
def _search_kernel(
    vectors: jax.Array,      # [N, d] f32
    norms_sq: jax.Array,     # [N] f32 (precomputed row |v|^2)
    valid: jax.Array,        # [N] bool
    key_bits: jax.Array,     # [N] uint32 (top 32 bits of each slot's key)
    queries: jax.Array,      # [Q, d] f32
    k: int,
    metric: str,
) -> tuple[jax.Array, jax.Array]:
    """Return (scores [Q,k], slot_ids [Q,k]); invalid slots get -inf score.

    Ties break CANONICALLY by smaller key (via ``key_bits``), not by slot
    order — so a sharded index cuts each shard's local top-k with exactly the
    rule the cross-shard merge uses, and worker count cannot change which of
    several equal-score documents survive the cut.

    Precision caveat: the composite keeps the top 30 bits of the top-32 key
    bits (x64 is off, so the composite must fit int32). Equal-score candidates
    whose keys collide in those 30 bits fall back to ``lax.top_k`` slot order —
    the worker-count byte-identity guarantee is therefore probabilistic,
    ~2^-30 per tied pair (keys are hashes, so bit collisions are uniform)."""
    return _search_body(vectors, norms_sq, valid, key_bits, queries, k, metric)


@partial(_dev_prof.traced_jit, "knn.rescore")
@partial(jax.jit, static_argnames=("k", "metric"))
def _rescore_kernel(
    rows: jax.Array,       # [C, d] f32 candidate matrix (padded)
    valid: jax.Array,      # [C] bool (padding rows False)
    key_bits: jax.Array,   # [C] uint32
    queries: jax.Array,    # [Q, d] f32
    k: int,
    metric: str,
) -> tuple[jax.Array, jax.Array]:
    """Exact top-k over an ad-hoc candidate matrix. Row norms are computed on
    device with the same expression ``_scatter_block`` uses at ingest, and
    scoring/selection is ``_search_body`` — so a candidate's score here is the
    same bits the resident-index search produces for it."""
    rows32 = rows.astype(jnp.float32)
    norms_sq = jnp.sum(rows32 * rows32, axis=-1)
    return _search_body(rows, norms_sq, valid, key_bits, queries, k, metric)


def exact_rescore(
    rows: np.ndarray,          # [m, d] f32 candidate vectors (raw, un-normalized)
    keys: Sequence[Any],       # len m candidate keys
    queries: np.ndarray | jax.Array,  # [Q, d]
    k: int,
    metric: str = "cos",
) -> list[list[tuple[Any, float]]]:
    """Per-query exact top-k over an explicit candidate set, via the device
    kernel (einsum + canonical select). The candidate count pads to a
    power-of-two capacity (padded slots invalid) so the compile cache stays a
    small closed set under varying candidate volumes. Used by the tiered
    index to score cold-tier candidates with the same math as the HBM shard."""
    m = len(keys)
    if m == 0:
        q = np.atleast_2d(np.asarray(queries))
        return [[] for _ in range(q.shape[0])]
    cap = _pad_to_capacity(m)
    mat = np.zeros((cap, rows.shape[1]), dtype=np.float32)
    mat[:m] = rows
    valid = np.zeros(cap, dtype=bool)
    valid[:m] = True
    bits = np.zeros(cap, dtype=np.uint32)
    bits[:m] = _key_bits_of(list(keys))
    q = queries
    if isinstance(q, jax.Array):
        # same coercion as the np path: the bit-identical-score guarantee
        # holds only for f32 [Q, d] operands
        if q.ndim == 1:
            q = q[None, :]
        q = q.astype(jnp.float32)
    else:
        q = jnp.asarray(np.atleast_2d(np.asarray(q, np.float32)))
    scores, ids = _rescore_kernel(
        jnp.asarray(mat), jnp.asarray(valid), jnp.asarray(bits), q,
        k=min(k, cap), metric=metric,
    )
    scores_np = np.asarray(scores)
    ids_np = np.asarray(ids)
    slot_to_key = {i: key for i, key in enumerate(keys)}
    return _decode_hits(scores_np, ids_np, slot_to_key, k)


def _topk_rows(x: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Exact per-row top-k, chunked for wide rows: ``lax.top_k`` over the full
    [Q, N] row costs ~30 ms at N=1M on v5e while per-chunk top-k + a top-k
    over the nc*k candidates costs ~4 ms (measured at a small k; exact because
    every global top-k element is in its chunk's top-k). The second stage is
    N*k/256 wide, so the saving shrinks as k grows — at k = 64 it selects over
    a quarter of the row — and callers ask for no more than they need. Falls
    back to plain top_k for narrow rows, for k past the chunk, or when N
    doesn't split evenly (capacities are powers of two, so the chunked path
    is the norm)."""
    n = x.shape[-1]
    chunk = 256
    if n < 8192 or n % chunk or k > chunk:
        return jax.lax.top_k(x, k)
    q = x.shape[0]
    nc = n // chunk
    cs, ci = jax.lax.top_k(x.reshape(q, nc, chunk), k)
    base = (jnp.arange(nc, dtype=jnp.int32) * chunk)[None, :, None]
    ms, mi = jax.lax.top_k(cs.reshape(q, nc * k), k)
    return ms, jnp.take_along_axis((ci + base).reshape(q, nc * k), mi, axis=1)


def _canonical_select(
    scores: jax.Array,    # [Q, C] f32, -inf = invalid
    key_bits: jax.Array,  # [C] or [Q, C] uint32
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """Exact top-k under the canonical (score desc, key asc) order.

    Two passes, int32-safe (x64 stays off): pass 1 finds the k-th score per
    query; pass 2 takes everything strictly above it plus the smallest-key
    boundary ties — |above| < k always, so one top_k over the composite
    selects exactly the canonical set. Used by both the single-device kernel
    and the cross-shard candidate merge, so shard count cannot change which
    equal-score candidates survive."""
    top_scores0, _ = _topk_rows(scores, k)
    thr = top_scores0[:, -1:]
    above = scores > thr
    eq = (scores == thr) & jnp.isfinite(scores)
    inv_key30 = (jnp.uint32(0x3FFFFFFF) - (key_bits >> 2)).astype(jnp.int32)
    if inv_key30.ndim == 1:
        inv_key30 = jnp.broadcast_to(inv_key30[None, :], scores.shape)
    comp = jnp.where(
        above,
        jnp.int32(0x7FFFFFFF),
        jnp.where(eq, inv_key30, jnp.int32(-1)),
    )
    _c, top_ids = _topk_rows(comp, k)
    top_scores = jnp.take_along_axis(scores, top_ids, axis=1)
    return top_scores, top_ids


def _key_order(key: Any):
    from pathway_tpu.internals.keys import tie_order

    return tie_order(key)


def _decode_hits(
    scores_np: np.ndarray, ids_np: np.ndarray, slot_to_key: dict, k: int
) -> list[list[tuple[Any, float]]]:
    """Turn [Q, kk] device results into per-query (key, score) lists ordered
    canonically (score desc, key asc), dropping -inf (invalid-slot) entries and
    slots freed since the last flush."""
    out: list[list[tuple[Any, float]]] = []
    for qi in range(ids_np.shape[0]):
        hits: list[tuple[Any, float]] = []
        for j in range(ids_np.shape[1]):
            if not np.isfinite(scores_np[qi, j]):
                continue
            key = slot_to_key.get(int(ids_np[qi, j]))
            if key is not None:
                hits.append((key, float(scores_np[qi, j])))
        hits.sort(key=lambda kv: (-kv[1], _key_order(kv[0])))
        out.append(hits[:k])
    return out


@partial(_dev_prof.traced_jit, "knn.scatter")
@partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _scatter_block(
    vectors: jax.Array,   # [N, d]
    norms_sq: jax.Array,  # [N] f32
    valid: jax.Array,     # [N] bool
    key_bits: jax.Array,  # [N] uint32
    slots_bits: jax.Array,  # [2, m] int32: row 0 = slots, row 1 = key bits
    rows: jax.Array,      # [m, d]
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One fused ingest scatter: vectors, norms (computed on device), validity,
    and tie-break bits in a single dispatch. ``slots_bits`` packs the two host
    int arrays into ONE host→device transfer instead of two."""
    slots = slots_bits[0]
    bits = jax.lax.bitcast_convert_type(slots_bits[1], jnp.uint32)
    rows32 = rows.astype(jnp.float32)
    vectors = vectors.at[slots].set(rows.astype(vectors.dtype))
    norms_sq = norms_sq.at[slots].set(jnp.sum(rows32 * rows32, axis=-1))
    valid = valid.at[slots].set(True)
    key_bits = key_bits.at[slots].set(bits)
    return vectors, norms_sq, valid, key_bits


@partial(_dev_prof.traced_jit, "knn.pack_hits")
@jax.jit
def _pack_hits(scores: jax.Array, slot_ids: jax.Array) -> jax.Array:
    """Pack (scores [Q,k] f32, ids [Q,k] i32) into one [Q, 2k] f32 array so
    results cross the host boundary in a SINGLE fetch (one device sync per
    query, not two). Ids are value-cast (exact for ids < 2^24), NOT bitcast:
    small ints bitcast to f32 are denormals, which the TPU flushes to zero."""
    return jnp.concatenate([scores, slot_ids.astype(jnp.float32)], axis=1)


def _unpack_hits(packed: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    k = packed.shape[1] // 2
    return packed[:, :k], packed[:, k:].astype(np.int64)


@partial(_dev_prof.traced_jit, "knn.invalidate")
@jax.jit
def _invalidate(valid: jax.Array, slots: jax.Array) -> jax.Array:
    return valid.at[slots].set(False)


class BruteForceKnnIndex:
    """Single-device HBM-resident brute-force KNN with add/remove/search.

    External-index contract of the reference (``external_integration/mod.rs:40``):
    ``add(key, vector)``, ``remove(key)``, ``search(queries, k)`` — updated by the
    data stream's additions/retractions, queried as-of-now.
    """

    def __init__(
        self,
        dimension: int,
        metric: KnnMetric | str = KnnMetric.COS,
        capacity: int = _MIN_CAPACITY,
        dtype: Any = jnp.float32,
        component: str = "knn_index",
    ):
        self.dimension = dimension
        self.metric = KnnMetric(metric) if not isinstance(metric, KnnMetric) else metric
        self.dtype = dtype
        self._mem_component = component
        capacity = _pad_to_capacity(capacity)
        self._vectors = jnp.zeros((capacity, dimension), dtype=dtype)
        self._norms_sq = jnp.zeros((capacity,), dtype=jnp.float32)
        self._valid = jnp.zeros((capacity,), dtype=bool)
        # canonical tie-break bits per slot (top 32 bits of the key)
        self._key_bits = jnp.zeros((capacity,), dtype=jnp.uint32)
        # host-side bookkeeping (not in the hot path)
        self._key_to_slot: dict[Any, int] = {}
        self._slot_to_key: dict[int, Any] = {}
        self._free: list[int] = list(range(capacity - 1, -1, -1))
        # staged updates, flushed as one batched scatter before the next search
        self._pending_slots: list[int] = []
        self._pending_rows: list[np.ndarray] = []
        self._pending_bits: list[int] = []
        self._pending_invalidate: list[int] = []
        # device-resident staged blocks: (host slots i32, [m, d] device rows,
        # host key-bits u32) — slots+bits stay host-side so _apply_scatter can
        # pack them into ONE host→device transfer
        self._pending_device: list[tuple[np.ndarray, Any, np.ndarray]] = []
        # memory attribution: index shards appear as
        # pathway_device_bytes{component="knn_index"} while this instance
        # lives (tiered indexes relabel their hot shard "knn_hot")
        _dev_prof.register_memory(self, component, lambda ix: ix.device_bytes())

    def device_bytes(self) -> int:
        """Live device bytes of the index arrays (vectors + norms + validity +
        tie-break bits)."""
        return int(
            self._vectors.nbytes
            + self._norms_sq.nbytes
            + self._valid.nbytes
            + self._key_bits.nbytes
        )

    def __getstate__(self):
        """Snapshot form: device arrays DMA'd to host (operator persistence
        writes this at snapshot ticks; reference ``operator_snapshot.rs``)."""
        self._flush()
        d = dict(self.__dict__)
        d["_vectors"] = np.asarray(self._vectors)
        d["_norms_sq"] = np.asarray(self._norms_sq)
        d["_valid"] = np.asarray(self._valid)
        d["_key_bits"] = np.asarray(self._key_bits)
        return d

    def __setstate__(self, d):
        self.__dict__.update(d)
        self._vectors = jnp.asarray(d["_vectors"])
        self._norms_sq = jnp.asarray(d["_norms_sq"])
        self._valid = jnp.asarray(d["_valid"])
        # recompute tie-break bits from the keys instead of trusting the
        # snapshot: a snapshot written under an older tie-order scheme (or a
        # different PATHWAY_HASH_SALT) would otherwise leave device bits that
        # disagree with the host-side canonical order
        bits = np.zeros(len(d["_key_bits"]), dtype=np.uint32)
        if self._slot_to_key:
            slots = np.fromiter(self._slot_to_key, dtype=np.int64, count=len(self._slot_to_key))
            bits[slots] = _key_bits_of(list(self._slot_to_key.values()))
        self._key_bits = jnp.asarray(bits)
        # a restored index re-attributes its HBM bytes (weak registration does
        # not survive pickling)
        _dev_prof.register_memory(
            self,
            self.__dict__.get("_mem_component", "knn_index"),
            lambda ix: ix.device_bytes(),
        )

    # -- capacity ------------------------------------------------------------
    @property
    def capacity(self) -> int:
        return self._vectors.shape[0]

    def __len__(self) -> int:
        return len(self._key_to_slot)

    def _grow(self) -> None:
        old = self.capacity
        new = old * 2
        self._vectors = jnp.concatenate(
            [self._vectors, jnp.zeros((old, self.dimension), dtype=self.dtype)]
        )
        self._norms_sq = jnp.concatenate([self._norms_sq, jnp.zeros((old,), jnp.float32)])
        self._valid = jnp.concatenate([self._valid, jnp.zeros((old,), bool)])
        self._key_bits = jnp.concatenate([self._key_bits, jnp.zeros((old,), jnp.uint32)])
        self._free.extend(range(new - 1, old - 1, -1))

    # -- mutation ------------------------------------------------------------
    def _stage_host(self, key: Any, vec: np.ndarray) -> None:
        if key in self._key_to_slot:
            slot = self._key_to_slot[key]  # upsert in place
        else:
            if not self._free:
                self._flush()
                self._grow()
            slot = self._free.pop()
            self._key_to_slot[key] = slot
            self._slot_to_key[slot] = key
        self._pending_slots.append(slot)
        self._pending_rows.append(vec)
        self._pending_bits.append(_key_bits_one(key))

    def add(self, key: Any, vector: np.ndarray | Sequence[float]) -> None:
        vec = np.asarray(vector, dtype=np.float32)
        if vec.shape != (self.dimension,):
            raise ValueError(
                f"vector shape {vec.shape} != ({self.dimension},) for key {key!r}"
            )
        self._stage(key, vec)

    def add_batch(self, keys: Sequence[Any], vectors: np.ndarray) -> None:
        """Bulk add/upsert: one host loop over bookkeeping, vectors staged as rows
        of a single [m, d] array (skips per-row asarray/validate overhead)."""
        vecs = np.asarray(vectors, dtype=np.float32)
        if vecs.shape != (len(keys), self.dimension):
            raise ValueError(
                f"vectors shape {vecs.shape} != ({len(keys)}, {self.dimension})"
            )
        for key, vec in zip(keys, vecs):
            self._stage(key, vec)

    def add_batch_device(self, keys: Sequence[Any], vectors: "jax.Array") -> None:
        """Bulk add of embeddings that already live in HBM (e.g. straight from
        the encoder): slots are assigned host-side, the data never leaves the
        device — the whole ingest loop stays async with zero per-batch
        device→host syncs."""
        m = len(keys)
        if vectors.shape != (m, self.dimension):
            raise ValueError(
                f"vectors shape {vectors.shape} != ({m}, {self.dimension})"
            )
        if self._pending_slots:
            # host rows staged earlier must land first (staging order decides
            # the upsert winner); apply them before queuing this device block
            self._flush_host()
        slots = np.empty(m, dtype=np.int32)
        for i, key in enumerate(keys):
            slot = self._key_to_slot.get(key)
            if slot is None:
                if not self._free:
                    self._grow()
                slot = self._free.pop()
                self._key_to_slot[key] = slot
                self._slot_to_key[slot] = key
            slots[i] = slot
        bits = _key_bits_of(list(keys))
        if len(np.unique(slots)) != len(slots):
            # duplicate keys in one call: scatter winners are undefined, keep
            # the last staging per slot (device-side gather)
            last = {int(s): i for i, s in enumerate(slots)}
            keep = sorted(last.values())
            vectors = vectors[jnp.asarray(keep)]
            slots = slots[keep]
            bits = bits[keep]
        self._pending_device.append((slots, vectors, bits))

    def remove(self, key: Any) -> None:
        slot = self._key_to_slot.pop(key, None)
        if slot is None:
            raise KeyError(f"KNN index: remove of unknown key {key!r}")
        del self._slot_to_key[slot]
        self._free.append(slot)
        self._pending_invalidate.append(slot)

    def _stage(self, key: Any, vec: np.ndarray) -> None:
        if self._pending_device:
            # keep global application order == staging order
            self._flush_device()
        self._stage_host(key, vec)

    def _flush(self) -> None:
        if not (self._pending_slots or self._pending_device or self._pending_invalidate):
            return
        tok = _obs.begin("index/scatter")
        rows = len(self._pending_slots) + sum(len(p[0]) for p in self._pending_device)
        self._flush_host()
        self._flush_device()
        if self._pending_invalidate:
            # a slot may have been re-added after removal; only invalidate slots
            # that are currently free
            free = set(self._free)
            dead = [s for s in self._pending_invalidate if s in free]
            if dead:
                self._valid = _invalidate(
                    self._valid, jnp.asarray(dead, dtype=jnp.int32)
                )
            self._pending_invalidate = []
        if tok is not None:
            _obs.end(tok, {"pathway.rows": rows})

    def _flush_host(self) -> None:
        if self._pending_slots:
            # the same slot can be staged twice (upsert within one flush window);
            # jnp scatter with duplicate indices has an undefined winner, so keep
            # only the last staging per slot before dispatch
            slot_arr = np.asarray(self._pending_slots, dtype=np.int32)
            if len(np.unique(slot_arr)) != len(slot_arr):
                last = {int(s): i for i, s in enumerate(slot_arr)}
                keep = sorted(last.values())
                slot_arr = slot_arr[keep]
                self._pending_rows = [self._pending_rows[i] for i in keep]
                self._pending_bits = [self._pending_bits[i] for i in keep]
            stacked = np.stack(self._pending_rows).astype(np.float32)
            # pad to a power-of-two bucket so jit sees a small closed set of
            # scatter shapes (sharded runs hands each worker a different shard
            # size — unpadded, every size would compile its own kernel);
            # padding repeats the last (slot, row) pair: duplicate writes of an
            # identical value are harmless
            from pathway_tpu.ops.microbatch import LENGTH_MAX_BUCKET, bucket_size

            # bits were captured at staging time: a key may have been removed
            # since (its slot gets invalidated separately)
            bits = np.asarray(self._pending_bits, dtype=np.uint32)
            m = len(slot_arr)
            bucket = bucket_size(m, min_bucket=32, max_bucket=LENGTH_MAX_BUCKET)
            if bucket > m:
                pad = bucket - m
                slot_arr = np.concatenate([slot_arr, np.repeat(slot_arr[-1:], pad)])
                stacked = np.concatenate([stacked, np.repeat(stacked[-1:], pad, axis=0)])
                bits = np.concatenate([bits, np.repeat(bits[-1:], pad)])
            # ship f32 rows: _scatter_block computes norms from full precision
            # BEFORE casting to the index dtype, so host- and device-ingested
            # rows score identically on non-f32 indexes
            self._apply_scatter(slot_arr, bits, _dev_prof.put(stacked, "knn.rows"))
            self._pending_slots, self._pending_rows, self._pending_bits = [], [], []

    def _apply_scatter(self, slots_np: np.ndarray, bits_np: np.ndarray, rows) -> None:
        """Land one block: slots+bits cross as a single packed put, and the
        whole (vectors, norms, valid, bits) update is one fused dispatch with
        donated buffers (no HBM copy of the index matrix)."""
        slots_bits = _dev_prof.put(
            np.stack([slots_np.astype(np.int32), bits_np.view(np.int32)]), "knn.slots"
        )
        self._vectors, self._norms_sq, self._valid, self._key_bits = _scatter_block(
            self._vectors, self._norms_sq, self._valid, self._key_bits,
            slots_bits, rows,
        )

    def _flush_device(self) -> None:
        if self._pending_device:
            for slots, dev, bits in self._pending_device:
                self._apply_scatter(slots, bits, dev)
            self._pending_device = []

    # -- search --------------------------------------------------------------
    def _prep_queries(self, queries: np.ndarray | jax.Array) -> jax.Array:
        if isinstance(queries, jax.Array):
            q = queries.astype(self.dtype)
            if q.ndim == 1:
                q = q[None, :]
        else:
            q = _dev_prof.put(
                np.atleast_2d(np.asarray(queries, np.float32)), "knn.queries", self.dtype
            )
        if q.shape[-1] != self.dimension:
            raise ValueError(f"query dim {q.shape[-1]} != {self.dimension}")
        return q

    def search_device(
        self, queries: np.ndarray | jax.Array, k: int
    ) -> tuple[jax.Array, jax.Array]:
        """Raw device-resident result: (scores [Q,k], slot ids [Q,k]) with NO
        host sync — chain into further device ops or pack for one fetch."""
        self._flush()
        q = self._prep_queries(queries)
        stats = _dev_prof.stats()
        if stats.enabled:
            # the padded-vs-valid gap of the scan is exactly the pad waste
            stats.note_pad_rows("knn.search", len(self), self.capacity - len(self))
        return _search_kernel(
            self._vectors, self._norms_sq, self._valid, self._key_bits, q,
            k=min(k, self.capacity), metric=self.metric.value,
        )

    def search(
        self, queries: np.ndarray, k: int
    ) -> list[list[tuple[Any, float]]]:
        """Top-k per query as (key, score) lists, best first. Scores follow the
        metric's 'higher is better' convention (L2SQ is negated squared dist).
        Accepts a device array directly (e.g. from ``encode_texts_device``) so
        an encode→search chain costs one host round-trip, not two; scores and
        ids come back packed in a single device→host fetch."""
        scores, slot_ids = self.search_device(queries, k)
        scores_np, ids_np = self._fetch_hits(scores, slot_ids)
        return _decode_hits(scores_np, ids_np, self._slot_to_key, k)

    def _fetch_hits(
        self, scores: jax.Array, slot_ids: jax.Array
    ) -> tuple[np.ndarray, np.ndarray]:
        """One packed device→host fetch when the f32 value-cast of ids stays
        exact (capacity < 2^24); two plain fetches otherwise."""
        if self.capacity < (1 << 24):
            return _unpack_hits(_dev_prof.fetch(_pack_hits(scores, slot_ids), "knn.hits"))
        return _dev_prof.fetch(scores, "knn.scores"), _dev_prof.fetch(slot_ids, "knn.ids")


def sharded_search(
    mesh: Mesh,
    axis: str,
    vectors: jax.Array,    # [N, d] sharded on axis over N
    norms_sq: jax.Array,   # [N]
    valid: jax.Array,      # [N]
    key_bits: jax.Array,   # [N] uint32, sharded on axis
    queries: jax.Array,    # [Q, d] replicated
    k: int,
    metric: str = "cos",
) -> tuple[jax.Array, jax.Array]:
    """Search a mesh-sharded KNN matrix: local einsum+top_k per device, all-gather
    of k candidates, global top-k merge. Returns (scores [Q,k], global slot ids).

    Each shard's local cut uses the same canonical (score desc, key asc)
    tie-break as the single-device kernel, so which equal-score documents
    survive does not depend on the shard count (matches ``_decode_hits`` and
    the cross-shard merge ordering).
    """
    n_shards = mesh.shape[axis]
    shard_n = vectors.shape[0] // n_shards
    k_local = min(k, shard_n)
    # n_shards * k_local candidates always cover the true global top min(k, N):
    # either k_local == k (each shard alone could supply all k) or the candidate
    # set is the entire index
    k_final = min(k, n_shards * k_local)

    def local(vecs, nsq, val, bits, q):
        s, ids = _search_kernel(vecs, nsq, val, bits, q, k=k_local, metric=metric)
        shard_idx = jax.lax.axis_index(axis)
        gids = ids + shard_idx * shard_n
        bsel = bits[ids]  # per-candidate key bits ride along for the merge
        # gather all shards' candidates: [n_shards*k_local] per query
        all_s = jax.lax.all_gather(s, axis, axis=1, tiled=True)
        all_g = jax.lax.all_gather(gids, axis, axis=1, tiled=True)
        all_b = jax.lax.all_gather(bsel, axis, axis=1, tiled=True)
        # canonical merge: equal-score candidates cut by smaller key, NOT by
        # shard order (plain top_k would prefer earlier shards on ties)
        ms, mi = _canonical_select(all_s, all_b, k_final)
        return ms, jnp.take_along_axis(all_g, mi, axis=1)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(axis), P(axis), P(None, None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )
    return fn(vectors, norms_sq, valid, key_bits, queries)


class ShardedBruteForceKnnIndex(BruteForceKnnIndex):
    """BruteForceKnnIndex whose slot matrix is sharded across a 1-D mesh axis.

    The [N, d] matrix lives partitioned in HBM across the mesh's devices; adds land
    in any free slot (slot→device mapping is implicit: slot // (N/n_devices));
    search runs the shard_map'd einsum + hierarchical top-k merge.
    """

    def __init__(
        self,
        dimension: int,
        mesh: Mesh,
        axis: str = "data",
        metric: KnnMetric | str = KnnMetric.COS,
        capacity: int = _MIN_CAPACITY,
        dtype: Any = jnp.float32,
    ):
        self.mesh = mesh
        self.axis = axis
        n_dev = mesh.shape[axis]
        capacity = _pad_to_capacity(max(capacity, n_dev * _MIN_CAPACITY))
        super().__init__(dimension, metric=metric, capacity=capacity, dtype=dtype)
        self._reshard()

    def _sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def _reshard(self) -> None:
        self._vectors = jax.device_put(self._vectors, self._sharding(P(self.axis, None)))
        self._norms_sq = jax.device_put(self._norms_sq, self._sharding(P(self.axis)))
        self._valid = jax.device_put(self._valid, self._sharding(P(self.axis)))
        self._key_bits = jax.device_put(self._key_bits, self._sharding(P(self.axis)))

    def _grow(self) -> None:
        super()._grow()
        self._reshard()

    def _flush(self) -> None:
        super()._flush()
        # scatters preserve sharding of the operand; nothing to do

    def search(self, queries: np.ndarray, k: int) -> list[list[tuple[Any, float]]]:
        self._flush()
        q = self._prep_queries(queries)
        scores, gids = sharded_search(
            self.mesh, self.axis, self._vectors, self._norms_sq, self._valid,
            self._key_bits, q,
            k=min(k, self.capacity), metric=self.metric.value,
        )
        scores_np, ids_np = self._fetch_hits(scores, gids)
        return _decode_hits(scores_np, ids_np, self._slot_to_key, k)
