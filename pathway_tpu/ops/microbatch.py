"""Accumulate-then-launch UDF microbatcher.

The reference dispatches one boxed future **per row** for async UDFs
(``src/engine/dataflow.rs:1924-1962``). That per-row pattern is the single worst fit
for XLA (SURVEY §3.4, §7.1.5): each jit call has fixed dispatch overhead and a fresh
compile per shape. This dispatcher instead:

1. buffers rows per (udf, logical time window),
2. cuts a flush into launches of at most ``max_batch`` rows — from the rows in
   arrival order, or, when the UDF declares a per-row length and the flush holds
   more than one launch, from the rows stable-sorted by that length, so short
   rows launch with short rows and a launch pads to ITS longest, not the flush's,
3. pads each launch to the next power-of-two *bucket* (so the jitted callee sees a
   small closed set of shapes → compile cache hits),
4. invokes the batch function once per launch,
5. un-pads and scatters results back in submit order.

Works for any callee that maps ``list[values] -> list[results]``; TPU model UDFs
(embedder/reranker) provide a ``batch_fn`` operating on the padded arrays directly.
"""

from __future__ import annotations

from typing import Any, Callable, Protocol, Sequence

import numpy as np

_MIN_BUCKET = 8

#: sequence-LENGTH bucketing cap (token-id padding in the encoder/reranker and
#: scatter-block padding in knn): deliberately NOT the row-batch knob —
#: ``PATHWAY_MICROBATCH_MAX_BATCH`` caps how many ROWS launch together, while a
#: single row's padded token length may legitimately exceed it
LENGTH_MAX_BUCKET = 4096


def bucket_size(n: int, min_bucket: int = _MIN_BUCKET, max_bucket: int | None = None) -> int:
    """Smallest power-of-two ≥ n (clamped) — the padded batch shape.

    ``max_bucket=None`` (the default) resolves to ``PATHWAY_MICROBATCH_MAX_BATCH``
    so the knob actually caps row-batch launch shapes (it was a hardcoded 4096
    before r9, letting >knob flushes launch oversized buckets); length-bucketing
    callers pass :data:`LENGTH_MAX_BUCKET` explicitly."""
    if max_bucket is None:
        from pathway_tpu.internals.config import get_pathway_config

        max_bucket = get_pathway_config().microbatch_max_batch
    b = min_bucket
    while b < n and b < max_bucket:
        b *= 2
    return b


class RowStepper(Protocol):
    """Rows in flight across launches, for a UDF whose launch does not finish
    every row (``UDF.microbatch_stepper`` returns one). The dataflow node
    (``engine.operators.SteppingApplyNode``) admits waiting rows while
    ``free()`` says there is room, calls ``step()`` between ticks' other work,
    and emits each ``(handle, result)`` in the tick in which it came back."""

    def free(self) -> int:
        """How many more rows ``admit`` can take now."""

    def live(self) -> int:
        """Rows admitted and not yet finished or cancelled."""

    def admit(self, rows: list[tuple[Any, tuple, dict]]) -> list[tuple[Any, Any]]:
        """Take ``(handle, args, kwargs)`` rows in (at most ``free()``); they
        join the live rows at the next ``step``. Returns rows finished at once."""

    def step(self) -> list[tuple[Any, Any]]:
        """One launch over every live row; returns the rows it finished."""

    def cancel(self, handle: Any) -> None:
        """Drop a live row and free its place; it is never returned."""

    def size(self, result: Any) -> int:
        """What a finished row produced, in the stepper's unit (tokens): for the spans."""


class MicrobatchDispatcher:
    """Buffer rows, flush in padded power-of-two batches.

    ``fn`` is called as ``fn(items: list) -> Sequence`` where ``len(items)`` is
    always a bucket size; entries beyond the real row count are ``pad_item``
    repeats whose results are discarded.

    ``length_of`` is the UDF's declared per-row length estimate (``item -> int``,
    e.g. a text's word count for an encoder that pads a launch to its longest
    row). It only orders rows inside a flush of more than one launch; a poor
    estimate costs padding, never correctness.
    """

    def __init__(
        self,
        fn: Callable[[list], Sequence],
        max_batch: int | None = None,
        min_bucket: int = _MIN_BUCKET,
        pad_item: Any = None,
        label: str | None = None,
        length_of: Callable[[Any], int] | None = None,
    ):
        if max_batch is None:
            # align the default launch chunk with the knob (it was a hardcoded
            # 1024, so PATHWAY_MICROBATCH_MAX_BATCH silently didn't cap ad-hoc
            # dispatchers)
            from pathway_tpu.internals.config import get_pathway_config

            max_batch = get_pathway_config().microbatch_max_batch
        self.fn = fn
        self.max_batch = max_batch
        self.min_bucket = min_bucket
        self.pad_item = pad_item
        # span label for the live trace plane (e.g. the UDF name); dispatch
        # spans are suppressed when unset or tracing is off
        self.label = label
        self.length_of = length_of
        self._items: list = []

    def __len__(self) -> int:
        return len(self._items)

    def submit(self, item: Any) -> None:
        self._items.append(item)

    def flush(self, only_full: bool = False) -> list:
        """Run the batch fn over everything buffered; returns results in submit
        order. ``only_full=True`` launches only complete ``max_batch`` chunks
        (zero padding waste) and leaves the remainder buffered — the cross-tick
        accumulation mode: the engine keeps feeding rows and flushes the tail
        when nothing is queued behind it, at the latest on its autocommit
        deadline.

        With a declared ``length_of``, a flush of more than one launch cuts
        its launches from the rows stable-sorted by length (the partial chunk,
        if any, is the one at the long end); a flush of one launch, and a
        dispatcher without ``length_of``, launch in arrival order."""
        from pathway_tpu import observability as _obs
        from pathway_tpu.observability import device as _dev
        from pathway_tpu.observability import requests as _requests

        tracer = _obs.current() if self.label is not None else None
        if tracer is not None and not tracer.active:
            # head sampling: an unsampled tick records NO spans — dispatches
            # included (same gate as MicrobatchApplyNode's launch span)
            tracer = None
        # request plane: launches are stage events of every in-flight request
        # regardless of head sampling (tail sampling decides keep later)
        rp = _requests.current() if self.label is not None else None
        if rp is not None and not rp.hot:
            rp = None
        stats = _dev.stats()
        profiled = stats.enabled
        take = len(self._items)
        if only_full:
            take -= take % self.max_batch
        order = None
        if self.length_of is not None and take > self.max_batch:
            lengths = [self.length_of(it) for it in self._items[:take]]
            order = sorted(range(take), key=lengths.__getitem__)
            self._items[:take] = [self._items[i] for i in order]
        out: list = []
        while self._items and (not only_full or len(self._items) >= self.max_batch):
            chunk = self._items[: self.max_batch]
            del self._items[: self.max_batch]
            n = len(chunk)
            b = bucket_size(n, self.min_bucket, self.max_batch)
            pad = chunk[-1] if self.pad_item is None else self.pad_item
            padded = chunk + [pad] * (b - n)
            # cold = first sight of this padded launch shape on this process
            # (the XLA compile-cache lifetime, so tracked process-wide, not
            # per tracer); pad accounting runs on every launch. With the
            # profile plane off the r8 per-tracer cold marker still stands.
            label = self.label or getattr(self.fn, "__name__", "udf")
            if profiled:
                cold = stats.first_shape(f"udf:{label}", b)
                stats.note_pad_rows(f"udf:{label}", n, b - n)
                _dev.push_label(f"udf:{label}")
            else:
                cold = tracer is not None and tracer.first_shape(self.label, b)
            try:
                if tracer is not None or cold or rp is not None:
                    import time as _t

                    inner0 = _dev.thread_cold_s()
                    tok = tracer.begin("device/dispatch") if tracer is not None else None
                    w0 = _t.monotonic_ns()
                    results = self.fn(padded)
                    w1 = _t.monotonic_ns()
                    if rp is not None:
                        # pad share + cold-compile attribution ride the
                        # request flight path (the serving tier's "why was
                        # this query slow" often reads "cold bucket compile")
                        rattrs = {
                            "udf": label,
                            "bucket": b,
                            "pad": b - n,
                            "cold": cold,
                        }
                        if cold:
                            rattrs["compile_ms"] = round((w1 - w0) / 1e6, 3)
                        rp.note_stage(
                            None, f"microbatch/{label}", w0, w1, n, rattrs
                        )
                    if cold and profiled:
                        # measured compile wall time: the cold call pays jit
                        # trace + XLA compile (+ one execution) — accumulated
                        # into the per-process compile-seconds counter, net
                        # of compiles traced jits inside the launch already
                        # booked for themselves
                        stats.note_cold(
                            f"udf:{label}",
                            (w1 - w0) / 1e9,
                            b,
                            inner_s=_dev.thread_cold_s() - inner0,
                        )
                    if tok is not None:
                        attrs = {
                            "pathway.udf": self.label,
                            "pathway.bucket": b,
                            "pathway.rows": n,
                            "pathway.cold_shape": cold,
                        }
                        if cold:
                            attrs["pathway.compile_ms"] = round((w1 - w0) / 1e6, 3)
                        tracer.end(tok, attrs)
                else:
                    results = self.fn(padded)
            finally:
                if profiled:
                    _dev.pop_label()
            if len(results) != b:
                raise ValueError(
                    f"microbatch fn returned {len(results)} results for batch of {b}"
                )
            out.extend(results[:n])
        if order is not None:
            by_submit: list = [None] * take
            for i, r in zip(order, out):
                by_submit[i] = r
            out = by_submit
        return out

    def map(self, items: list) -> list:
        """One-shot convenience: submit all, flush."""
        for it in items:
            self.submit(it)
        return self.flush()


def pad_ragged_2d(
    rows: list[np.ndarray], bucket_len: int | None = None, fill: float = 0
) -> tuple[np.ndarray, np.ndarray]:
    """Pad a list of 1-D arrays to [n, L] + bool mask, L a power-of-two bucket —
    the shape discipline for token-id batches entering jitted models."""
    n = len(rows)
    max_len = max((len(r) for r in rows), default=1)
    L = bucket_len or bucket_size(max_len, min_bucket=16, max_bucket=LENGTH_MAX_BUCKET)
    out = np.full((n, L), fill, dtype=np.asarray(rows[0]).dtype if rows else np.int32)
    mask = np.zeros((n, L), dtype=bool)
    for i, r in enumerate(rows):
        r = np.asarray(r)[:L]
        out[i, : len(r)] = r
        mask[i, : len(r)] = True
    return out, mask
