"""Live span pipeline: always-on sampled tracing of the running dataflow.

The shutdown-time OTLP export (``internals/telemetry.py``) describes a run
*after* it ends; this module is the Dapper-style live plane the ROADMAP's
serving workloads need: while the pipeline runs, every sampled tick produces

- one ``tick`` span per process (child of a shared, deterministic run root),
- child spans for each ``_sweep`` node execution, microbatch UDF launch,
  device dispatch and kernel launch, persistence epoch commit, cluster
  barrier round, and the collector's passes (``Tracer.on_gc``),

appended incrementally to a bounded in-memory ring (served by the monitoring
server's ``/trace?since=`` endpoint) and, when configured, to a rotating
OTLP-JSON file sink — one ``ExportTraceServiceRequest`` JSON document per
line, the OTel collector file-exporter convention, loadable in Perfetto or
otel-desktop-viewer.

One clock: every site stamps ``time.monotonic_ns()`` — the clock a JAX
profiler trace is mapped onto with one marker — and :func:`unix_ns` converts
to Unix nanoseconds where OTLP is materialized. A span's parent is the span
open around it on the same thread (the tick where none is); spans that were
really open on a thread (``begin``/``end``) carry its id, so self time is a
span's duration minus what its children cover.

Overhead discipline (SnailTrail's "observe without perturbing"):

- ``PATHWAY_TRACE=off`` (default) installs **no tracer at all** — hot loops
  guard on a single ``is None`` check — until a JAX profiler session starts:
  ``observability.tick_tracer`` then brings one up (ring only, every tick
  sampled) for exactly the session's ticks;
- head sampling (``PATHWAY_TRACE_SAMPLE``) decides per TICK with a
  deterministic hash of the tick number, so every process of a cluster
  samples the SAME ticks and their spans stitch under one trace id;
- recording a span costs one tuple + ring append: OTLP-JSON
  materialization (attribute boxing, span-id formatting, serialization)
  happens lazily on the READ side — ``/trace`` requests and the file sink's
  background writer thread — never in the engine loop.
"""

from __future__ import annotations

import hashlib
import itertools
import json
import os
import secrets
import threading
import time as _time
from collections import deque
from typing import Any

#: 64-bit splitmix constant for the deterministic tick-sampling hash
_MIX = 0x9E3779B97F4A7C15
_MASK = (1 << 64) - 1

#: background file-sink writer wake period, seconds — the live file trails
#: the engine by at most this much
_SINK_FLUSH_S = 0.25

#: Unix-minus-monotonic nanoseconds: the ONE (time_ns, monotonic_ns) pair,
#: retaken at every ``observability.install_from_env``
_unix_offset_ns = _time.time_ns() - _time.monotonic_ns()


def reanchor() -> None:
    global _unix_offset_ns
    _unix_offset_ns = _time.time_ns() - _time.monotonic_ns()


def unix_ns(mono_ns: int) -> int:
    """A ``monotonic_ns`` stamp as Unix nanoseconds (OTLP read side only)."""
    return mono_ns + _unix_offset_ns


def mono_ns(unix: int) -> int:
    """Inverse of :func:`unix_ns`: a stamp another process shipped as Unix
    nanoseconds, on this process's monotonic clock."""
    return unix - _unix_offset_ns


def _attr(key: str, value: Any) -> dict:
    """OTLP attribute boxing (read-side only — never in the engine loop)."""
    if value is True or value is False:
        v: dict = {"boolValue": value}
    elif isinstance(value, int):
        v = {"intValue": str(value)}
    elif isinstance(value, float):
        v = {"doubleValue": value}
    else:
        v = {"stringValue": str(value)}
    return {"key": key, "value": v}


def derive_trace_id(run_id: str) -> str:
    """Deterministic 16-byte trace id from a run id — every process of a
    cluster run (sharing ``PATHWAY_RUN_ID`` via spawn) derives the SAME id,
    so per-process tick spans stitch into one trace."""
    return hashlib.sha256(("pathway-trace:" + run_id).encode()).hexdigest()[:32]


def derive_root_span_id(trace_id: str) -> str:
    """Deterministic root span id: peers parent their tick spans under the
    run root WITHOUT coordination; only process 0 emits the root span."""
    return hashlib.sha256(("pathway-root:" + trace_id).encode()).hexdigest()[:16]


def tick_hash_sampled(tick: int, rate: float) -> bool:
    """Deterministic head-sampling decision for a tick (identical on every
    process). ``rate`` ≥ 1 keeps everything, ≤ 0 nothing."""
    if rate >= 1.0:
        return True
    if rate <= 0.0:
        return False
    h = (tick * _MIX) & _MASK
    h ^= h >> 31
    h = (h * _MIX) & _MASK
    h ^= h >> 29
    return (h >> 11) / float(1 << 53) < rate


class RotatingTraceSink:
    """Append OTLP/JSON trace documents to a file, rotating at a size cap.

    Each write is one ``ExportTraceServiceRequest`` line containing a batch of
    spans; rotation moves the file to ``<path>.1`` (one generation kept) so a
    long-lived streaming run cannot fill the disk."""

    def __init__(self, path: str, rotate_bytes: int = 64 * 1024 * 1024):
        self.path = path
        self.rotate_bytes = rotate_bytes
        self._fh = open(path, "a", encoding="utf-8")
        self._resource = None  # built lazily (service.name + pid attrs)

    def _doc(self, spans: list[dict]) -> dict:
        if self._resource is None:
            self._resource = {
                "attributes": [
                    _attr("service.name", "pathway_tpu"),
                    _attr("process.pid", os.getpid()),
                ]
            }
        return {
            "resourceSpans": [
                {
                    "resource": self._resource,
                    "scopeSpans": [
                        {
                            "scope": {"name": "pathway_tpu.live", "version": "1"},
                            "spans": spans,
                        }
                    ],
                }
            ]
        }

    def write(self, spans: list[dict]) -> None:
        if not spans or self._fh.closed:
            return
        self._fh.write(json.dumps(self._doc(spans)) + "\n")
        self._fh.flush()
        if self._fh.tell() > self.rotate_bytes:
            self._rotate()

    def write_line(self, line: str) -> None:
        """Append one pre-serialized ExportTraceServiceRequest line (the
        tracer's direct serializer — generic ``json.dumps`` over materialized
        span dicts costs ~75µs/span on slow hosts; the fixed span shape
        serializes in a few µs with plain string building)."""
        if self._fh.closed:
            return
        self._fh.write(line + "\n")
        self._fh.flush()
        if self._fh.tell() > self.rotate_bytes:
            self._rotate()

    def _rotate(self) -> None:
        self._fh.close()
        try:
            os.replace(self.path, self.path + ".1")
        except OSError:
            pass
        self._fh = open(self.path, "a", encoding="utf-8")

    def close(self) -> None:
        if not self._fh.closed:
            self._fh.close()


class SpanBuffer:
    """Thread-safe bounded ring of span records with monotonically increasing
    sequence numbers (the ``/trace?since=`` cursor).

    Appends are the engine-side hot path: one lock + one deque append of
    whatever record the tracer hands in. ``materialize`` (set by the owning
    tracer; identity by default) converts a ``(seq, record)`` pair to its
    OTLP span dict on the READ side — ``since()`` and the file sink's
    background writer thread, which drains new records every
    ``_SINK_FLUSH_S`` seconds. ``dropped`` counts the records the ring
    overwrote: a reader that needs a whole window checks it is 0.

    A collector pass can start on a thread that holds the lock (a pass runs
    between any two bytecodes): its callback appends with ``wait`` False,
    and where the lock is taken the record waits in ``_late`` for the next
    append or read."""

    def __init__(self, max_spans: int = 8192, sink: RotatingTraceSink | None = None):
        self.max_spans = max_spans
        self.sink = sink
        self.materialize = lambda seq, rec: rec
        # set by the owning tracer: (seq, record) batch -> one OTLP/JSON line;
        # None falls back to materialize + sink.write
        self.serialize_batch = None
        self.dropped = 0
        self._lock = threading.Lock()
        self._ring: deque[tuple[int, Any]] = deque(maxlen=max_spans)
        self._late: deque = deque()
        self._seq = 0
        self._pending_sink: list[tuple[int, Any]] = []
        self._stop = threading.Event()
        self._writer: threading.Thread | None = None
        if sink is not None:
            self._writer = threading.Thread(
                target=self._writer_loop, name="pathway-trace-sink", daemon=True
            )
            self._writer.start()

    def append(self, record: Any, wait: bool = True) -> None:
        if not self._lock.acquire(wait):
            self._late.append(record)
            return
        try:
            self._take_late()
            self._push(record)
        finally:
            self._lock.release()

    def _push(self, record: Any) -> None:
        self._seq += 1
        if len(self._ring) == self.max_spans:
            self.dropped += 1
        self._ring.append((self._seq, record))
        if self.sink is not None:
            self._pending_sink.append((self._seq, record))

    def _take_late(self) -> None:
        while self._late:
            self._push(self._late.popleft())

    def records(self) -> list:
        """The ring's raw records, oldest first (monotonic stamps, parent
        ids, threads — what a reader in this process computes self times and
        gap covers from; stays readable after the tracer is retired)."""
        with self._lock:
            self._take_late()
            return [r for _q, r in self._ring]

    def since(self, seq: int, limit: int = 4096) -> tuple[list[dict], int]:
        """Spans recorded after cursor ``seq`` (oldest first) + the new
        cursor. When ``limit`` truncates, the cursor points at the last span
        actually RETURNED (not the ring head), so a slow poller drains the
        backlog over successive requests instead of silently skipping it."""
        with self._lock:
            self._take_late()
            out = [(q, r) for q, r in self._ring if q > seq]
            if len(out) > limit:
                out = out[:limit]
                next_seq = out[-1][0]
            else:
                next_seq = self._seq
        return [self.materialize(q, r) for q, r in out], next_seq

    # ------------------------------------------------------------- file sink
    def _writer_loop(self) -> None:
        while not self._stop.wait(_SINK_FLUSH_S):
            self.flush()

    def flush(self) -> None:
        if self.sink is None:
            return
        with self._lock:
            batch, self._pending_sink = self._pending_sink, []
        if not batch:
            return
        if self.serialize_batch is not None:
            self.sink.write_line(self.serialize_batch(batch))
        else:
            self.sink.write([self.materialize(q, r) for q, r in batch])

    def close(self) -> None:
        self._stop.set()
        if self._writer is not None:
            self._writer.join(timeout=5.0)
        self.flush()
        if self.sink is not None:
            self.sink.close()


class Tracer:
    """Per-run live tracer. Installed when ``PATHWAY_TRACE`` is on, or by
    ``observability.tick_tracer`` for the ticks of a JAX profiler session —
    every hot-path call site guards on ``tracer is not None`` first, so the
    off mode costs one attribute read + ``is None`` test.

    A record is ``(name, span_id, parent_id, start_ns, end_ns, attrs,
    trace_id, thread)``: stamps are ``monotonic_ns``; ids are ints (formatted
    at materialization), ready hex strings, or None (derived from the ring
    sequence number — leaf spans never draw an id); ``trace_id`` is None for
    the run's own trace (the request plane's per-request traces stitch next
    to it); ``thread`` is the thread a ``begin``/``end`` span was open on,
    None for a span recorded from stamps."""

    def __init__(
        self,
        *,
        trace_id: str,
        process_id: int = 0,
        sample: float = 1.0,
        buffer: SpanBuffer | None = None,
        session: bool = False,
    ):
        self.trace_id = trace_id
        self.root_span_id = derive_root_span_id(trace_id)
        self.process_id = process_id
        self.sample = sample
        #: brought up by a profiler session, retired with it
        self.session = session
        #: a profiler session is on: ``begin`` also enters a TraceAnnotation,
        #: so the tick loop shows above the device ops in the ``.xplane.pb``
        self.annotate = False
        self.buffer = buffer if buffer is not None else SpanBuffer()
        self.buffer.materialize = self._materialize
        self.buffer.serialize_batch = self._serialize_batch
        # static ExportTraceServiceRequest envelope around the span array
        self._doc_prefix = (
            '{"resourceSpans":[{"resource":{"attributes":['
            '{"key":"service.name","value":{"stringValue":"pathway_tpu"}},'
            f'{{"key":"process.pid","value":{{"intValue":"{os.getpid()}"}}}}'
            ']},"scopeSpans":[{"scope":{"name":"pathway_tpu.live","version":"1"},"spans":['
        )
        self._doc_suffix = "]}]}]}"
        # span names and attribute keys come from a tiny fixed set — cache
        # their JSON-escaped forms across flush batches
        self._dumps_cache: dict[str, str] = {}
        self.start_ns = _time.monotonic_ns()
        #: the last ``begin_tick``'s sampling decision; it stands until the
        #: next one, so the wait between two ticks follows the tick before it
        self.active = False
        # current sampled tick's span id, or None between/for unsampled ticks;
        # read by child-span emitters on worker threads (a benign race: a span
        # landing exactly at a tick boundary parents to the nearer tick)
        self.tick_span_id: str | None = None
        self._tick_id: int | None = None  # the same id as the ring holds it
        self.current_tick: int | None = None
        # (label, bucket) shapes already dispatched — first sight of a padded
        # shape is the process's XLA-compile proxy (fresh jit cache entry)
        self._seen_shapes: set = set()
        # ONE urandom draw; explicit ids (spans with children need theirs up
        # front for parenting) walk the 64-bit space from the random base,
        # and implicit ids derive from (base ^ seq) at materialization
        self._id_base = int.from_bytes(secrets.token_bytes(8), "big")
        self._id_counter = itertools.count(1)
        # per-thread stack of open spans: [span id, TraceAnnotation | None,
        # children recorded]
        self._tls = threading.local()
        # the collector's pass in progress (one at a time, on any thread), and
        # the open tick's generation-0 passes: [count, nanoseconds]
        self._gc_t0: int | None = None
        self._gc0 = [0, 0]

    def _hex_id(self, span_id: int | str) -> str:
        if isinstance(span_id, str):
            return span_id
        return f"{(self._id_base + span_id) & _MASK:016x}"

    def _seq_span_id(self, seq: int) -> str:
        # disjoint from _hex_id's range for any realistic run: explicit ids
        # count up from base, seq-derived ids flip the top bit
        return f"{(self._id_base ^ (1 << 63) ^ seq) & _MASK:016x}"

    def _materialize(self, seq: int, rec: tuple) -> dict:
        name, span_id, parent_id, start_ns, end_ns, attrs, trace_id, thread = rec
        boxed = [_attr(k, v) for k, v in attrs.items()] if attrs else []
        if thread is not None:
            boxed.append(_attr("thread.id", thread))
        span = {
            "traceId": trace_id or self.trace_id,
            "spanId": self._hex_id(span_id) if span_id is not None else self._seq_span_id(seq),
            "name": name,
            "kind": 1,
            "startTimeUnixNano": str(unix_ns(start_ns)),
            "endTimeUnixNano": str(unix_ns(end_ns)),
            "attributes": boxed,
        }
        if parent_id is not None:
            span["parentSpanId"] = self._hex_id(parent_id)
        return span

    def _serialize_batch(self, batch: list[tuple[int, tuple]]) -> str:
        """One OTLP/JSON line for a flush batch, by direct string building —
        the file-sink writer thread shares the GIL with the engine, so
        serialization speed IS tracing overhead."""
        dumps = json.dumps
        cache = self._dumps_cache

        def cdumps(s: str) -> str:
            r = cache.get(s)
            if r is None:
                r = cache[s] = dumps(s)
            return r

        parts = []
        for seq, rec in batch:
            name, span_id, parent_id, start_ns, end_ns, attrs, trace_id, thread = rec
            span_id = self._hex_id(span_id) if span_id is not None else self._seq_span_id(seq)
            a_parts = []
            for k, v in (attrs or {}).items():
                if v is True or v is False:
                    box = '{"boolValue":true}' if v else '{"boolValue":false}'
                elif isinstance(v, int):
                    box = f'{{"intValue":"{v}"}}'
                elif isinstance(v, float):
                    box = f'{{"doubleValue":{v!r}}}'
                else:
                    box = f'{{"stringValue":{dumps(str(v))}}}'
                a_parts.append(f'{{"key":{cdumps(k)},"value":{box}}}')
            if thread is not None:
                a_parts.append(f'{{"key":"thread.id","value":{{"intValue":"{thread}"}}}}')
            parent = (
                f'"parentSpanId":"{self._hex_id(parent_id)}",'
                if parent_id is not None
                else ""
            )
            parts.append(
                f'{{"traceId":"{trace_id or self.trace_id}","spanId":"{span_id}",{parent}'
                f'"name":{cdumps(name)},"kind":1,'
                f'"startTimeUnixNano":"{unix_ns(start_ns)}",'
                f'"endTimeUnixNano":"{unix_ns(end_ns)}",'
                f'"attributes":[{",".join(a_parts)}]}}'
            )
        return self._doc_prefix + ",".join(parts) + self._doc_suffix

    # -------------------------------------------------------------- sampling
    def tick_sampled(self, tick: int) -> bool:
        return tick_hash_sampled(tick, self.sample)

    # ----------------------------------------------------------- open spans
    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _unwind(self, st: list, depth: int) -> bool:
        """Close the entries above ``depth`` (one, unless an exception left
        deeper ones open) and say whether the entry at ``depth`` had a child
        recorded under it."""
        had_children = bool(st[depth][2]) if depth < len(st) else False
        while len(st) > depth:
            ann = st.pop()[1]
            if ann is not None:
                ann.__exit__(None, None, None)
        return had_children

    def begin(self, name: str) -> tuple | None:
        """Open a span on this thread: returns the token for :meth:`end`, or
        None on an unsampled tick (head sampling suppresses every child span
        of the tick)."""
        if not self.active:
            return None
        st = self._stack()
        ann = None
        if self.annotate:
            ann = _trace_annotation()(name)
            ann.__enter__()
        span_id = next(self._id_counter)
        st.append([span_id, ann, 0])
        return (self, len(st) - 1, span_id, name, _time.monotonic_ns())

    def end(self, tok: tuple, attrs: dict | None = None, keep: bool = True) -> None:
        """Close the span ``tok`` opened and record it under the span open
        around it on this thread (the tick where none is). ``keep`` False —
        nothing happened under it — leaves no span, unless a child was
        recorded, which would otherwise name a parent nobody can find."""
        _self, depth, span_id, name, start_ns = tok
        end_ns = _time.monotonic_ns()
        st = self._stack()
        if not (self._unwind(st, depth) or keep):
            return
        if st:
            st[-1][2] += 1
            parent = st[-1][0]
        else:
            parent = self._tick_id or self.root_span_id
        self.buffer.append(
            (name, span_id, parent, start_ns, end_ns, attrs, None, threading.get_ident())
        )

    # ------------------------------------------------------------------ ticks
    def begin_tick(self, tick: int) -> tuple | None:
        """Start-of-tick hook: returns the tick span's token when the tick is
        sampled (pass it back to ``end_tick``), else None — and the None also
        suppresses every child span of the tick (head sampling)."""
        self.active = self.tick_sampled(tick)
        self._unwind(self._stack(), 0)
        self._gc0 = [0, 0]
        tok = self.begin("tick")
        self._tick_id = tok[2] if tok is not None else None
        self.tick_span_id = self._hex_id(tok[2]) if tok is not None else None
        self.current_tick = tick if tok is not None else None
        return tok

    def end_tick(self, tick: int, tok: tuple, worked: bool = True, **attrs: Any) -> None:
        """Close the tick. A tick in which nothing was polled, swept or
        flushed (``worked`` False) and no span was recorded leaves no span."""
        if self.tick_span_id is None:
            return
        self.tick_span_id = self._tick_id = None  # its parent is the run root
        self.current_tick = None
        attrs["pathway.tick"] = tick
        attrs["pathway.process_id"] = self.process_id
        if self._gc0[0]:
            attrs["pathway.gc0_passes"], attrs["pathway.gc0_ns"] = self._gc0
        self.end(tok, attrs, keep=worked)

    # ---------------------------------------------------- spans from stamps
    def span(self, name: str, start_ns: int, end_ns: int, attrs: dict | None = None) -> None:
        """Record one finished span from two ``monotonic_ns`` stamps, under
        the span open on this thread, else the current tick, else the run
        root (e.g. a persistence commit between ticks)."""
        st = getattr(self._tls, "stack", None)
        if st:
            st[-1][2] += 1
            parent = st[-1][0]
        else:
            parent = self._tick_id or self.root_span_id
        self.buffer.append((name, None, parent, start_ns, end_ns, attrs, None, None))

    def event(self, name: str, attrs: dict | None = None) -> None:
        now = _time.monotonic_ns()
        self.span(name, now, now, attrs)

    def on_gc(self, phase: str, info: dict) -> None:
        """The ``gc.callbacks`` entry, registered by ``observability`` only
        while this tracer records. A pass of generation 1 or 2 is a
        ``gc/collect`` span (generation, collected) on the thread that ran
        it, under the span open there: it holds the GIL, so every thread
        waits on it. Generation 0's passes are too many for the ring: their
        count and nanoseconds go on the open tick (``pathway.gc0_passes``,
        ``pathway.gc0_ns``)."""
        now = _time.monotonic_ns()
        if phase == "start":
            self._gc_t0 = now
            return
        t0, self._gc_t0 = self._gc_t0, None
        if t0 is None or not self.active:
            return
        gen = info["generation"]
        if gen == 0:
            if self.tick_span_id is not None:
                self._gc0[0] += 1
                self._gc0[1] += now - t0
            return
        st = getattr(self._tls, "stack", None)
        if st:
            st[-1][2] += 1
            parent = st[-1][0]
        else:
            parent = self.root_span_id
        attrs = {"pathway.generation": gen, "pathway.collected": info["collected"]}
        self.buffer.append(("gc/collect", None, parent, t0, now, attrs, None, threading.get_ident()), wait=False)

    def first_shape(self, label: str, bucket: int) -> bool:
        """True exactly once per (udf label, padded bucket) — marks the
        device dispatch that triggers a fresh XLA compile on this process."""
        key = (label, bucket)
        if key in self._seen_shapes:
            return False
        self._seen_shapes.add(key)
        return True

    # ----------------------------------------------------------------- close
    def close(self, emit_root: bool = True) -> None:
        """Flush + close the sink; process 0 emits the shared run-root span
        every process's tick spans already parent to. The ring stays
        readable."""
        if emit_root and self.process_id == 0 and not self.session:
            self.buffer.append(
                (
                    "pathway.run",
                    self.root_span_id,
                    None,
                    self.start_ns,
                    _time.monotonic_ns(),
                    {"pathway.process_id": self.process_id},
                    None,
                    None,
                )
            )
        self.buffer.close()


_annotation_cls: Any = None


def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, or False where jax cannot be had."""
    global _annotation_cls
    if _annotation_cls is None:
        try:
            from jax.profiler import TraceAnnotation

            _annotation_cls = TraceAnnotation
        except Exception:  # pragma: no cover - jax is baked into the image
            _annotation_cls = False
    return _annotation_cls


def profiler_session_active() -> bool:
    """True between ``jax.profiler.start_trace`` and ``stop_trace`` (host
    tracer level >= 1): one flag read, ~80 ns."""
    cls = _trace_annotation()
    return bool(cls) and cls.is_enabled()


# ------------------------------------------------------------- sweep steps
# The ONE span block of a sweep step, shared by the three runtimes
# (engine/graph.py, parallel/sharded.py, parallel/cluster.py): a span on the
# live tracer and a stage event on the request plane, from the same stamps.


def step_begin(tracer: Tracer | None, rp: Any, name: str) -> tuple:
    """``tracer`` is None on an unsampled tick, ``rp`` None with no request
    in flight; a caller with neither skips the call."""
    return (tracer.begin(name) if tracer is not None else None, rp, name, _time.monotonic_ns())


def step_end(tok: tuple, time: int, rows_in: int, rows_out: int, attrs: dict) -> None:
    span, rp, name, start_ns = tok
    if rp is not None and (rows_in or rows_out):
        # a no-op visit (nothing drained, nothing emitted) touched no
        # request's rows — don't spend the per-tick ring budget
        rp.note_stage(time, name, start_ns, _time.monotonic_ns(), rows_in)
    if span is not None:
        attrs["pathway.rows_in"] = rows_in
        attrs["pathway.rows_out"] = rows_out
        span[0].end(span, attrs)


def step_drop(tok: tuple) -> None:
    if tok[0] is not None:
        tok[0][0].end(tok[0], keep=False)
