"""Cross-tick device microbatching (ISSUE r6 tentpole).

The dispatcher (``ops/microbatch.py``) is wired into the real UDF dispatch
path: ``is_batched`` UDF rows buffer ACROSS streaming ticks per UDF, launch as
padded power-of-two batches, and scatter back on the completing tick. These
tests pin the correctness contract: byte-identity of final streaming results
vs per-tick dispatch, retractions mid-buffer, per-row error poisoning,
flush-on-deadline ordering, and the ``pending``/``await_futures`` discipline.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine.blocks import DeltaBatch
from pathway_tpu.engine.graph import EngineGraph, Node, Scheduler
from pathway_tpu.engine.operators import MicrobatchApplyNode, MicrobatchUdfSpec, StreamInputNode
from pathway_tpu.engine.runtime import Runtime
from pathway_tpu.internals.errors import ERROR, PENDING
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.internals.udfs import UDF
from utils import keyed_rows_of, rows_of


class _TrackingUdf(UDF):
    """Deterministic batched UDF that records every launch's size and inputs."""

    is_batched = True

    def __init__(self, fn=None):
        self.batches: list[list] = []
        base = fn or (lambda x: x * 3 + 1)

        def batch_fn(xs):
            self.batches.append(list(xs))
            return [base(x) for x in xs]

        super().__init__(_fn=batch_fn, return_type=int)

    @property
    def seen(self) -> list:
        return [x for b in self.batches for x in b]


class KS(pw.Schema):
    k: int = pw.column_definition(primary_key=True)
    x: int


# events: (k, x, time, diff) — inserts over 6 ticks with a retract+re-insert
_EVENTS = (
    [(i, 10 + i, i // 8, 1) for i in range(48)]
    + [(3, 13, 2, -1), (3, 113, 3, 1)]  # upsert of k=3 mid-stream
    + [(40, 50, 6, -1)]  # plain retract of a row inserted at tick 5
)


def _pipeline(u: UDF):
    t = pw.debug.table_from_rows(KS, _EVENTS, is_stream=True)
    s = t.select(t.k, y=u(t.x), parity=t.x % 2)
    # a stateful consumer downstream: corrections must flow through groupby
    g = s.groupby(s.parity).reduce(s.parity, total=pw.reducers.sum(s.y))
    return s, g


def test_streaming_results_identical_to_per_tick_dispatch(monkeypatch):
    monkeypatch.setenv("PATHWAY_MICROBATCH", "off")
    u_off = _TrackingUdf()
    s, g = _pipeline(u_off)
    rows_off, agg_off = keyed_rows_of(s), rows_of(g)

    G.clear()
    monkeypatch.setenv("PATHWAY_MICROBATCH", "auto")
    u_on = _TrackingUdf()
    s2, g2 = _pipeline(u_on)
    rows_on, agg_on = keyed_rows_of(s2), rows_of(g2)

    assert rows_on == rows_off
    assert agg_on == agg_off
    # the whole point: strictly fewer launches than the per-tick path, and
    # power-of-two padded launch sizes
    assert len(u_on.batches) < len(u_off.batches)
    assert all((len(b) & (len(b) - 1)) == 0 for b in u_on.batches)


def test_retraction_mid_buffer_cancels_launch(monkeypatch):
    monkeypatch.setenv("PATHWAY_MICROBATCH", "auto")
    # huge deadline: nothing flushes until the stream drains, so the tick-2
    # retract of k=3 lands while its row is still buffered
    monkeypatch.setenv("PATHWAY_MICROBATCH_FLUSH_MS", "60000")
    u = _TrackingUdf()
    t = pw.debug.table_from_rows(
        KS, [(1, 10, 0, 1), (3, 13, 0, 1), (2, 20, 1, 1), (3, 13, 2, -1)],
        is_stream=True,
    )
    s = t.select(t.k, y=u(t.x))
    assert sorted(rows_of(s)) == [(1, 31), (2, 61)]
    # the cancelled row never reached the device: 13 appears in no launch
    # (pad rows repeat the LAST buffered row, which is never the cancelled one
    # here), and exactly one launch covers the surviving rows
    assert 13 not in u.seen
    assert len(u.batches) == 1


def test_udf_error_poisons_only_its_rows(monkeypatch):
    monkeypatch.setenv("PATHWAY_MICROBATCH", "auto")

    def explode(x):
        if x == 13:
            raise ValueError("bad row")
        return x * 3 + 1

    u = _TrackingUdf(fn=explode)
    t = pw.debug.table_from_rows(
        KS, [(i, 10 + i, i // 4, 1) for i in range(8)], is_stream=True
    )
    s = t.select(t.k, y=u(t.x))
    rows = {row[0]: row for row in keyed_rows_of(s).values()}
    assert rows[3] == (3, ERROR)
    for k in [0, 1, 2, 4, 5, 6, 7]:
        assert rows[k] == (k, (10 + k) * 3 + 1)


def test_pending_mode_settles_through_await_futures(monkeypatch):
    monkeypatch.setenv("PATHWAY_MICROBATCH", "pending")
    u = _TrackingUdf()
    t = pw.debug.table_from_rows(
        KS, [(i, 10 + i, i // 4, 1) for i in range(8)], is_stream=True
    )
    s = t.select(t.k, y=u(t.x))
    settled = s.await_futures()
    from pathway_tpu.debug import _capture

    cap = _capture(settled)
    rows = {row[0]: tuple(row) for row in cap.rows.values()}
    assert rows == {k: (k, (10 + k) * 3 + 1) for k in range(8)}
    # no PENDING survives await_futures, at any tick
    assert all(PENDING not in row for (_t, _k, _d, row) in cap.deltas)


# ------------------------------------------------------------- node-level unit


def _make_node(max_batch=64, runtime=None, flush_ms=None, mode="hold"):
    calls: list[int] = []

    def fn(xs):
        calls.append(len(xs))
        return [x * 2 for x in xs]

    def args_program(batch):
        return [np.asarray(batch.data["x"])], []

    spec = MicrobatchUdfSpec("y", args_program, fn, [], False)
    node = MicrobatchApplyNode(
        ["y"], [], lambda b: {}, [spec],
        np_dtypes={"y": np.dtype(np.int64)},
        mode=mode, max_batch=max_batch, flush_ms=flush_ms, runtime=runtime,
    )
    return node, calls


def _batch(keys, xs, time, diffs=None):
    n = len(keys)
    return DeltaBatch(
        np.asarray(keys, dtype=np.uint64),
        np.asarray(diffs if diffs is not None else [1] * n, dtype=np.int64),
        {"x": np.asarray(xs, dtype=np.int64)},
        time,
    )


class _LiveDriver:
    def is_finished(self):
        return False


class _FakeRuntime:
    streaming = True
    autocommit_duration_ms = 5

    def __init__(self):
        self.connectors = [_LiveDriver()]


def test_flush_on_deadline_ordering():
    """A buffered row must launch within the autocommit deadline, at a LATER
    tick than its arrival, and full chunks launch immediately."""
    rt = _FakeRuntime()
    node, calls = _make_node(max_batch=8, runtime=rt)
    node.process([_batch([1, 2], [10, 20], 0)], 0)
    assert node.on_frontier(0) == []  # fresh rows: held, latency budget intact
    assert calls == []
    time.sleep(0.01)  # > autocommit_duration_ms
    out = node.on_frontier(3)
    assert calls == [8]  # padded to the min bucket
    [b] = out
    assert b.time == 3  # scattered back on the completing tick
    assert sorted(zip(b.keys.tolist(), b.data["y"].tolist())) == [(1, 20), (2, 40)]

    # a full max_batch chunk launches in process(), before any deadline
    node.process([_batch(list(range(10, 22)), list(range(12)), 4)], 4)
    assert calls[1:] == [8]  # one full chunk of 8 launched, 4 rows remain
    assert len(node.waiting) == 4


class _OpaqueSource(Node):
    """A source that cannot say whether input is queued (the base answer)."""

    name = "opaque_source"

    def __init__(self):
        super().__init__(n_inputs=0)

    def poll(self, time):
        return []


def _live_graph(opaque=False, flush_ms=None):
    """source -> microbatch node under a live single-process ``Runtime`` that
    the test ticks by hand. ``arrivals[t]`` are the rows that reach the
    source's queue while tick ``t`` runs (after its poll); ``launches`` notes
    every flush as (reason, rows)."""
    rt = Runtime(autocommit_duration_ms=5)
    rt.connectors.append(_LiveDriver())
    rt.streaming = True
    node, calls = _make_node(max_batch=8, runtime=rt, flush_ms=flush_ms)
    src = StreamInputNode(["x"], {"x": np.dtype(np.int64)})
    graph = EngineGraph()
    graph.add_node(src, [])
    if opaque:
        graph.add_node(_OpaqueSource(), [])
    graph.add_node(node, [src])
    rt.scheduler = Scheduler(graph)
    arrivals: dict[int, list] = {}
    launches: list[tuple[str, int]] = []

    def poll(time, _poll=src.poll):
        out = _poll(time)
        for k, x in arrivals.pop(time, ()):
            src.push(k, (x,))
        return out

    def flush(time, only_full=False, reason="full", _flush=node._flush):
        held = len(node.waiting)
        out = _flush(time, only_full=only_full, reason=reason)
        if held != len(node.waiting):
            launches.append((reason, held - len(node.waiting)))
        return out

    src.poll, node._flush = poll, flush
    return rt, src, node, calls, arrivals, launches


@pytest.mark.parametrize(
    "case", ["idle", "queued_behind", "poller_cannot_say", "runtime_cannot_say", "arrivals_under_a_launch"]
)
def test_tail_is_held_only_while_input_is_queued_behind_it(case):
    """ISSUE 35: a tail leaves at the frontier of the tick that brought it
    when no source holds unpolled input (``idle``); with input queued it waits
    for the tick that input brings, the deadline its upper bound; a runtime or
    a source that cannot say is held to the deadline, as before."""
    if case == "runtime_cannot_say":
        node, calls = _make_node(max_batch=8, runtime=_FakeRuntime())
        node.process([_batch([1, 2], [10, 20], 0)], 0)
        assert node._should_flush(0) is None
        time.sleep(0.01)  # > autocommit_duration_ms
        assert node._should_flush(1) == "deadline"
        return
    rt, src, node, calls, arrivals, launches = _live_graph(
        opaque=case == "poller_cannot_say", flush_ms=60000 if case == "queued_behind" else None
    )
    src.push(1, (10,))
    src.push(2, (20,))
    if case == "idle":
        assert rt.input_queued()  # the two rows, until a tick polls them
        rt.scheduler.run_tick(0)
        assert not rt.input_queued()
        # one launch of the minimum bucket, in the tick that brought the rows
        assert launches == [("idle", 2)] and calls == [8] and not node.waiting
    elif case == "queued_behind":
        arrivals[0] = [(3, 30), (4, 40)]  # queued while tick 0 runs
        rt.scheduler.run_tick(0)
        assert rt.input_queued() and launches == [] and len(node.waiting) == 2
        rt.scheduler.run_tick(1)  # the tick that input asked for: all four leave together
        assert launches == [("idle", 4)] and calls == [8] and not node.waiting
    elif case == "poller_cannot_say":
        rt.scheduler.run_tick(0)
        assert rt.input_queued() and launches == []
        time.sleep(0.01)  # > autocommit_duration_ms
        rt.scheduler.run_tick(1)
        assert launches == [("deadline", 2)] and calls == [8]
    else:
        # batching under load needs no timer: what arrives while a launch and
        # its tick run waits in the source's queue, and the next tick takes it
        # all in one launch
        arrivals[0] = [(3, 30), (4, 40), (5, 50)]
        rt.scheduler.run_tick(0)
        assert launches == [] and rt.input_queued()  # held: the next tick is already asked for
        arrivals[1] = [(k, k * 10) for k in range(6, 11)]
        rt.scheduler.run_tick(1)
        assert launches == [] and len(node.waiting) == 5  # still under the chunk, still queued behind
        rt.scheduler.run_tick(2)
        # ten rows over three ticks: one full chunk on arrival, the tail at
        # the first frontier that finds nothing behind it
        assert launches == [("full", 8), ("idle", 2)] and calls == [8, 8]


def _launches_of(run, monkeypatch, parent_rule: bool):
    """The launches (inputs, in order) of ``run`` under this tree's rule or
    the parent's (every runtime counts as holding queued input)."""
    G.clear()
    u = _TrackingUdf()
    with monkeypatch.context() as m:
        if parent_rule:
            m.setattr(Runtime, "input_queued", lambda self: True)
        return run(u), u.batches


@pytest.mark.parametrize("stream", ["events", "retraction", "two_sources"])
def test_debug_streams_launch_what_they_launched_before(stream, monkeypatch):
    """A debug stream's later times are queued input: its tails are held as
    before ISSUE 35 and the same rows reach the UDF in the same launches."""

    def run(u):
        # wall-clock out of the picture: only the stream's drain flushes a tail
        monkeypatch.setenv("PATHWAY_MICROBATCH", "auto")
        monkeypatch.setenv("PATHWAY_MICROBATCH_FLUSH_MS", "60000")
        if stream == "events":
            s, g = _pipeline(u)
            return keyed_rows_of(s), rows_of(g)
        if stream == "retraction":
            t = pw.debug.table_from_rows(
                KS, [(1, 10, 0, 1), (3, 13, 0, 1), (2, 20, 1, 1), (3, 13, 2, -1)], is_stream=True
            )
            return keyed_rows_of(t.select(t.k, y=u(t.x)))
        # one source ends at tick 1, the other at tick 4: the first's tail
        # waits for the second's last tick
        a = pw.debug.table_from_rows(KS, [(i, i, i // 2, 1) for i in range(4)], is_stream=True)
        b = pw.debug.table_from_rows(KS, [(10 + i, i, i, 1) for i in range(5)], is_stream=True)
        t = a.concat_reindex(b)
        return rows_of(t.select(y=u(t.x)))

    new, launched = _launches_of(run, monkeypatch, parent_rule=False)
    old, launched_before = _launches_of(run, monkeypatch, parent_rule=True)
    assert new == old
    # everything leaves at the drain: one launch a run ("events" runs twice, a capture each)
    assert launched == launched_before and len(launched) == (2 if stream == "events" else 1)


def _make_deterministic_node(max_batch=8, runtime=None):
    """All-deterministic spec: the node keeps NO ``emitted`` state — retracts
    of settled rows hit the r14 bounded replay cache (or recompute on miss)."""
    calls: list[int] = []

    def fn(xs):
        calls.append(len(xs))
        return [x * 2 for x in xs]

    def args_program(batch):
        return [np.asarray(batch.data["x"])], []

    spec = MicrobatchUdfSpec("y", args_program, fn, [], False, deterministic=True)
    node = MicrobatchApplyNode(
        ["y"], [], lambda b: {}, [spec],
        np_dtypes={"y": np.dtype(np.int64)},
        max_batch=max_batch, runtime=runtime,
    )
    return node, calls


def test_settled_retract_replays_cached_output_without_relaunch():
    """r14 serving hot path: a retract of a recently-emitted row (the
    delete_completed_queries pattern — every served query row is retracted
    one tick later) must replay the cached output, NOT re-run the device UDF
    in a tiny padded launch."""
    rt = _FakeRuntime()
    node, calls = _make_deterministic_node(max_batch=8, runtime=rt)
    assert not node._remember  # deterministic: no emitted-row state
    node.process([_batch([5], [21], 0)], 0)
    time.sleep(0.01)
    [b0] = node.on_frontier(1)
    assert calls == [8] and b0.data["y"].tolist() == [42]
    # settled retract: answered from the replay cache, zero launches
    [b1] = node.process([_batch([5], [21], 2, diffs=[-1])], 2)
    assert b1.diffs.tolist() == [-1]
    assert b1.data["y"].tolist() == [42]
    assert calls == [8], "retract must not re-launch the UDF"
    # a REUSED key with different input values must miss the cache (the
    # signature guard) and fall back to recompute — correctness over speed
    [b2] = node.process([_batch([5], [50], 3, diffs=[-1])], 3)
    assert b2.data["y"].tolist() == [100]
    assert len(calls) == 2  # the recompute launched


def test_cross_tick_upsert_out_of_order_retract():
    """A key with BOTH a settled row and a newer buffered version: a retract
    must target whichever version its input values match — the old settled row
    keeps flowing out, the buffered one keeps its launch."""
    rt = _FakeRuntime()
    node, calls = _make_node(max_batch=8, runtime=rt)
    node.process([_batch([5], [10], 0)], 0)
    time.sleep(0.01)
    [b1] = node.on_frontier(1)  # v1 settles: y = 20
    assert b1.data["y"].tolist() == [20]

    # new version buffered, then the OLD version's retract arrives
    node.process([_batch([5], [11], 2)], 2)
    [b2] = node.process([_batch([5], [10], 3, diffs=[-1])], 3)
    assert b2.diffs.tolist() == [-1]
    assert b2.data["y"].tolist() == [20]  # retracts settled v1, not buffered v2
    time.sleep(0.01)
    [b3] = node.on_frontier(4)
    assert b3.data["y"].tolist() == [22]  # v2 still launches

    # and the converse: retract of the BUFFERED version cancels in-buffer
    node.process([_batch([5], [12], 5)], 5)
    launches_before = list(calls)
    out = node.process([_batch([5], [12], 6, diffs=[-1])], 6)
    assert out == [] or all(b.is_empty for b in out)
    time.sleep(0.01)
    assert node.on_frontier(7) == []  # nothing left to flush
    assert calls == launches_before  # the cancelled row never launched


def test_retract_exceeding_buffered_count_reaches_settled_row():
    """consolidate may merge retracts of a buffered copy AND a settled copy of
    one key into a single diff — the excess beyond the buffered count must
    retract the settled row, not vanish."""
    rt = _FakeRuntime()
    node, calls = _make_node(max_batch=8, runtime=rt)
    node.process([_batch([5], [10], 0)], 0)
    time.sleep(0.01)
    node.on_frontier(1)  # first copy settles downstream
    node.process([_batch([5], [10], 2)], 2)  # identical second copy buffered
    [b] = node.process([_batch([5], [10], 3, diffs=[-2])], 3)
    assert b.diffs.tolist() == [-1]
    assert b.data["y"].tolist() == [20]  # the settled row is retracted
    assert not node.waiting and not node.emitted


def test_retract_of_buffered_nan_row_cancels():
    """NaN inputs: NaN != NaN must not defeat the retract-vs-buffer value
    match — the retract cancels in-buffer, nothing phantom flows downstream."""
    rt = _FakeRuntime()
    node, calls = _make_node(max_batch=8, runtime=rt)

    def nan_batch(diffs):
        return DeltaBatch(
            np.asarray([7], dtype=np.uint64),
            np.asarray(diffs, dtype=np.int64),
            {"x": np.asarray([float("nan")], dtype=np.float64)},
            0,
        )

    node.process([nan_batch([1])], 0)
    out = node.process([nan_batch([-1])], 1)
    assert out == [] or all(b.is_empty for b in out)
    assert not node.waiting
    time.sleep(0.01)
    assert node.on_frontier(2) == []
    assert calls == []  # the cancelled row never launched


def test_static_run_flushes_at_its_single_tick():
    node, calls = _make_node(runtime=None)  # no runtime = static discipline
    node.process([_batch([1], [5], 0)], 0)
    out = node.on_frontier(0)
    assert calls == [8]
    assert out[0].time == 0


# ------------------------------------------------ the ingest cell's own lengths


class _TS(pw.Schema):
    k: int = pw.column_definition(primary_key=True)
    text: str


class _RecordingTokenizer:
    """The embedder's tokenizer, noting the shape of every launch."""

    def __init__(self, tok):
        self.tok = tok
        self.pad_id_zero = tok.pad_id_zero
        self.shapes: list[tuple] = []

    def __call__(self, texts):
        ids, mask = self.tok(texts)
        self.shapes.append(ids.shape)
        return ids, mask


@pytest.mark.parametrize("blocks_per_tick", [8, 1])
def test_ingest_cell_lengths_pad_share(monkeypatch, blocks_per_tick):
    """A count of work on ``minilm-l6.ingest-backfill``'s documents: eight
    blocks of 512 arriving in one tick are one flush of eight launches, cut
    from the length-sorted rows; arriving a block a tick, every flush is one
    launch in arrival order and pads to the block's 240-word document."""
    import json
    import os

    from chipbench import corpus
    from pathway_tpu.observability import device
    from pathway_tpu.ops.encoder import EncoderConfig
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    monkeypatch.setenv("PATHWAY_MICROBATCH", "auto")
    here = os.path.dirname(os.path.abspath(corpus.__file__))
    with open(os.path.join(here, "configs", "live-rag-minilm-l6.json"), encoding="utf-8") as f:
        lengths = json.load(f)["documents"]
    texts = corpus.docs(7, 0, 8, lengths)
    emb = SentenceTransformerEmbedder(
        EncoderConfig(vocab_size=30522, d_model=16, n_heads=1, n_layers=1, d_ff=32)
    )
    tok = emb._encoder.tokenizer = _RecordingTokenizer(emb._encoder.tokenizer)
    tick = lambda i: i // (corpus.BLOCK * blocks_per_tick)  # noqa: E731
    t = pw.debug.table_from_rows(
        _TS, [(i, x, tick(i), 1) for i, x in enumerate(texts)], is_stream=True
    )
    got = keyed_rows_of(t.select(t.k, v=emb(t.text)))
    assert len(got) == len(texts)
    # utils._norm gives an embedding as ("ndarray", shape, values)
    assert all(row[1][1] == (16,) and np.isfinite(row[1][2]).all() for row in got.values())

    # the run's own counters: a run resets them when it starts
    _rows, _pad_rows, real, pad = device.stats().pad["encoder"]
    assert real == sum(len(x.split()) + 1 for x in texts)
    share = 100.0 * pad / (real + pad)
    assert [rows for rows, _ in tok.shapes] == [corpus.BLOCK] * 8
    if blocks_per_tick == 8:
        assert {L for _, L in tok.shapes} == {32, 64, 128, 256}
        assert share < 45.0
    else:
        assert {L for _, L in tok.shapes} == {256}
        assert 74.0 < share < 75.0
