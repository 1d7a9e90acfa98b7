"""The span plane inside the tick loop (ISSUE 25): one recorder on one clock,
on exactly when somebody is tracing — ``PATHWAY_TRACE`` or a JAX profiler
session —, real parents, waits and transfers recorded where they happen, no
device sync added, and a ring that outlives the run."""

from __future__ import annotations

import tempfile
import time

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu import observability as obs
from pathway_tpu.engine.runtime import TickWakeup
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.observability import device, spans
from pathway_tpu.observability.spans import SpanBuffer, Tracer
from tests.test_trace_schema import validate_span

NAME, SID, PARENT, T0, T1, ATTRS, TRACE, THREAD = range(8)


@pytest.fixture(autouse=True)
def _no_trace_env(monkeypatch):
    for k in ("PATHWAY_TRACE", "PATHWAY_TRACE_BUFFER", "PATHWAY_PROFILE", "PATHWAY_PROFILE_DIR"):
        monkeypatch.delenv(k, raising=False)
    yield
    obs.shutdown()


def _stream(rows, pause=0.03, hook=None):
    class Subj(pw.io.python.ConnectorSubject):
        def run(self):
            for i, text in enumerate(rows):
                if hook is not None:
                    hook(i)
                self.next(text=text)
                time.sleep(pause)

    return Subj()


def _index_pipeline(subject, on_text=None):
    """Live documents -> embed (microbatched) -> KNN index <- one static query.
    ``on_text`` sees every document's text in the sweep of the tick that
    polled it, before the embedder's frontier."""
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.mocks import FakeEmbedder

    G.clear()
    docs = pw.io.python.read(subject, schema=pw.schema_from_types(text=str))
    if on_text is not None:
        docs = docs.select(text=pw.apply(on_text, docs.text))
    index = BruteForceKnnFactory(
        embedder=FakeEmbedder(dimension=8, deterministic=True), reserved_space=64
    ).build_index(docs.text, docs)
    q = pw.debug.table_from_rows(pw.schema_from_types(query=str), [("doc 1",)])
    res = index.query(q.query, number_of_matches=1).select(top=pw.right.text)
    pw.io.subscribe(res, on_change=lambda **k: None)


def _records():
    ring = obs.last_recording()
    return ring.records() if ring is not None else []


def _wait_for(cond, seconds=20.0):
    deadline = time.monotonic() + seconds
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.01)


def _active_tracer(monkeypatch) -> Tracer:
    monkeypatch.setenv("PATHWAY_TRACE", "on")
    tracer = obs.install_from_env()
    tracer.begin_tick(0)
    return tracer


# ------------------------------------------------------- on with a session


@pytest.mark.parametrize("how", ["start_trace", "flag_patched"])
def test_spans_appear_with_a_profiler_session_and_stop_with_it(how, monkeypatch):
    """PATHWAY_TRACE unset: the first tick that finds a session brings the
    tracer up, the first that finds it gone retires it."""
    import jax

    state = {"on": False, "at_stop": None}
    if how == "flag_patched":
        monkeypatch.setattr(spans, "profiler_session_active", lambda: state["on"])

    def hook(i):
        if i == 2:
            if how == "start_trace":
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level, opts.host_tracer_level = 0, 1
                jax.profiler.start_trace(tempfile.mkdtemp(), profiler_options=opts)
            state["on"] = True
        if i == 6:
            _wait_for(lambda: {"microbatch/launch", "tick/wait"} <= {r[NAME] for r in _records()})
            if how == "start_trace":
                jax.profiler.stop_trace()
            state["on"] = False
            _wait_for(lambda: obs.current() is None)  # a tick finds the session gone
            state["at_stop"] = (obs.current(), len(_records()))

    _index_pipeline(_stream([f"doc {i}" for i in range(10)], hook=hook))
    assert obs.current() is None
    pw.run(monitoring_level="none")
    tracer_at_stop, n_at_stop = state["at_stop"]
    assert tracer_at_stop is None and obs.current() is None
    assert pw.internals.run.current_runtime().scheduler.tracer is None
    recs = _records()
    assert len(recs) == n_at_stop  # nothing recorded after the session
    names = {r[NAME] for r in recs}
    assert {"tick", "tick/wait", "microbatch/launch", "device/dispatch", "index/add"} <= names
    assert any(n.startswith("tick/poll/") for n in names) and any(n.startswith("frontier/") for n in names)
    waits = [r for r in recs if r[NAME] == "tick/wait"]
    assert {r[ATTRS]["pathway.woken"] for r in waits} <= {"arrival", "period"}


def test_wakeup_wait_says_what_woke_it():
    w = TickWakeup()
    assert w.wait(0.01) == "period"
    w.request()
    assert w.wait(1.0) == "arrival"
    w.request(delay_s=0.001)
    assert w.wait(1.0) == "arrival"  # the coalesce deadline, not the period


# --------------------------------------------------------------- one clock


def test_stamps_are_monotonic_and_materialize_as_unix_times():
    m0, u0 = time.monotonic_ns(), time.time_ns()
    tr = Tracer(trace_id="ab" * 16, buffer=SpanBuffer(max_spans=16))
    tick = tr.begin_tick(1)
    tr.end(tr.begin("sweep/x"))
    tr.event("audit/violation")
    tr.end_tick(1, tick)
    tr.close()
    m1, u1 = time.monotonic_ns(), time.time_ns()
    for rec in tr.buffer.records():
        assert m0 <= rec[T0] <= rec[T1] <= m1, rec
    out, _ = tr.buffer.since(0)
    assert [s["name"] for s in out] == ["sweep/x", "audit/violation", "tick", "pathway.run"]
    slack = 50_000_000  # the two clocks are read apart
    for span in out:
        validate_span(span)
        assert u0 - slack <= int(span["startTimeUnixNano"]) <= int(span["endTimeUnixNano"]) <= u1 + slack
    assert out == [tr._materialize(q, r) for q, r in tr.buffer._ring]
    assert spans.mono_ns(spans.unix_ns(m0)) == m0


# ------------------------------------------------------------ real parents


def _tree(recs):
    """(by id, children by parent id) of the run's own records."""
    by_id = {r[SID]: r for r in recs if r[SID] is not None and r[TRACE] is None}
    kids: dict = {}
    for r in recs:
        if r[TRACE] is None:
            kids.setdefault(r[PARENT], []).append(r)
    return by_id, kids


def test_parents_nest_and_self_times_sum_to_the_tick(monkeypatch):
    monkeypatch.setenv("PATHWAY_TRACE", "on")
    _index_pipeline(_stream([f"doc {i}" for i in range(6)]))
    pw.run(monitoring_level="none")
    recs = _records()
    by_id, kids = _tree(recs)
    dispatches = [r for r in recs if r[NAME] == "device/dispatch"]
    assert dispatches
    for d in dispatches:
        launch = by_id[d[PARENT]]
        assert launch[NAME] == "microbatch/launch"
        node = by_id[launch[PARENT]]
        assert node[NAME].startswith(("sweep/", "frontier/")) and "microbatch" in node[NAME]
        tick = by_id[node[PARENT]]
        assert tick[NAME] == "tick"
        for inner, outer in ((d, launch), (launch, node), (node, tick)):
            assert outer[T0] <= inner[T0] <= inner[T1] <= outer[T1]
            assert inner[THREAD] == outer[THREAD] is not None

    def self_ns(rec):
        open_kids = [k for k in kids.get(rec[SID], ()) if k[THREAD] is not None] if rec[SID] is not None else []
        return rec[T1] - rec[T0] - sum(k[T1] - k[T0] for k in open_kids)

    def tree_of(rec):
        yield rec
        if rec[SID] is not None:
            for k in kids.get(rec[SID], ()):
                if k[THREAD] is not None:
                    yield from tree_of(k)

    ticks = [r for r in recs if r[NAME] == "tick"]
    assert ticks
    for tick in ticks:
        members = list(tree_of(tick))
        assert all(self_ns(m) >= 0 for m in members)
        assert sum(self_ns(m) for m in members) == tick[T1] - tick[T0]
    # the transfers sit inside the index's spans
    puts = [r for r in recs if r[NAME] == "device/put"]
    assert puts and {by_id[r[PARENT]][NAME] for r in puts} <= {"index/scatter", "index/search", "device/dispatch"}


def test_an_idle_tick_records_its_wait_and_nothing_else(monkeypatch):
    monkeypatch.setenv("PATHWAY_TRACE", "on")
    _index_pipeline(_stream(["doc 0", "doc 1"], pause=0.25))
    pw.run(monitoring_level="none")
    recs = _records()
    ticks = [r for r in recs if r[NAME] == "tick"]
    waits = [r for r in recs if r[NAME] == "tick/wait"]
    assert len(waits) > len(ticks) + 5  # ~20 ms ticks over half a second, few of them with rows
    by_id, kids = _tree(recs)
    assert all(kids.get(t[SID]) for t in ticks)  # a tick that left a span did something


# ------------------------------------------------------------------- waits


@pytest.mark.parametrize("queued", [False, True])
def test_oldest_wait_equals_the_deadline_in_a_one_row_flush(queued, monkeypatch):
    """A tail is held only while input is queued behind its tick. A lone row
    of a live run leaves at the frontier of the tick that brought it
    (``idle``); rows that each find another queued behind them, and never fill
    the chunk, are held to the 20 ms deadline and leave together."""
    monkeypatch.setenv("PATHWAY_TRACE", "on")
    subject = _stream(["doc 0"], pause=0.3)

    def push_next(text):
        # from the engine thread, mid-sweep: the next document is queued
        # before this tick's frontier asks
        i = int(text.split()[1])
        if i < 5:
            subject.next(text=f"doc {i + 1}")
        return text

    _index_pipeline(subject, on_text=push_next if queued else None)
    pw.run(monitoring_level="none", autocommit_duration_ms=20)
    launches = [r[ATTRS] for r in _records() if r[NAME] == "microbatch/launch"]
    assert launches
    if queued:
        # the documents' embedder (the static query's launches its one row)
        docs_node = max(launches, key=lambda a: a["pathway.rows"])["pathway.operator.id"]
        launches = [a for a in launches if a["pathway.operator.id"] == docs_node]
        first = launches[0]
        assert first["pathway.reason"] == "deadline" and first["pathway.rows"] >= 2
        # the 20 ms deadline, plus at most the ticks it takes to notice
        assert 20_000_000 <= first["pathway.oldest_wait_ns"] < 120_000_000
        assert sum(a["pathway.rows"] for a in launches) == 6
    else:
        # the document's embedder and the static query's: a row each
        assert {(a["pathway.reason"], a["pathway.rows"]) for a in launches} == {("idle", 1)}
        # under the period: the tick that brought it
        assert all(a["pathway.oldest_wait_ns"] < 20_000_000 for a in launches)


# --------------------------------------------------------------- transfers


@pytest.mark.parametrize("what", ["encoder_launch", "search"])
def test_put_and_fetch_bytes_equal_the_arrays_nbytes(what, monkeypatch):
    tracer = _active_tracer(monkeypatch)
    if what == "encoder_launch":
        from pathway_tpu.ops.encoder import EncoderConfig, JaxSentenceEncoder

        enc = JaxSentenceEncoder(EncoderConfig(n_layers=1, d_model=64, n_heads=2, d_ff=128, vocab_size=512))
        texts = ["hello world", "a much longer sentence with many words here"]
        ids, _mask = enc.tokenizer(texts)
        out = enc.encode_texts(texts)
        up, down = ids.nbytes, out.nbytes
    else:
        from pathway_tpu.ops.knn import BruteForceKnnIndex

        ix = BruteForceKnnIndex(dimension=16, capacity=64)
        for i in range(10):
            ix.add(i, np.random.default_rng(i).standard_normal(16).astype(np.float32))
        ix._flush()
        tracer.buffer._ring.clear()
        q = np.zeros((2, 16), np.float32)
        ix.search(q, k=3)
        up, down = q.nbytes, 2 * 3 * 2 * 4  # scores and ids, packed into one float32 fetch
    recs = tracer.buffer.records()
    puts = [r[ATTRS] for r in recs if r[NAME] == "device/put"]
    fetches = [r[ATTRS] for r in recs if r[NAME] == "device/fetch"]
    assert [a["pathway.bytes"] for a in puts] == [up] and [a["pathway.bytes"] for a in fetches] == [down]
    assert puts[0]["pathway.label"] and fetches[0]["pathway.label"]
    if what == "encoder_launch":
        tok = [r[ATTRS] for r in recs if r[NAME] == "embed/tokenize"][0]
        assert tok["pathway.rows"] == 2 and tok["pathway.padded_len"] == ids.shape[1]
        assert tok["pathway.real_tokens"] == int(np.count_nonzero(ids))
    else:
        search_free = [r for r in recs if r[NAME] == "index/scatter"]
        assert not search_free  # nothing was pending: no empty scatter span


# ---------------------------------------------------------------- no sync


@pytest.mark.parametrize("mode,blocks", [("on", 0), ("full", 1)])
def test_a_traced_warm_call_blocks_only_in_full_mode(mode, blocks, monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("PATHWAY_PROFILE", mode)
    _active_tracer(monkeypatch)  # a sampled tick is open
    f = device.traced_jit(f"test.nosync.{mode}", jax.jit(lambda x: x * x))
    x = jnp.ones((32,))
    f(x)  # cold: blocks to time the compile
    called = []
    monkeypatch.setattr(device, "_block", lambda out: called.append(1))
    f(x)
    assert len(called) == blocks
    assert device.stats().want_split() is (mode == "full")


# ----------------------------------------------------- readable after the run


def test_the_ring_outlives_shutdown_and_reports_dropped(monkeypatch):
    monkeypatch.setenv("PATHWAY_TRACE", "on")
    monkeypatch.setenv("PATHWAY_TRACE_BUFFER", "64")
    class UntilDropped(pw.io.python.ConnectorSubject):
        def run(self):
            for i in range(400):  # a dozen rows fill 64 slots; a loaded host packs more rows a tick
                ring = obs.last_recording()
                if i >= 12 and ring is not None and ring.dropped:
                    break
                self.next(text=f"doc {i}")
                time.sleep(0.03)

    _index_pipeline(UntilDropped())
    pw.run(monitoring_level="none")
    assert obs.current() is None  # shut down
    ring = obs.last_recording()
    assert len(ring.records()) == 64 and ring.dropped > 0
    assert ring.dropped == ring._seq - 64
    obs.shutdown()  # again: the ring stays
    assert obs.last_recording() is ring
    # the next recording replaces it once it retires
    _index_pipeline(_stream(["doc 0"]))
    pw.run(monitoring_level="none")
    assert obs.last_recording() is not ring


def test_a_dropped_span_closes_what_an_exception_left_open():
    tr = Tracer(trace_id="cd" * 16, buffer=SpanBuffer(max_spans=16))
    tick = tr.begin_tick(0)
    outer = tr.begin("sweep/x")
    tr.begin("device/dispatch")  # never ended: its launch raised
    tr.end(outer)
    idle = tr.begin("frontier/y")
    tr.end(idle, keep=False)  # nothing under it: leaves no span
    kept = tr.begin("frontier/z")
    tr.end(tr.begin("microbatch/launch"))
    tr.end(kept, keep=False)  # a child names it: recorded after all
    tr.end_tick(0, tick)
    names = [r[NAME] for r in tr.buffer.records()]
    assert names == ["sweep/x", "microbatch/launch", "frontier/z", "tick"]
    by = {r[NAME]: r for r in tr.buffer.records()}
    assert by["sweep/x"][PARENT] == by["tick"][SID] and by["microbatch/launch"][PARENT] == by["frontier/z"][SID]
