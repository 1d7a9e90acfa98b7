"""Reducer coverage incl. retractions (reference: tests/test_reducers.py +
engine/reduce.rs semantics)."""

import json

import numpy as np
import pytest

import pathway_tpu as pw
from tests.utils import assert_rows, assert_stream_consistent, deltas_of, rows_of


def vals():
    return pw.debug.table_from_markdown(
        """
        g | v
        a | 3
        a | 1
        a | 2
        b | 5
        """
    )


def test_basic_reducers():
    r = vals().groupby(pw.this.g).reduce(
        pw.this.g,
        cnt=pw.reducers.count(),
        s=pw.reducers.sum(pw.this.v),
        mn=pw.reducers.min(pw.this.v),
        mx=pw.reducers.max(pw.this.v),
        av=pw.reducers.avg(pw.this.v),
    )
    assert_rows(r, [("a", 3, 6, 1, 3, 2.0), ("b", 1, 5, 5, 5, 5.0)])


def test_tuple_reducers():
    r = vals().groupby(pw.this.g).reduce(
        pw.this.g,
        st=pw.reducers.sorted_tuple(pw.this.v),
        nd=pw.reducers.ndarray(pw.this.v),
    )
    rows = {row[0]: row for row in rows_of(r)}
    assert rows["a"][1] == (1, 2, 3)
    assert rows["b"][1] == (5,)


def test_unique_and_any():
    t = pw.debug.table_from_markdown(
        """
        g | v
        a | 7
        a | 7
        b | 1
        b | 2
        """
    )
    r = t.groupby(pw.this.g).reduce(pw.this.g, u=pw.reducers.any(pw.this.v))
    rows = {row[0]: row[1] for row in rows_of(r)}
    assert rows["a"] == 7
    assert rows["b"] in (1, 2)

    from pathway_tpu.internals.errors import ERROR

    ru = t.groupby(pw.this.g).reduce(pw.this.g, u=pw.reducers.unique(pw.this.v))
    rows = {row[0]: row[1] for row in rows_of(ru)}
    assert rows["a"] == 7
    assert rows["b"] is ERROR


def test_argmin_argmax():
    t = vals().with_id_from(pw.this.g, pw.this.v)
    r = t.groupby(pw.this.g).reduce(
        pw.this.g, lo=pw.reducers.argmin(pw.this.v), hi=pw.reducers.argmax(pw.this.v)
    )
    looked = r.select(pw.this.g, lo_v=t.ix(r.lo).v, hi_v=t.ix(r.hi).v)
    assert_rows(looked, [("a", 1, 3), ("b", 5, 5)])


def test_earliest_latest_with_stream():
    t = pw.debug.table_from_markdown(
        """
        g | v | __time__
        a | 1 | 2
        a | 2 | 4
        a | 3 | 6
        """
    )
    r = t.groupby(pw.this.g).reduce(
        pw.this.g,
        first=pw.reducers.earliest(pw.this.v),
        last=pw.reducers.latest(pw.this.v),
    )
    assert_rows(r, [("a", 1, 3)])


def test_incremental_updates_emit_retractions():
    t = pw.debug.table_from_markdown(
        """
        g | v | __time__ | __diff__
        a | 1 | 2        | 1
        a | 2 | 4        | 1
        a | 1 | 6        | -1
        """
    )
    r = t.groupby(pw.this.g).reduce(pw.this.g, s=pw.reducers.sum(pw.this.v))
    assert_stream_consistent(r)
    deltas = deltas_of(r)
    # final state: sum=2; stream passed through 1 -> 3 -> 2
    assert_rows(r, [("a", 2)])
    inserted = [row for (_, _, d, row) in deltas if d > 0]
    assert ("a", 1) in inserted and ("a", 3) in inserted and ("a", 2) in inserted


def test_group_disappears_on_full_retraction():
    t = pw.debug.table_from_markdown(
        """
        g | v | __time__ | __diff__
        a | 1 | 2        | 1
        a | 1 | 4        | -1
        b | 7 | 4        | 1
        """
    )
    r = t.groupby(pw.this.g).reduce(pw.this.g, n=pw.reducers.count())
    assert_rows(r, [("b", 1)])


def test_stateful_single():
    def accumulate(state, value):
        return (state or 0) + value

    reducer = pw.reducers.stateful_single(accumulate)
    r = vals().groupby(pw.this.g).reduce(pw.this.g, s=reducer(pw.this.v))
    assert_rows(r, [("a", 6), ("b", 5)])


def test_udf_reducer():
    class StdDevAcc(pw.BaseCustomAccumulator):
        def __init__(self, cnt, s, s2):
            self.cnt, self.s, self.s2 = cnt, s, s2

        @classmethod
        def from_row(cls, row):
            (v,) = row
            return cls(1, v, v * v)

        def update(self, other):
            self.cnt += other.cnt
            self.s += other.s
            self.s2 += other.s2

        def retract(self, other):
            self.cnt -= other.cnt
            self.s -= other.s
            self.s2 -= other.s2

        def compute_result(self) -> float:
            mean = self.s / self.cnt
            return self.s2 / self.cnt - mean * mean

    stddev = pw.reducers.udf_reducer(StdDevAcc)
    t = pw.debug.table_from_markdown(
        """
        g | v
        a | 2
        a | 4
        """
    )
    r = t.groupby(pw.this.g).reduce(pw.this.g, var=stddev(pw.this.v))
    rows = list(rows_of(r))
    assert rows[0][1] == pytest.approx(1.0)


def test_expression_over_reducers():
    r = vals().groupby(pw.this.g).reduce(
        pw.this.g,
        spread=pw.reducers.max(pw.this.v) - pw.reducers.min(pw.this.v),
    )
    assert_rows(r, [("a", 2), ("b", 0)])


def test_global_reduce():
    r = vals().reduce(n=pw.reducers.count(), s=pw.reducers.sum(pw.this.v))
    assert_rows(r, [(4, 11)])


# ------------------------------------------------------------------------------------
# PR 29: a group's multiset reducers fold one tick's rows through ``block_rows`` /
# ``fold_rows``. The reference is the per-row ``update`` as it stood, kept here:
# every value tuple built and encoded row by row.


def _per_row_update(self, state, values, diff, time, seq):
    from pathway_tpu.internals.keys import _canonical_bytes

    ck = _canonical_bytes(values)
    ent = state.items.get(ck)
    if ent is None:
        ent = [values, 0, (time, seq)]
        state.items[ck] = ent
    ent[1] += diff
    if ent[1] == 0:
        del state.items[ck]
    state.total += diff
    return state


_NUMBERS = [-0.0, 0.0, 1, 1.0, 2.5, -3, 2.5, 7]
_ANYTHING = _NUMBERS + [None, None, "s", "t", True]

_DIFFERENTIAL = {
    "min": (lambda t: pw.reducers.min(t.v), _NUMBERS),
    "max": (lambda t: pw.reducers.max(t.v), _NUMBERS),
    "argmin": (lambda t: pw.reducers.argmin(t.v), _NUMBERS),
    "argmax": (lambda t: pw.reducers.argmax(t.v), _NUMBERS),
    "unique": (lambda t: pw.reducers.unique(t.v), _ANYTHING),
    "any": (lambda t: pw.reducers.any(t.v), _ANYTHING),
    "tuple": (lambda t: pw.reducers.tuple(t.v), _ANYTHING),
    "tuple_skip_nones": (lambda t: pw.reducers.tuple(t.v, skip_nones=True), _ANYTHING),
    "sorted_tuple": (lambda t: pw.reducers.sorted_tuple(t.v), _NUMBERS),
    "earliest": (lambda t: pw.reducers.earliest(t.v), _ANYTHING),
    "latest": (lambda t: pw.reducers.latest(t.v), _ANYTHING),
}


def _churn_events(pool, n_events, tick_rows, seed):
    """Random inserts and retractions of live rows over three groups, cut into
    ticks of ``tick_rows``; half way, group 0 empties and then refills."""
    rng = np.random.default_rng(seed)
    live: dict[int, tuple] = {}
    events = []
    next_key = 1

    def emit(key, row, diff):
        events.append((len(events) // tick_rows, key, row, diff))

    while len(events) < n_events:
        if len(events) == n_events // 2:
            for key in [k for k, row in live.items() if row[0] == 0]:
                emit(key, live.pop(key), -1)
        if live and rng.random() < 0.35:
            key = list(live)[int(rng.integers(len(live)))]
            emit(key, live.pop(key), -1)
        else:
            row = (int(rng.integers(3)), pool[int(rng.integers(len(pool)))])
            live[next_key] = row
            emit(next_key, row, 1)
            next_key += 1
    return events


def _per_row_reference(m):
    from pathway_tpu.engine import reducers_impl as ri

    m.setattr(ri.MultisetReducer, "update", _per_row_update, raising=False)
    m.setattr(ri.MultisetReducer, "block_rows", ri.ReducerImpl.block_rows)
    m.setattr(ri.MultisetReducer, "fold_rows", ri.ReducerImpl.fold_rows)


def _groupby_emissions(
    events, make_reducer, monkeypatch, reference: bool, *,
    patch=_per_row_reference, sort_by=None, before_tick=None,
):
    """What the groupby emits tick by tick and the final table. ``reference``
    runs it under ``patch``; rows carry a third column ``s`` when ``sort_by``
    names it; ``before_tick(node, time)`` runs ahead of each ``process``."""
    from pathway_tpu.debug import _capture
    from pathway_tpu.engine import operators as ops
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.io.python import _StaticStreamSubject, read_subject

    G.clear()
    emitted = []
    process = ops.GroupByNode.process
    types = {"g": int, "v": pw.internals.dtype.ANY, "s": int}
    columns = ["g", "v", "s"][: len(events[0][2])]

    def recording(self, inputs, time):
        if before_tick is not None:
            before_tick(self, time)
        out = process(self, inputs, time)
        for b in out:
            emitted.append(
                (time, b.keys.tolist(), b.diffs.tolist(), [repr(row) for _, _, row in b.rows()])
            )
        return out

    with monkeypatch.context() as m:
        m.setattr(ops.GroupByNode, "process", recording)
        if reference:
            patch(m)
        t = read_subject(
            _StaticStreamSubject(events, columns),
            schema=pw.schema_from_types(**{c: types[c] for c in columns}),
        )
        grouped = t.groupby(t.g, sort_by=t[sort_by]) if sort_by else t.groupby(t.g)
        r = grouped.reduce(t.g, n=pw.reducers.count(), out=make_reducer(t))
        final = sorted((k, repr(row)) for k, row in _capture(r).rows.items())
    return emitted, final


@pytest.mark.parametrize("tick_rows,n_events", [(1, 240), (7, 900), (4096, 9000)])
@pytest.mark.parametrize("reducer", list(_DIFFERENTIAL))
def test_block_fold_emits_what_the_per_row_loop_emits(reducer, tick_rows, n_events, monkeypatch):
    make_reducer, pool = _DIFFERENTIAL[reducer]
    events = _churn_events(pool, n_events, tick_rows, seed=29 + tick_rows)
    got, got_final = _groupby_emissions(events, make_reducer, monkeypatch, reference=False)
    want, want_final = _groupby_emissions(events, make_reducer, monkeypatch, reference=True)
    assert len(got) >= min(3, n_events // tick_rows)
    assert got == want
    assert got_final == want_final


# ------------------------------------------------------------------------------------
# PR 31: the ordered reducers (``tuple``, ``ndarray``) keep their sorted order from
# one extract to the next and merge a tick's new entries into it. The reference is
# the extract as it stood, kept here: the whole group sorted on every extract.


def _full_sort_tuple_extract(self, state):
    if self.with_sort_key:
        entries = sorted(state.items.values(), key=lambda e: (e[0][1], e[2]))
    else:
        entries = sorted(state.items.values(), key=lambda e: e[2])
    out = []
    for e in entries:
        v = e[0][0]
        if self.skip_nones and v is None:
            continue
        out.extend([v] * max(e[1], 0))
    return tuple(out)


def _full_sort_ndarray_extract(self, state):
    entries = sorted(state.items.values(), key=lambda e: (e[0][1], e[2]))
    vals = []
    for e in entries:
        vals.extend([e[0][0]] * max(e[1], 0))
    return np.asarray(vals)


def _full_sort_reference(m):
    from pathway_tpu.engine import reducers_impl as ri

    m.setattr(ri.TupleReducer, "extract", _full_sort_tuple_extract)
    m.setattr(ri.NdarrayReducer, "extract", _full_sort_ndarray_extract)


_ORDERED = {
    "tuple": (lambda t: pw.reducers.tuple(t.v), _ANYTHING),
    "tuple_skip_nones": (lambda t: pw.reducers.tuple(t.v, skip_nones=True), _ANYTHING),
    "ndarray": (lambda t: pw.reducers.ndarray(t.v), _NUMBERS),
}


def _order_spans(monkeypatch):
    """The names of the ``reduce/order`` spans the reducers ask for, in a list
    that fills as they do (the tracer is off: nothing is recorded)."""
    from pathway_tpu.engine import reducers_impl as ri

    names = []

    def begin(name):
        if name.startswith("reduce/order"):
            names.append(name)

    monkeypatch.setattr(ri._obs, "begin", begin)
    return names


def _with_tying_sort_key(events):
    # a key in 0..2: most entries of a group tie, so arrival decides among them
    return [(tick, key, (*row, key % 3), diff) for tick, key, row, diff in events]


@pytest.mark.parametrize("tick_rows,n_events", [(1, 240), (7, 900), (4096, 9000)])
@pytest.mark.parametrize("sort_by", [None, "s"], ids=["by_row_id", "by_tying_key"])
@pytest.mark.parametrize("reducer", list(_ORDERED))
def test_kept_order_emits_what_the_full_sort_emits(reducer, sort_by, tick_rows, n_events, monkeypatch):
    make_reducer, pool = _ORDERED[reducer]
    events = _churn_events(pool, n_events, tick_rows, seed=31 + tick_rows)
    if sort_by:
        events = _with_tying_sort_key(events)
    spans = _order_spans(monkeypatch)
    got, got_final = _groupby_emissions(events, make_reducer, monkeypatch, reference=False, sort_by=sort_by)
    merges = spans.count("reduce/order{merge}")
    want, want_final = _groupby_emissions(
        events, make_reducer, monkeypatch, reference=True, patch=_full_sort_reference, sort_by=sort_by
    )
    assert len(got) >= min(3, n_events // tick_rows)
    assert got == want
    assert got_final == want_final
    # every extract of a group but its first merged into the kept order
    assert merges >= 2 and spans == ["reduce/order{merge}"] * merges


def _pickle_mid_stream(node, time):
    import pickle

    if time % 5 == 3:
        node.restore_state(pickle.loads(pickle.dumps(node.snapshot_state())))


def _forget_order(node, time):
    if time % 4 == 2:
        for st in node.state.values():
            for acc in st["acc"]:
                if hasattr(acc, "order"):
                    acc.order = None


@pytest.mark.parametrize("case", ["pickled_mid_stream", "order_forgotten", "untracked_fold"])
def test_kept_order_is_rebuilt_when_it_is_not_there(case, monkeypatch):
    """A state restored from a snapshot, a state handed over with no order, and
    a state folded by code that tracks nothing (PR 29's per-row reference) take
    the full sort again and emit the same."""
    make_reducer, pool = _ORDERED["tuple"]
    events = _with_tying_sort_key(_churn_events(pool, 900, 7, seed=31))
    spans = _order_spans(monkeypatch)
    if case == "untracked_fold":
        got, got_final = _groupby_emissions(events, make_reducer, monkeypatch, reference=True, sort_by="s")
        assert spans and set(spans) == {"reduce/order{sort}"}
    else:
        hook = _pickle_mid_stream if case == "pickled_mid_stream" else _forget_order
        got, got_final = _groupby_emissions(
            events, make_reducer, monkeypatch, reference=False, sort_by="s", before_tick=hook
        )
        # what follows a state with no order is a first extract: no span, then merges
        assert spans and set(spans) == {"reduce/order{merge}"}
    want, want_final = _groupby_emissions(
        events, make_reducer, monkeypatch, reference=True, patch=_full_sort_reference, sort_by="s"
    )
    assert got == want
    assert got_final == want_final


def test_multiset_state_pickles_as_it_always_did(monkeypatch):
    """The kept order is not in a snapshot, and a snapshot written by the class
    as it stood before PR 31 loads."""
    import pickle

    from pathway_tpu.engine import reducers_impl as ri

    class _MultisetState:  # the class as it stood
        __slots__ = ("items", "total")

        def __init__(self):
            self.items = {}
            self.total = 0

    _MultisetState.__module__ = ri.__name__
    _MultisetState.__qualname__ = "_MultisetState"
    old = _MultisetState()
    old.items[b"k"] = [(7, np.uint64(3)), 1, (2, 0)]
    old.total = 1
    with monkeypatch.context() as m:
        m.setattr(ri, "_MultisetState", _MultisetState)
        written_before = pickle.dumps(old)

    reducer = ri.TupleReducer(with_sort_key=True)
    state = pickle.loads(written_before)
    assert type(state) is ri._MultisetState and state.order is None and state.fresh is None
    assert reducer.extract(state) == (7,)
    assert state.order is not None
    assert pickle.dumps(state) == written_before


class _CountedKey:
    """A sort key that counts the comparisons a sort makes of it."""

    calls = 0

    def __init__(self, k):
        self.k = k

    def __lt__(self, other):
        _CountedKey.calls += 1
        return self.k < other.k

    def __repr__(self):
        return f"_CountedKey({self.k})"


def test_a_later_extract_merges_and_does_not_sort_the_group_again():
    """Counts of work, no timing: a group of 50,000 entries taking 500 new rows
    a tick compares about N + k log k keys an extract, not N log N."""
    import math

    from pathway_tpu.engine import reducers_impl as ri

    n, k, ticks = 50_000, 500, 4
    rng = np.random.default_rng(31)
    keys = rng.permutation(n + k * ticks)
    reducer = ri.TupleReducer(with_sort_key=True)
    state = reducer.make()

    def tick(lo, hi, time):
        values = np.empty(hi - lo, dtype=object)
        sort_keys = np.empty(hi - lo, dtype=object)
        for j, i in enumerate(range(lo, hi)):
            values[j], sort_keys[j] = i, _CountedKey(int(keys[i]))
        block = reducer.block_rows([values, sort_keys], np.ones(hi - lo, dtype=np.int64))
        reducer.fold_rows(state, block, list(range(hi - lo)), time, lo)
        _CountedKey.calls = 0
        out = reducer.extract(state)
        return out, _CountedKey.calls

    out, first = tick(0, n, 0)
    assert first > n * math.log2(n) / 2  # the first extract is the full sort
    for t in range(ticks):
        out, calls = tick(n + t * k, n + (t + 1) * k, t + 1)
        assert calls < 3 * (n + k * math.log2(k)), (t, calls)
        assert calls < n * math.log2(n) / 4
    assert list(out) == np.argsort(keys, kind="stable").tolist()


def test_a_first_extract_records_no_span_and_a_later_one_says_what_it_did(monkeypatch):
    from pathway_tpu import observability as obs
    from pathway_tpu.engine import reducers_impl as ri

    tracer = obs.Tracer(trace_id="0" * 32)
    monkeypatch.setattr(obs, "_tracer", tracer)
    tok = tracer.begin_tick(0)
    reducer = ri.TupleReducer(with_sort_key=True)
    state = reducer.make()

    def fold(values, seq):
        arrays = [np.array(values, dtype=object), np.array([np.uint64(100 - v) for v in values], dtype=object)]
        block = reducer.block_rows(arrays, np.ones(len(values), dtype=np.int64))
        reducer.fold_rows(state, block, list(range(len(values))), 0, seq)

    fold([1, 2, 3, 4, 5, 6], 0)
    assert reducer.extract(state) == (6, 5, 4, 3, 2, 1)
    assert tracer.buffer.records() == []  # a group's first extract: nothing

    fold([7, 8], 6)
    assert reducer.extract(state) == (8, 7, 6, 5, 4, 3, 2, 1)
    state.fresh = None  # as a fold that tracks nothing leaves it
    assert reducer.extract(state) == (8, 7, 6, 5, 4, 3, 2, 1)
    tracer.end_tick(0, tok)
    spans = [(r[0], r[5], r[7] is not None) for r in tracer.buffer.records() if r[0] != "tick"]
    assert spans == [
        ("reduce/order{merge}", {"pathway.entries": 8, "pathway.fresh": 2}, True),
        ("reduce/order{sort}", {"pathway.entries": 8}, True),
    ]


def test_values_are_read_off_the_order_only_while_every_count_is_one():
    from pathway_tpu.engine import reducers_impl as ri

    reducer = ri.TupleReducer(with_sort_key=True)
    state = reducer.make()

    def fold(rows, seq):  # rows of (value, sort key, diff)
        arrays = [np.array([r[0] for r in rows], dtype=object), np.array([r[1] for r in rows], dtype=object)]
        block = reducer.block_rows(arrays, np.array([r[2] for r in rows], dtype=np.int64))
        reducer.fold_rows(state, block, list(range(len(rows))), 0, seq)
        return reducer.extract(state)

    assert fold([("a", 3, 1), ("b", 1, 1)], 0) == ("b", "a") and state.plain
    assert fold([("c", 2, 1)], 2) == ("b", "c", "a") and state.plain  # created, each once: no entry visited
    assert fold([("c", 2, 1), ("d", 0, 1)], 3) == ("d", "b", "c", "c", "a") and not state.plain
    assert fold([("c", 2, -1)], 5) == ("d", "b", "c", "a") and state.plain  # the walk found every count at one
    assert fold([("b", 1, -1), ("e", 9, 2)], 6) == ("d", "c", "a", "e", "e") and not state.plain
    assert [t[-2] for t in state.order] == ["d", "c", "a", "e"]  # the dropped entry left the order


# The store's own graph (two flattens, the whole-table reducers behind
# /v1/statistics and /v1/inputs): its answers, tick by tick, against what the
# operators of the commit before PR 29 gave, recorded in tests/data.


def _store_answers(n_workers):
    """The DocumentStore graph over a timed stream of documents (inserts,
    a retraction, an update; several chunks a document) with a stub retriever:
    ``chunked_docs`` and the answers of ``/v1/statistics`` and ``/v1/inputs``,
    with the tick each lands in."""
    from pathway_tpu.engine import operators as ops
    from pathway_tpu.internals.logical import LogicalNode
    from pathway_tpu.internals.run import make_runtime
    from pathway_tpu.io.python import _StaticStreamSubject, read_subject
    from pathway_tpu.stdlib.indexing.retrievers import AbstractRetrieverFactory
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.splitters import TokenCountSplitter

    class NoIndex(AbstractRetrieverFactory):
        def build_index(self, data_column, data_table, metadata_column=None):
            return None

    def doc(i, version=0):
        words = " ".join(f"w{i}.{version}.{j}" for j in range(3 + (i * 5) % 11))
        md = {
            "path": f"dir{i % 3}/doc{i}.md",
            "modified_at": 500 + (i * 7) % 13 + version,
            "seen_at": 1000 + i,
        }
        return (words, md)

    events = [(i // 4, i + 1, doc(i), 1) for i in range(12)]
    events += [(6, 4, doc(3), -1)]  # a document goes
    events += [(7, 9, doc(8), -1), (7, 9, doc(8, version=1), 1)]  # one is rewritten
    events += [(8, 13 + i, doc(12 + i), 1) for i in range(3)]
    events.sort(key=lambda e: e[0])
    docs = read_subject(
        _StaticStreamSubject(events, ["data", "_metadata"]),
        schema=pw.schema_from_types(data=str, _metadata=dict),
    )
    store = DocumentStore(
        docs, retriever_factory=NoIndex(), splitter=TokenCountSplitter(min_tokens=2, max_tokens=4)
    )
    asked = [(2, 1, (None, None), 1), (7, 2, (None, "dir1/*"), 1), (9, 3, ("contains(path, 'doc1')", None), 1)]
    queries = read_subject(
        _StaticStreamSubject(asked, ["metadata_filter", "filepath_globpattern"]),
        schema=DocumentStore.InputsQuerySchema,
    )
    tables = {
        "chunked_docs": store.chunked_docs,
        "statistics": store.statistics_query(queries.select()),
        "inputs": store.inputs_query(queries),
    }
    captures = {}

    def capture(name, table):
        def factory():
            captures[name] = ops.CaptureNode(table.column_names())
            return captures[name]

        return LogicalNode(factory, [table._node], name=f"capture_{name}")

    make_runtime(n_workers=n_workers, autocommit_duration_ms=5).run(
        [capture(name, table) for name, table in tables.items()]
    )
    answers = {
        name: [
            [t, f"{key:016x}", diff, json.loads(json.dumps(list(row), default=pw.Json.dumps))]
            for t, key, diff, row in node.deltas
        ]
        for name, node in captures.items()
    }
    if n_workers > 1:
        # the workers' blocks of one tick reach the capture in any order, a
        # correction among them: net each tick, in a canonical order
        for name, deltas in answers.items():
            net: dict[str, int] = {}
            for t, key, diff, row in deltas:
                at = json.dumps([t, key, row])
                net[at] = net.get(at, 0) + diff
            answers[name] = sorted([*json.loads(at), d] for at, d in net.items() if d)
    return answers


@pytest.mark.parametrize("n_workers", [1, 2], ids=["thread", "sharded_2_workers"])
def test_document_store_answers_are_the_recorded_ones(n_workers):
    import os

    path = os.path.join(os.path.dirname(__file__), "data", "store_block_ops.json")
    with open(path) as f:
        recorded = json.load(f)[str(n_workers)]
    answers = _store_answers(n_workers)
    assert sorted(answers) == sorted(recorded)
    for name in recorded:
        assert answers[name] == recorded[name], name
