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


def _groupby_emissions(events, make_reducer, monkeypatch, reference: bool):
    from pathway_tpu.debug import _capture
    from pathway_tpu.engine import operators as ops
    from pathway_tpu.engine import reducers_impl as ri
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.io.python import _StaticStreamSubject, read_subject

    G.clear()
    emitted = []
    process = ops.GroupByNode.process

    def recording(self, inputs, time):
        out = process(self, inputs, time)
        for b in out:
            emitted.append(
                (time, b.keys.tolist(), b.diffs.tolist(), [repr(row) for _, _, row in b.rows()])
            )
        return out

    with monkeypatch.context() as m:
        m.setattr(ops.GroupByNode, "process", recording)
        if reference:
            m.setattr(ri.MultisetReducer, "update", _per_row_update, raising=False)
            m.setattr(ri.MultisetReducer, "block_rows", ri.ReducerImpl.block_rows)
            m.setattr(ri.MultisetReducer, "fold_rows", ri.ReducerImpl.fold_rows)
        t = read_subject(
            _StaticStreamSubject(events, ["g", "v"]),
            schema=pw.schema_from_types(g=int, v=pw.internals.dtype.ANY),
        )
        r = t.groupby(t.g).reduce(t.g, n=pw.reducers.count(), out=make_reducer(t))
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
