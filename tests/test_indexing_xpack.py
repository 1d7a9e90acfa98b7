"""Tests for stdlib.indexing + the LLM xpack.

Models the reference's xpack tests (``python/pathway/xpacks/llm/tests/``): fake
chat/embedder models, DocumentStore behaviors, index queries against in-process
pipelines (SURVEY §4.4).
"""

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.stdlib.indexing import (
    BruteForceKnnFactory,
    HybridIndexFactory,
    TantivyBM25Factory,
)
from pathway_tpu.xpacks.llm import DocumentStore
from pathway_tpu.xpacks.llm.mocks import FakeChatModel, FakeEmbedder
from pathway_tpu.xpacks.llm.question_answering import (
    AdaptiveRAGQuestionAnswerer,
    BaseRAGQuestionAnswerer,
    answer_with_geometric_rag_strategy,
)
from pathway_tpu.xpacks.llm.rerankers import rerank_topk_filter
from pathway_tpu.xpacks.llm.splitters import (
    NullSplitter,
    RecursiveSplitter,
    TokenCountSplitter,
)
from utils import rows_of


DOCS_MD = '''
    | data
1   | Kafka connector reads topics into tables.
2   | The TPU engine runs matmuls on the MXU systolic array.
3   | Bananas are yellow fruit rich in potassium.
'''


def make_docs():
    return pw.debug.table_from_markdown(DOCS_MD, schema=pw.schema_from_types(data=str))


def retrieve(store, query, k=1, metadata_filter=None, globpattern=None):
    queries = pw.debug.table_from_rows(
        DocumentStore.RetrieveQuerySchema, [(query, k, metadata_filter, globpattern)]
    )
    rows = list(rows_of(store.retrieve_query(queries)))
    assert len(rows) == 1
    result = rows[0][0]
    return result.value if hasattr(result, "value") else result


def test_bm25_document_store_retrieval():
    store = DocumentStore(make_docs(), retriever_factory=TantivyBM25Factory())
    hits = retrieve(store, "kafka topics", k=2)
    assert hits[0]["text"].startswith("Kafka connector")


def test_knn_document_store_retrieval():
    emb = FakeEmbedder(dimension=12)
    store = DocumentStore(make_docs(), retriever_factory=BruteForceKnnFactory(embedder=emb))
    # FakeEmbedder is deterministic per text: querying with an exact document
    # text must retrieve that document first (cos similarity 1)
    hits = retrieve(store, "Bananas are yellow fruit rich in potassium.", k=1)
    assert hits[0]["text"].startswith("Bananas")


def test_hybrid_index_fusion():
    factory = HybridIndexFactory(
        [TantivyBM25Factory(), BruteForceKnnFactory(embedder=FakeEmbedder())]
    )
    store = DocumentStore(make_docs(), retriever_factory=factory)
    hits = retrieve(store, "kafka topics", k=2)
    assert any("Kafka" in h["text"] for h in hits)


def test_metadata_filter_and_glob():
    docs = pw.debug.table_from_rows(
        pw.schema_from_types(data=str, _metadata=dict),
        [
            ("kafka doc one", {"path": "a/one.md", "owner": "x"}),
            ("kafka doc two", {"path": "b/two.txt", "owner": "y"}),
        ],
    )
    store = DocumentStore(docs, retriever_factory=TantivyBM25Factory())
    hits = retrieve(store, "kafka", k=5, globpattern="a/*.md")
    assert [h["metadata"]["path"] for h in hits] == ["a/one.md"]
    hits = retrieve(store, "kafka", k=5, metadata_filter="owner == 'y'")
    assert [h["metadata"]["owner"] for h in hits] == ["y"]


def test_document_store_statistics_and_inputs():
    docs = pw.debug.table_from_rows(
        pw.schema_from_types(data=str, _metadata=dict),
        [("alpha", {"path": "x.md", "modified_at": 100, "seen_at": 200})],
    )
    store = DocumentStore(docs, retriever_factory=TantivyBM25Factory())
    sq = pw.debug.table_from_rows(pw.schema_from_types(), [()])
    stats = list(rows_of(store.statistics_query(sq)))[0][0]
    stats = stats.value if hasattr(stats, "value") else stats
    assert stats["file_count"] == 1 and stats["last_modified"] == 100
    iq = pw.debug.table_from_rows(DocumentStore.InputsQuerySchema, [(None, None)])
    inputs = list(rows_of(store.inputs_query(iq)))[0][0]
    inputs = inputs.value if hasattr(inputs, "value") else inputs
    assert inputs[0]["path"] == "x.md"


def test_index_updates_incrementally():
    """As-of-now: doc additions after a query must not revise old answers, but
    new queries see the new docs."""
    docs = pw.debug.table_from_markdown('''
        | data    | __time__
    1   | alpha doc about kafka | 2
    2   | beta doc about tpu    | 6
    ''')
    store = DocumentStore(docs, retriever_factory=TantivyBM25Factory())
    queries = pw.debug.table_from_markdown('''
        | query | k | metadata_filter | filepath_globpattern | __time__
    1   | tpu | 1 | None | None | 4
    2   | tpu | 1 | None | None | 8
    ''')
    res = store.retrieve_query(queries)
    rows = [r[0].value if hasattr(r[0], "value") else r[0] for r in rows_of(res)]
    empties = [r for r in rows if not r]
    nonempty = [r for r in rows if r]
    assert len(empties) == 1  # early query: tpu doc not yet ingested
    assert len(nonempty) == 1 and "tpu" in nonempty[0][0]["text"]


def test_hybrid_respects_per_query_k():
    factory = HybridIndexFactory(
        [TantivyBM25Factory(), BruteForceKnnFactory(embedder=FakeEmbedder())]
    )
    store = DocumentStore(make_docs(), retriever_factory=factory)
    assert len(retrieve(store, "kafka", k=1)) == 1


def test_malformed_filter_poisons_only_its_query():
    store = DocumentStore(make_docs(), retriever_factory=TantivyBM25Factory())
    queries = pw.debug.table_from_rows(
        DocumentStore.RetrieveQuerySchema,
        [("kafka", 1, "owner == 'unclosed", None), ("kafka", 1, None, None)],
    )
    rows = [r[0].value if hasattr(r[0], "value") else r[0] for r in rows_of(store.retrieve_query(queries))]
    assert sorted(len(r) for r in rows) == [0, 1]  # bad filter → empty, good → hit


def test_data_index_flat_mode():
    store = DocumentStore(make_docs(), retriever_factory=TantivyBM25Factory())
    q = pw.debug.table_from_rows(pw.schema_from_types(query=str), [("kafka",)])
    flat = store.index.query_as_of_now(
        q.query, number_of_matches=2, collapse_rows=False
    ).select(q=pw.left.query, doc=pw.right.text)
    assert list(rows_of(flat)) == [("kafka", "Kafka connector reads topics into tables.")]


def test_index_doc_upsert_not_dropped():
    """A same-tick (-1 old, +1 new) doc update must leave the NEW doc in the
    index regardless of consolidation's row order (code-review regression)."""
    import time as _time

    class Subj(pw.io.python.ConnectorSubject):
        def run(self):
            self.next(data="original kafka doc")
            _time.sleep(0.15)
            self.next(data="updated kafka doc")

        @property
        def _session_type(self):
            return "upsert"

    class DocSchema(pw.Schema):
        data: str = pw.column_definition(primary_key=True)

    # direct node-level check: same-key remove+add in ONE batch, add sorted first
    from pathway_tpu.engine.blocks import DeltaBatch
    from pathway_tpu.stdlib.indexing._engine import BM25Backend, ExternalIndexNode

    node = ExternalIndexNode(BM25Backend, as_of_now=False)
    import numpy as np

    docs = DeltaBatch.from_rows(
        [7, 7],
        [("new kafka text",), ("old kafka text",)],
        ["__item"],
        0,
        diffs=[+1, -1],  # +1 physically before -1: the hazardous order
    )
    docs.data["__meta"] = np.array([None, None], dtype=object)
    node.process([docs, None], 0)
    assert 7 in node.backend.docs and node.backend.docs[7].get("new") == 1


def test_vector_backend_k_zero():
    import numpy as np

    from pathway_tpu.stdlib.indexing._engine import VectorBackend

    b = VectorBackend(dimension=4)
    b.add(1, np.ones(4, np.float32), None)
    assert b.search([np.ones(4, np.float32)], [0], [lambda m: True]) == [[]]


# -- VectorBackend.search fetches what was asked; ten times k only after a
# rejection (ISSUE 33) --------------------------------------------------------

_KNN_DIM = 8


def _lazy_overfetch_backend(n_rows):
    """Integer rows under the dot metric: every score is exact in float32, in
    numpy as on the device, and many of them tie. Capacity 8192, so
    ``_topk_rows`` takes its chunked path as it does at the cell's size."""
    from pathway_tpu.stdlib.indexing._engine import VectorBackend

    rng = np.random.default_rng(33)
    rows = rng.integers(-3, 4, size=(n_rows, _KNN_DIM)).astype(np.float32)
    backend = VectorBackend(dimension=_KNN_DIM, metric="dot", reserved_space=8192)
    keys = [int(key) for key in rng.choice(1 << 40, size=n_rows, replace=False)]
    for key, row in zip(keys, rows):
        backend.add(key, row, {"key": key})
    return backend, keys, rows


def _ranked(keys, rows, query):
    """Every row as (key, score), in the canonical (score desc, key order)
    order of a plain numpy scan."""
    from pathway_tpu.internals.keys import tie_order

    scores = rows @ query
    order = sorted(range(len(keys)), key=lambda i: (-scores[i], tie_order(keys[i])))
    return [(keys[i], float(scores[i])) for i in order]


def _parent_rule(keys, rows, queries, ks, filters):
    """What the parent commit answers: the canonical top ten times kmax
    (rounded up to a power of two), walked through the filter until k are
    accepted."""
    fetch = min(len(keys), 10 * max(ks, default=0))
    fetch = 1 << (fetch - 1).bit_length() if fetch else 0
    return [
        [hit for hit in _ranked(keys, rows, query)[:fetch] if flt({"key": hit[0]})][:k]
        for query, k, flt in zip(queries, ks, filters)
    ]


def _node_filter_none():
    from pathway_tpu.stdlib.indexing._engine import ExternalIndexNode, VectorBackend

    return ExternalIndexNode(lambda: VectorBackend(dimension=_KNN_DIM), as_of_now=True)._filter(None)


def _reject(ranked, positions):
    dropped = {ranked[p] for p in positions}
    return lambda meta: meta["key"] not in dropped


def _accept_only(ranked, positions):
    kept = {ranked[p] for p in positions}
    return lambda meta: meta["key"] in kept


# case: (live rows, the k of each query, a filter of each query built from its
# canonical ranking, the launches [(queries, k)] expected, queries refetched)
_LAZY_OVERFETCH_CASES = {
    "accept_all_lambda": (300, [6, 6], [lambda _r: (lambda _meta: True)] * 2, [(2, 8)], 0),
    "the_node_s_filter_of_none": (300, [6, 6], [lambda _r: _node_filter_none()] * 2, [(2, 8)], 0),
    "rejects_two_of_the_top_k_and_the_margin_fills_it": (
        300, [6], [lambda r: _reject(r, [0, 4])], [(1, 8)], 0),
    "rejects_three_of_the_top_k": (300, [6], [lambda r: _reject(r, [1, 2, 5])], [(1, 8), (1, 64)], 1),
    "rejects_so_much_that_k_stays_unfilled": (
        300, [6], [lambda r: _accept_only(r, [3, 40, 63, 64, 200])], [(1, 8), (1, 64)], 1),
    "short_and_satisfied_queries_with_different_k": (
        300,
        [6, 2, 3],
        [lambda _r: (lambda _meta: True), lambda r: _reject(r, range(0, 8)), lambda r: _reject(r, [0])],
        [(3, 8), (1, 64)],
        1,
    ),
    "fewer_live_rows_than_k": (3, [6], [lambda r: _reject(r, [0])], [(1, 4)], 0),
    "k_zero": (300, [0], [lambda _r: (lambda _meta: True)], [(1, 0)], 0),
    "k_not_a_power_of_two": (300, [5, 3], [lambda _r: (lambda _meta: True)] * 2, [(2, 8)], 0),
    "k_one": (300, [1], [lambda _r: (lambda _meta: True)], [(1, 1)], 0),
}


@pytest.mark.parametrize("case", list(_LAZY_OVERFETCH_CASES))
def test_vector_backend_overfetches_only_after_a_rejection(case, monkeypatch):
    from pathway_tpu import observability as obs

    n_rows, ks, make_filters, launches, refetched = _LAZY_OVERFETCH_CASES[case]
    backend, keys, rows = _lazy_overfetch_backend(n_rows)
    rng = np.random.default_rng(len(case))
    queries = [rng.integers(-3, 4, size=_KNN_DIM).astype(np.float32) for _ in ks]
    filters = [make([key for key, _ in _ranked(keys, rows, q)]) for make, q in zip(make_filters, queries)]

    seen = []
    index_search = backend.index.search
    monkeypatch.setattr(
        backend.index, "search", lambda batch, k: seen.append((len(batch), k)) or index_search(batch, k)
    )
    monkeypatch.setenv("PATHWAY_TRACE", "on")
    tracer = obs.install_from_env()
    try:
        tracer.begin_tick(0)
        obs.device.stats().reset_run()
        got = backend.search(queries, ks, filters)
        spans = [r[5] for r in tracer.buffer.records() if r[0] == "index/search"]
        pad = obs.device.stats().pad["knn.fetch"]
    finally:
        obs.shutdown()

    assert got == _parent_rule(keys, rows, queries, ks, filters)
    assert seen == launches
    asked = sum(min(k, n_rows) for k in ks)
    assert pad[:2] == [asked, sum(q * k for q, k in launches) - asked]
    assert len(spans) == 1  # one span a call, however many launches
    assert spans[0]["pathway.refetched"] == refetched
    assert spans[0]["pathway.fetch"] == launches[0][1] and spans[0]["pathway.queries"] == len(ks)


def test_filter_runtime_error_excludes_doc_only():
    store = DocumentStore(make_docs(), retriever_factory=TantivyBM25Factory())
    # contains(path, 5) parses but raises per doc (int in str) — query must
    # survive with an empty reply, not kill the run
    queries = pw.debug.table_from_rows(
        DocumentStore.RetrieveQuerySchema, [("kafka", 2, "contains(path, 5)", None)]
    )
    rows = [r[0].value if hasattr(r[0], "value") else r[0] for r in rows_of(store.retrieve_query(queries))]
    assert rows == [[]]


def test_batch_udf_row_isolation():
    """One bad row in a batched UDF must not error the whole block."""
    from pathway_tpu.internals.udfs import UDF

    class PickyEmbed(UDF):
        is_batched = True

        def __init__(self):
            def fn(texts):
                if any(t == "bad" for t in texts):
                    raise ValueError("bad input")
                return [len(t) for t in texts]

            super().__init__(_fn=fn, return_type=int)

    t = pw.debug.table_from_rows(pw.schema_from_types(text=str), [("ok",), ("bad",), ("fine",)])
    out = t.select(n=PickyEmbed()(pw.this.text)).remove_errors()
    assert sorted(rows_of(out)) == [(2,), (4,)]


def test_geometric_rag_strategy_grows_context():
    calls = []

    def answer_fn(prompt):
        calls.append(prompt)
        if "MAGIC" in prompt:
            return "found it"
        return "No information found."

    chat = FakeChatModel(answer_fn)
    t = pw.debug.table_from_rows(
        pw.schema_from_types(q=str, docs=list),
        [("find magic", ("doc one", "doc two", "MAGIC doc three", "doc four"))],
    )
    answers = answer_with_geometric_rag_strategy(t.q, t.docs, chat, 1, 2, 3)
    out = list(rows_of(t.select(a=answers)))
    assert out == [("found it",)]
    # 1 doc → no; 2 docs → no; 4 docs → includes MAGIC
    assert len(calls) == 3


def test_splitters():
    null = NullSplitter()
    assert null.func("abc") == [("abc", {})]
    tok = TokenCountSplitter(min_tokens=2, max_tokens=5)
    chunks = tok.func("one two three four five six seven eight nine ten")
    assert len(chunks) >= 2
    assert all(isinstance(c[0], str) for c in chunks)
    rec = RecursiveSplitter(chunk_size=5)
    parts = rec.func("Para one.\n\nPara two is a bit longer here.\n\nPara three.")
    assert len(parts) >= 2


def test_rerank_topk_filter():
    docs, scores = rerank_topk_filter(["a", "b", "c"], [1.0, 3.0, 2.0], k=2)
    assert docs == ("b", "c") and scores == (3.0, 2.0)


def test_cross_encoder_reranker_batched():
    from pathway_tpu.ops.encoder import EncoderConfig
    from pathway_tpu.xpacks.llm.rerankers import CrossEncoderReranker

    rr = CrossEncoderReranker(
        EncoderConfig(vocab_size=128, d_model=32, n_heads=2, n_layers=1, d_ff=64, max_len=16)
    )
    t = pw.debug.table_from_rows(
        pw.schema_from_types(doc=str, query=str),
        [("tpu accelerates matmul", "what is tpu"), ("banana bread", "what is tpu")],
    )
    scored = t.select(score=rr(pw.this.doc, pw.this.query))
    vals = [r[0] for r in rows_of(scored)]
    assert len(vals) == 2 and all(np.isfinite(v) for v in vals)


def test_sentence_transformer_embedder_in_pipeline():
    from pathway_tpu.ops.encoder import EncoderConfig
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    emb = SentenceTransformerEmbedder(
        EncoderConfig(vocab_size=128, d_model=32, n_heads=2, n_layers=1, d_ff=64, max_len=16)
    )
    assert emb.get_embedding_dimension() == 32
    t = pw.debug.table_from_rows(pw.schema_from_types(text=str), [("hello",), ("world",)])
    out = t.select(v=emb(pw.this.text))
    # rows_of normalizes ndarrays to ("ndarray", shape, values)
    vals = [r[0] for r in rows_of(out)]
    assert all(v[1] == (32,) for v in vals)
    np.testing.assert_allclose(
        [np.linalg.norm(v[2]) for v in vals], 1.0, rtol=1e-4
    )


def test_adaptive_rag_answerer_end_to_end():
    store = DocumentStore(make_docs(), retriever_factory=TantivyBM25Factory())
    rag = AdaptiveRAGQuestionAnswerer(
        FakeChatModel(lambda p: "Kafka answer" if "Kafka" in p else "No information found."),
        store,
        n_starting_documents=1,
        factor=2,
        max_iterations=2,
    )
    queries = pw.debug.table_from_rows(
        rag.AnswerQuerySchema, [("how to read kafka", None, None, None)]
    )
    out = list(rows_of(rag.answer_query(queries)))
    assert out == [("Kafka answer",)]


def test_usearch_knn_routes_to_ivf():
    """VERDICT r5 #7: asking for the ANN index by the reference name must
    deliver the ANN backend (IVF-flat), not a silent exact brute-force alias."""
    from pathway_tpu.stdlib.indexing import UsearchKnn, UsearchKnnFactory
    from pathway_tpu.stdlib.indexing.ivf import IvfFlatBackend
    from pathway_tpu.stdlib.indexing.nearest_neighbors import IvfFlatKnn

    t = pw.debug.table_from_rows(pw.schema_from_types(x=int), [(1,)])
    idx = UsearchKnn(t.x, 8, reserved_space=64)  # usearch kwargs still accepted
    assert isinstance(idx, IvfFlatKnn)
    assert isinstance(idx.backend_factory(), IvfFlatBackend)
    assert UsearchKnnFactory._index_cls is UsearchKnn
