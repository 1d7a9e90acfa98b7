"""RAG correctness eval harness (reference ``integration_tests/rag_evals/``).

Scores Adaptive-RAG answers on a fixed QA set over a deterministic corpus:
facts are indexed through the real DocumentStore pipeline (parse → split →
embed → index), questions run through the geometric Adaptive-RAG loop, and
an answer counts as correct when it contains the gold string. The LLM is the
deterministic mock (it can only answer from text actually present in the
retrieved context — so the score measures RETRIEVAL + the adaptive loop, not
model knowledge), and the embedder is a bag-of-hashed-words vectorizer so
similarity is real, not random.

Run: ``python benchmarks/rag_evals.py``. Prints one JSON line with the score;
``tests/test_rag_evals.py`` asserts the quality floor.
"""

from __future__ import annotations

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

CAPITALS = {
    "France": "Paris",
    "Japan": "Tokyo",
    "Brazil": "Brasilia",
    "Kenya": "Nairobi",
    "Canada": "Ottawa",
    "Norway": "Oslo",
    "Egypt": "Cairo",
    "Australia": "Canberra",
    "Peru": "Lima",
    "Mongolia": "Ulaanbaatar",
}

FILLER = [
    f"Regional museum bulletin number {i} discusses pottery, trade routes and "
    f"restoration budgets for the {y} season."
    for i, y in enumerate(range(1990, 2020))
]


def corpus() -> list[str]:
    docs = [
        f"Travel factbook: the capital of {country} is {cap}. The city hosts "
        "the national parliament and the central archives."
        for country, cap in CAPITALS.items()
    ]
    return docs + FILLER


def qa_set() -> list[tuple[str, str]]:
    return [
        (f"What is the capital of {country}?", cap)
        for country, cap in CAPITALS.items()
    ]


def word_embedder(dimension: int = 256):
    """Bag-of-hashed-words unit vectors: real lexical similarity, no model."""
    import pathway_tpu as pw
    from pathway_tpu.internals.udfs import UDF

    class WordEmbedder(UDF):
        is_batched = True

        def __init__(self):
            def embed_batch(texts):
                out = []
                for t in texts:
                    v = np.zeros(dimension, dtype=np.float32)
                    for w in re.findall(r"[a-z0-9]+", str(t).lower()):
                        v[hash(w) % dimension] += 1.0
                    n = np.linalg.norm(v)
                    out.append(v / n if n else v)
                return out

            super().__init__(_fn=embed_batch, return_type=np.ndarray)

        def get_embedding_dimension(self, **kwargs):
            return dimension

        @property
        def dimension(self):
            return dimension

    return WordEmbedder()


def extractive_llm():
    """Mock chat that answers ONLY from the prompt context: finds
    'capital of X is Y' in the provided docs, else the no-info response."""
    from pathway_tpu.xpacks.llm.mocks import FakeChatModel

    def answer(prompt: str) -> str:
        # the question (not a doc) carries the interrogative form
        q = re.search(r"What is the capital of (\w+)\?", prompt)
        if q:
            m = re.search(rf"capital of {q.group(1)} is (\w+)", prompt)
            if m:
                return m.group(1)
        return "No information found."

    return FakeChatModel(answer_fn=answer)


def run(n_starting_documents: int = 2, factor: int = 2, max_iterations: int = 4) -> dict:
    import pathway_tpu as pw
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.question_answering import AdaptiveRAGQuestionAnswerer
    from pathway_tpu.xpacks.llm.splitters import NullSplitter

    G.clear()
    docs_table = pw.debug.table_from_rows(
        pw.schema_from_types(data=bytes, _metadata=dict),
        [(d.encode(), {"path": f"doc{i}"}) for i, d in enumerate(corpus())],
    )
    store = DocumentStore(
        docs_table,
        retriever_factory=BruteForceKnnFactory(embedder=word_embedder()),
        splitter=NullSplitter(),
    )
    rag = AdaptiveRAGQuestionAnswerer(
        extractive_llm(),
        store,
        n_starting_documents=n_starting_documents,
        factor=factor,
        max_iterations=max_iterations,
    )
    qa = qa_set()
    queries = pw.debug.table_from_rows(
        rag.AnswerQuerySchema, [(q, None, None, None) for q, _ in qa]
    )
    res = rag.answer_query(queries)
    paired = queries.select(q=pw.this.prompt)
    paired = paired.with_columns(a=res.with_universe_of(paired).result)
    from tests.utils import rows_of

    got = dict(list(rows_of(paired)))  # rows_of yields (q, a) value tuples
    gold = dict(qa)
    correct = sum(
        1
        for q, cap in gold.items()
        if got.get(q) is not None and cap.lower() in str(got[q]).lower()
    )
    return {
        "metric": "adaptive-rag answer accuracy (fixed QA set, mock LLM)",
        "value": round(correct / len(gold), 3),
        "unit": "accuracy",
        "n_questions": len(gold),
        "n_docs": len(corpus()),
        "answered": sum(1 for a in got.values() if a is not None),
    }


if __name__ == "__main__":
    print(json.dumps(run()))
