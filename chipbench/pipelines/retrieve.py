"""SentenceTransformerEmbedder -> DocumentStore(BruteForceKnnFactory) ->
DocumentStoreServer ``/v1/retrieve``: the question-answering template's
retrieval door."""

from chipbench.pipelines._store import store_server


def build(ctx) -> dict:
    _store, routes = store_server(ctx)
    return routes
