"""What both served pipelines share: the live docs source and the store, as
``chip_smoke.py`` builds them (recipe copied, nothing imported from it)."""

from __future__ import annotations

import socket

import pathway_tpu as pw


class PipedDocs(pw.io.python.ConnectorSubject):
    """The store's docs connector: every block of texts the load generator
    sends down its pipe becomes one ``next_batch``. ``None`` ends the source."""

    def __init__(self, blocks):  # a queue.Queue of lists of texts; None ends the source
        super().__init__()
        self.blocks = blocks

    def run(self) -> None:
        while True:
            texts = self.blocks.get()
            if texts is None:
                return
            self.next_batch([{"data": t} for t in texts])

    def on_stop(self) -> None:
        self.blocks.put(None)


def free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def store_server(ctx) -> tuple:
    """docs connector -> DocumentStore(BruteForceKnnFactory) ->
    DocumentStoreServer; returns (store, routes so far)."""
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.servers import DocumentStoreServer

    docs = pw.io.python.read(PipedDocs(ctx.doc_blocks), schema=pw.schema_from_types(data=str))
    store = DocumentStore(docs, retriever_factory=ctx.retriever_factory)
    port = free_port()
    DocumentStoreServer("127.0.0.1", port, store)
    return store, {
        "retrieve": [port, "/v1/retrieve"],
        "statistics": [port, "/v1/statistics"],
    }
