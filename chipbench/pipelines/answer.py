"""The question-answering template: docs connector -> DocumentStore
(BruteForceKnnFactory over the embedder) -> ``BaseRAGQuestionAnswerer(llm=
JaxChat(...), search_topk)`` behind ``QARestServer``: ``/v2/answer`` beside
``/v1/retrieve`` and ``/v1/statistics``. The chat's weights are the
benchmark's own (``reference_kimi_k2.py``), in the configuration's compute
type; every executable it can ask for is compiled here, as set-up."""

from __future__ import annotations

import os

import pathway_tpu as pw

from chipbench import reference_kimi_k2 as K
from chipbench.flops_decoder import llm_config
from chipbench.pipelines._store import PipedDocs, free_port

#: the chat of the last build: the comparison runs the program's own
#: executables on the sampled prompts after the window
BUILT: list = []


def build(ctx) -> dict:
    import jax.numpy as jnp

    from pathway_tpu.ops.decoder import DecoderConfig
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.llms import JaxChat
    from pathway_tpu.xpacks.llm.question_answering import BaseRAGQuestionAnswerer

    c, llm = ctx.config, llm_config(ctx.config)
    # the deployment's launch cap for the embedder, read when the store's graph is built
    os.environ["PATHWAY_MICROBATCH_MAX_BATCH"] = str(c["embed_max_batch"])
    eparams = ctx.retriever_factory.embedder._encoder.params
    chat = JaxChat(
        DecoderConfig.from_hf(llm, getattr(jnp, c["compute_dtype"])),
        params=K.program_params(K.llm_key(eparams), llm, c["compute_dtype"]),
        max_tokens=c["max_tokens"], cache_rows=c["cache_rows"], cache_len=c["cache_len"],
    )
    chat.warm()
    BUILT[:] = [chat]
    docs = pw.io.python.read(PipedDocs(ctx.doc_blocks), schema=pw.schema_from_types(data=str))
    store = DocumentStore(docs, retriever_factory=ctx.retriever_factory)
    rag = BaseRAGQuestionAnswerer(llm=chat, indexer=store, search_topk=c["search_topk"])
    port = free_port()
    rag.build_server("127.0.0.1", port)
    return {
        "retrieve": [port, "/v1/retrieve"],
        "statistics": [port, "/v1/statistics"],
        "answer": [port, "/v2/answer"],
    }
