"""The question-answering template with a language model named by its
configuration: what ``pipelines/answer.py`` builds (docs connector ->
DocumentStore -> ``BaseRAGQuestionAnswerer(llm=JaxChat(...), search_topk)``
behind ``QARestServer``), with the chat's weights from the reference module
the ``.llm.json`` names under ``reference`` (``reference_module``: the
interface of ``reference_kimi_k2``). A further language model brings a
reference and an arithmetic file, and no pipeline. Every executable the chat
can ask for is compiled here, as set-up."""

from __future__ import annotations

import importlib
import os

import pathway_tpu as pw

from chipbench.flops_decoder import llm_config
from chipbench.pipelines._store import PipedDocs, free_port

#: the chat of the last build: the comparison runs the program's own
#: executables on the sampled prompts after the window
BUILT: list = []


def reference_module(llm: dict):
    """The plain reference the language model's configuration names."""
    return importlib.import_module(llm["reference"])


def build_chat(config: dict, key):
    """The deployment's chat over the benchmark's weights for ``key``."""
    import jax.numpy as jnp

    from pathway_tpu.ops.decoder import DecoderConfig
    from pathway_tpu.xpacks.llm.llms import JaxChat

    llm = llm_config(config)
    return JaxChat(
        DecoderConfig.from_hf(llm, getattr(jnp, config["compute_dtype"])),
        params=reference_module(llm).program_params(key, llm, config["compute_dtype"]),
        max_tokens=config["max_tokens"], cache_rows=config["cache_rows"], cache_len=config["cache_len"],
    )


def build(ctx) -> dict:
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.question_answering import BaseRAGQuestionAnswerer

    c = ctx.config
    # the deployment's launch cap for the embedder, read when the store's graph is built
    os.environ["PATHWAY_MICROBATCH_MAX_BATCH"] = str(c["embed_max_batch"])
    eparams = ctx.retriever_factory.embedder._encoder.params
    chat = build_chat(c, reference_module(llm_config(c)).llm_key(eparams))
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
