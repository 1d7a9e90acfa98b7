"""Open-loop answers of a language model named by its configuration: the
numbers of ``comparisons/answer.py`` (``token_gap``, ``logit_err``,
``logit_err_mean``, ``logit_err_over``, ``score_gap``, ``text_gap``,
``score_err_mean``, ``malformed``; read there what each is), with the plain
reference taken by the name the ``.llm.json`` gives and the chat from
``pipelines/answer_llm.py``. After the window the program's own warmed
``prefill`` and ``step`` executables are replayed over the sampled prompts
and the served tokens, the rows side by side in one cache, each row into the
slot a free list hands it (``answer.replay``): a recurrent slot a row has
left is taken by a later row as it is.
"""

from __future__ import annotations

import gc

from chipbench import check
from chipbench.comparisons.answer import (  # noqa: F401
    DECODER_NUMBERS, GAP_NAMES, UNREAD, answer_numbers, collect, in_flight, replay, served_tokens,
)
from chipbench.flops_decoder import llm_config


def sampled_prompts(K, cell, kept: list) -> list[list[int]]:
    """The ids the server fed its chat for each kept (question, body, tokens)."""
    c, llm = cell.config, llm_config(cell.config)
    room = c["cache_len"] - c["max_tokens"]
    return [K.prompt_ids(K.build_prompt(q, [d["text"] for d in body["context_docs"]]), llm["vocab_size"], room)
            for q, body, _t in kept]


def numbers(cell, seed, sample, window, eparams, rparams, archive, setup_texts) -> dict:
    from chipbench.pipelines import answer_llm as pipeline

    c, llm = cell.config, llm_config(cell.config)
    K = pipeline.reference_module(llm)
    ref = check.Reference(c, eparams, setup_texts, archive)
    docs_of = [[q, body.get("context_docs") if isinstance(body, dict) else None] for q, body in sample]
    out = check.retrieve_numbers(ref, docs_of, cell.traffic["payload"]["k"])
    kept = [(q, body, served_tokens(body, c)) for q, body in sample]
    out["malformed"] += float(sum(1 for _q, _b, toks in kept if toks is None))
    kept = [(q, body, toks) for q, body, toks in kept if toks is not None]
    prompts, answers = sampled_prompts(K, cell, kept), [toks for _q, _b, toks in kept]
    if not pipeline.BUILT:
        raise RuntimeError("the answer comparison found no chat to replay: pipelines/answer_llm.py BUILT is empty")
    chat = pipeline.BUILT.pop()
    got = replay(chat.model, prompts, answers) if kept else []
    chat = None
    gc.collect()  # the program's weights leave the device before the reference's float32 layers come
    want = K.forward_rows(K.llm_key(eparams), llm, c["compute_dtype"],
                          [p + t[:-1] for p, t in zip(prompts, answers)], [len(t) for t in answers]) if kept else []
    out.update(answer_numbers(want, answers, got))
    return out
