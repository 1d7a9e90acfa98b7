"""Open-loop answers: the bodies the generator kept (a sample drawn from the
seed: the 48 served tokens and the context documents) against the plain
reference.

- the six context documents against the reference's exact top-6, as the
  retrieve comparison holds them (``score_gap``, ``text_gap``,
  ``score_err_mean``);
- ``token_gap``: the reference's full float32 forward pass over the prompt it
  builds from the served documents plus the served answer; at every
  generated position its largest logit minus its logit of the served token,
  in units of that position's spread of logits (a value, not an identity:
  with random weights the argmax turns on rounding); the mean over the
  generated positions;
- ``logit_err`` / ``logit_err_mean`` / ``logit_err_over``: after the window,
  the program's own ``prefill`` and ``step`` executables at the warmed shapes
  over the same prompts and served tokens, the sampled rows side by side in
  one cache as the window ran them (``replay``): |program - reference| in
  units of the spread. ``logit_err`` is the median over the generated
  positions of the position's largest, ``logit_err_mean`` the mean over
  positions and vocabulary, ``logit_err_over`` the share of the positions
  whose largest is over ``OVER``. Not the largest of all: where rounding
  turns a token's eighth-best expert into its ninth and one of the two is
  held here, that token's state moves by a whole expert's output, in the
  reference at bfloat16 as in the program, and one position in a hundred
  reads 0.5-1.0 (printed, not compared; counted by ``logit_err_over``);
- ``malformed``: answers that are not ``max_tokens`` ids inside the slice
  with ``search_topk`` context documents in order of distance.

Every number has a limit and is returned whatever happened: a run whose chat
cannot be found raises, and where no sampled answer is well formed the
decoder's numbers read ``UNREAD``, past any limit.
"""

from __future__ import annotations

import gc
import sys

import numpy as np

from chipbench import check
from chipbench import reference_kimi_k2 as K
from chipbench.comparisons.retrieve import GAP_NAMES, collect, in_flight  # noqa: F401
from chipbench.flops_decoder import llm_config


def served_tokens(body, c: dict) -> list[int] | None:
    """The answer's token ids, or None where the body is not an answer of
    ``max_tokens`` ids inside the slice with its context documents."""
    if not isinstance(body, dict) or not isinstance(body.get("response"), str):
        return None
    docs = body.get("context_docs")
    if not isinstance(docs, list) or len(docs) != c["search_topk"] or any(
        not isinstance(d, dict) or not isinstance(d.get("text"), str) for d in docs
    ):
        return None
    words = body["response"].split()
    if len(words) != c["max_tokens"] or not all(w.isdigit() and int(w) < llm_config(c)["vocab_size"] for w in words):
        return None
    return [int(w) for w in words]


#: a position's largest error, in spreads, above which ``logit_err_over`` counts it
OVER = 0.2
DECODER_NUMBERS = ("token_gap", "logit_err", "logit_err_mean", "logit_err_over")
#: what a number reads where there was nothing to read it from (finite: the result line is strict JSON)
UNREAD = 1e30


def replay(model, prompts: list[list[int]], answers: list[list[int]]) -> list[np.ndarray]:
    """The program's logits at every generated position, the served tokens
    fed back in place of its own. The rows join a running batch a few steps
    apart, each into the slot a free list hands it, and leave when their
    tokens are through: the live rows go from one up to ``cache_rows`` and
    down again, so every step bucket the window could use is compared, and a
    later row decodes in a slot an earlier one has left."""
    m = model
    cache, free = m.new_cache(), list(range(m.cache_rows))
    apart = max(1, max(len(t) for t in answers) // m.cache_rows)
    out: list[list[np.ndarray]] = [[] for _ in prompts]
    todo, live, tick = list(range(len(prompts))), [], 0  # live: [sample, slot, tokens fed]
    while todo or live:
        if todo and free and tick % apart == 0:
            i, slot = todo.pop(0), free.pop(0)
            _o, logits, cache, _L = m.run_prefill(cache, [slot], [np.asarray(prompts[i], np.int32)])
            out[i].append(np.asarray(logits[0]))
            live.append([i, slot, 0])
        for row in [r for r in live if r[2] >= len(answers[r[0]]) - 1]:
            live.remove(row)
            free.append(row[1])
        if live:
            _o, logits, cache, _R = m.run_step(
                cache, [slot for _i, slot, _n in live], [answers[i][n] for i, _s, n in live],
                [len(prompts[i]) + n for i, _s, n in live],
            )
            logits = np.asarray(logits)
            for j, row in enumerate(live):
                out[row[0]].append(logits[j])
                row[2] += 1
        tick += 1
    return [np.stack(rows) for rows in out]


def answer_numbers(want: list[np.ndarray], answers: list[list[int]], got: list[np.ndarray]) -> dict:
    """``want`` / ``got``: per sample ``[tokens, vocabulary]`` logits of the
    reference / of what stands in the program's place. Every number is in
    units of the position's spread of reference logits (their standard
    deviation over the vocabulary)."""
    if not want:
        return dict.fromkeys(DECODER_NUMBERS, UNREAD)
    gaps, errs = [], []
    for w, toks, g in zip(want, answers, got):
        spread = w.std(axis=-1)
        gaps.append((w.max(axis=-1) - w[np.arange(len(toks)), toks]) / spread)
        errs.append(np.abs(g - w) / spread[:, None])
    gaps = np.concatenate(gaps)
    largest = np.concatenate([e.max(axis=-1) for e in errs])  # per generated position
    print(f"chipbench: answer: largest token gap {float(gaps.max()):.4f}, {int((gaps > 0).sum())} of {gaps.size} "
          f"served tokens not the reference's; largest logit error {float(largest.max()):.4f}, positions over "
          f"{OVER}: {int((largest > OVER).sum())} of {largest.size}", file=sys.stderr, flush=True)
    return {
        "token_gap": float(gaps.mean()),
        "logit_err": float(np.median(largest)),
        "logit_err_mean": float(np.mean([e.mean() for e in errs])),
        "logit_err_over": float((largest > OVER).mean()),
    }


def numbers(cell, seed, sample, window, eparams, rparams, archive, setup_texts) -> dict:
    from chipbench.pipelines import answer as pipeline

    c, llm = cell.config, llm_config(cell.config)
    ref = check.Reference(c, eparams, setup_texts, archive)
    docs_of = [[q, body.get("context_docs") if isinstance(body, dict) else None] for q, body in sample]
    out = check.retrieve_numbers(ref, docs_of, cell.traffic["payload"]["k"])
    kept = [(q, body, served_tokens(body, c)) for q, body in sample]
    out["malformed"] += float(sum(1 for _q, _b, toks in kept if toks is None))
    kept = [(q, body, toks) for q, body, toks in kept if toks is not None]
    room = c["cache_len"] - c["max_tokens"]
    prompts = [
        K.prompt_ids(K.build_prompt(q, [d["text"] for d in body["context_docs"]]), llm["vocab_size"], room)
        for q, body, _t in kept
    ]
    answers = [toks for _q, _b, toks in kept]
    if not pipeline.BUILT:
        raise RuntimeError("the answer comparison found no chat to replay: pipelines/answer.py BUILT is empty")
    chat = pipeline.BUILT.pop()
    got = replay(chat.model, prompts, answers) if kept else []
    chat = None
    gc.collect()  # the program's weights leave the device before the reference's float32 layers come
    want = K.forward_rows(K.llm_key(eparams), llm, c["compute_dtype"],
                          [p + t[:-1] for p, t in zip(prompts, answers)], [len(t) for t in answers]) if kept else []
    out.update(answer_numbers(want, answers, got))
    return out
