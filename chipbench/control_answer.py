"""The control of the answer cell's decoder limits: the reference put in the
program's place at a lower precision, which has to come out as not correct.

``python3 -m chipbench.control_answer --workload kimi-k2.answer-rag --seeds
1,2 [--precision fp8] [--samples n] [--tokens n]`` needs no served pipeline.
For each seed it makes the cell's weights and set-up documents, draws
questions as a run would, takes the float32 reference's top-k documents for
each, builds the prompt, lets the lowered reference answer it greedily (the
whole sequence computed again for every token) and holds that answer and the
lowered logits against the float32 reference with the cell's own comparison
(``comparisons/answer.py`` ``answer_numbers``). ``chipbench.control`` (kept as
it is) lowers the embedder and the index of the same cell; this lowers the
language model. It prints every number beside its limit.
"""

from __future__ import annotations

import argparse
import json
import sys

from chipbench import check, corpus
from chipbench import reference_kimi_k2 as K
from chipbench.comparisons.answer import answer_numbers
from chipbench.flops_decoder import llm_config


def control_numbers(cell, seed: int, precision: str, samples: int, tokens: int | None = None) -> dict:
    from chipbench import weights

    c, llm = cell.config, llm_config(cell.config)
    tokens = tokens or c["max_tokens"]
    eparams = weights.make_params(c, seed, 0)
    live = corpus.docs(seed, 0, c["live_documents"] // corpus.BLOCK, c["documents"])
    questions = corpus.queries(seed, samples, cell.traffic["queries"], live)
    ref = check.Reference(c, eparams, live, None)
    key, dtype = K.llm_key(eparams), c["compute_dtype"]
    room = c["cache_len"] - c["max_tokens"]
    prompts = [
        K.prompt_ids(K.build_prompt(q, [h["text"] for h in hits]), llm["vocab_size"], room)
        for q, hits in zip(questions, ref.bodies(questions, cell.traffic["payload"]["k"]))
    ]
    answers = [K.greedy(key, llm, dtype, p, tokens, precision) for p in prompts]
    rows, keep = [p + a[:-1] for p, a in zip(prompts, answers)], [tokens] * len(prompts)
    want = K.forward_rows(key, llm, dtype, rows, keep)
    low = K.forward_rows(key, llm, dtype, rows, keep, precision)
    return answer_numbers(want, answers, low)


def main(argv: list[str] | None = None) -> int:
    from chipbench.run import load_cell

    ap = argparse.ArgumentParser(prog="chipbench.control_answer")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--precision", default="fp8")
    ap.add_argument("--samples", type=int, default=None)
    ap.add_argument("--tokens", type=int, default=None)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        numbers = control_numbers(cell, seed, args.precision, args.samples or cell.cell["control_samples"], args.tokens)
        correct, compared = check.verdict(numbers, cell.cell["limits"])
        print(json.dumps({"workload": args.workload, "seed": seed, "precision": args.precision,
                          "correct": correct, "compared": compared}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
