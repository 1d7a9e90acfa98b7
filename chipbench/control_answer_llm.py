"""The controls of an answer cell whose language model is named by its
configuration: something lower than the configuration states put in the
program's place, which has to come out as not correct.

``python3 -m chipbench.control_answer_llm --workload <cell> --seeds 1,2
[--precision fp8] [--state-dtype bfloat16] [--samples n] [--tokens n]``
needs no served pipeline. For each seed it makes the cell's weights and
set-up documents, draws questions as a run would, takes the float32
reference's top-k documents for each and builds the prompt. Then either

- ``--precision``: the lowered reference answers greedily (the whole
  sequence computed again for every token), as ``control_answer`` does; or
- ``--state-dtype``: the program itself answers, built as the pipeline
  builds it but with the type its recurrent slots declare patched (a slot
  held in bfloat16: the program has no option that asks for it), through
  the comparison's own replay;

and that answer and those logits are held against the float32 reference with
the cell's own comparison (``answer_numbers``). It prints every number beside
its limit. ``chipbench.control`` (kept as it is) lowers the embedder and the
index of the same cell.
"""

from __future__ import annotations

import argparse
import json
import sys

from chipbench import check, corpus
from chipbench.comparisons.answer_llm import answer_numbers, replay
from chipbench.flops_decoder import llm_config
from chipbench.pipelines.answer_llm import build_chat, reference_module


def control_numbers(cell, seed: int, precision: str, samples: int, tokens: int | None = None,
                    state_dtype: str | None = None) -> dict:
    import numpy as np

    from chipbench import weights

    c, llm = cell.config, llm_config(cell.config)
    K = reference_module(llm)
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
    if state_dtype is None:
        answers = [K.greedy(key, llm, dtype, p, tokens, precision) for p in prompts]
    else:
        from unittest import mock

        import jax.numpy as jnp

        from pathway_tpu.ops import mixers
        from pathway_tpu.ops.decoder import generate

        with mock.patch.object(mixers, "STATE_DTYPE", getattr(jnp, state_dtype)):  # read when a cache is made
            model = build_chat(c, key).model
            answers = generate(model, [np.asarray(p, np.int32) for p in prompts], [tokens] * len(prompts))
            low = replay(model, prompts, answers)
        del model
    rows, keep = [p + a[:-1] for p, a in zip(prompts, answers)], [tokens] * len(prompts)
    want = K.forward_rows(key, llm, dtype, rows, keep)
    if state_dtype is None:
        low = K.forward_rows(key, llm, dtype, rows, keep, precision)
    return answer_numbers(want, answers, low)


def main(argv: list[str] | None = None) -> int:
    from chipbench.run import load_cell

    ap = argparse.ArgumentParser(prog="chipbench.control_answer_llm")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--precision", default="fp8")
    ap.add_argument("--state-dtype", default=None)
    ap.add_argument("--samples", type=int, default=None)
    ap.add_argument("--tokens", type=int, default=None)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        numbers = control_numbers(cell, seed, args.precision, args.samples or cell.cell["control_samples"],
                                  args.tokens, args.state_dtype)
        correct, compared = check.verdict(numbers, cell.cell["limits"])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "lowered": f"state {args.state_dtype}" if args.state_dtype else args.precision,
                          "correct": correct, "compared": compared}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
