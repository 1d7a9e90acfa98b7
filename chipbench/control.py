"""The control of ``correct``: the reference put in the program's place at a
lower precision, which has to come out as not correct.

``python3 -m chipbench.control --workload <cell> --seeds 1,2,3 [--precision
fp8] [--queries n]`` needs no served pipeline: for each seed it makes the
cell's weights, documents, archive and the query sample a run would keep,
lets the lowered reference answer them (its own top-k over the same archive
and its own embeddings of the live rows), and holds those answers against the
float32 reference with the cell's own comparison. It prints every number
beside its limit. The benchmark's runs never run it; ``tests/`` keeps it at a
size a test can hold.

``--precision bf16`` is what the configuration states (it should pass);
``fp8`` (e4m3 operands) is the nearest precision below, the step a later PR
would be tempted by.
"""

from __future__ import annotations

import argparse
import json
import sys

from chipbench import check, corpus


def control_numbers(cell, seed: int, precision: str, n_queries: int, ingest_blocks: int = 2) -> dict:
    from chipbench import archive as archive_mod
    from chipbench import reference, weights

    config, traffic = cell.config, cell.traffic
    eparams = weights.make_params(config, seed, 0)
    blocks = config["live_documents"] // corpus.BLOCK
    live = corpus.docs(seed, 0, blocks, config["documents"])
    arch = None
    if config["archive_rows"]:
        base = reference.embed_texts(eparams, live, config, config["archive_base_precision"])
        arch = archive_mod.Archive(
            weights.seed_key(seed, 7), base, config["archive_rows"], config["archive_block_rows"],
            config["archive"], config["archive_noise"],
        )
    kind = traffic["comparison"]
    if kind == "ingest":  # short queries made of the words of documents the window ingested
        new = corpus.docs(seed, blocks, ingest_blocks, config["documents"])
        live = live + new
        queries = corpus.queries(seed, n_queries, cell.cell["probe_queries"], new, stream=6)
        k = config["retrieve_k"]
    else:
        texts = corpus.queries(seed, n_queries, traffic["queries"], live)
        queries = texts
        k = config.get("rerank_candidates") if kind == "rerank" else traffic["payload"]["k"]
    ref = check.Reference(config, eparams, live, arch)
    low = check.Reference(config, eparams, live, arch, precision)
    if kind != "rerank":
        return check.retrieve_numbers(ref, list(zip(queries, low.bodies(queries, k))), k)
    rparams = weights.make_params(config["reranker"], seed, 1, head=True)
    top = config["rerank_top"]
    sample = []
    for q, hits in zip(queries, low.bodies(queries, k)):
        texts = [h["text"] for h in hits if h["text"] is not None]
        scores = reference.score_pairs(rparams, [(q, t) for t in texts], config["reranker"], precision)
        order = sorted(range(len(texts)), key=lambda i: -scores[i])[:top]
        sample.append([q, [{"text": texts[i], "score": float(scores[i])} for i in order]])
    return check.rerank_numbers(ref, rparams, config["reranker"], sample, k, top, cell.cell["tie_margin"])


def main(argv: list[str] | None = None) -> int:
    from chipbench.run import load_cell

    ap = argparse.ArgumentParser(prog="chipbench.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--precision", default="fp8")
    ap.add_argument("--queries", type=int, default=128)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        numbers = control_numbers(cell, seed, args.precision, args.queries)
        correct, compared = check.verdict(numbers, cell.cell["limits"])
        print(json.dumps({"workload": args.workload, "seed": seed, "precision": args.precision,
                          "correct": correct, "compared": compared}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
