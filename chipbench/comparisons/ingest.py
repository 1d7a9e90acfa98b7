"""Backfill: once the window has closed, the store is asked two kinds of
question, both drawn from the seed, and each answer is held against the
reference's exact top-k over the archive, the set-up documents and everything
the window ingested: short queries made of the words of documents the window
ingested (their scores show the precision of the vectors that were indexed),
and ingested documents' own texts, the last one among them (each has to come
back first: ``self_miss`` counts those that do not)."""

import numpy as np

from chipbench import check, corpus

GAP_NAMES = ("host_tick_loop_with_backlog", "no_backlog")


def window_texts(cell, seed, window) -> list[str]:
    return corpus.docs(seed, window["first_block"], window["blocks"], cell.config["documents"])


def collect(gen, cell, seed, window) -> list:
    texts = window_texts(cell, seed, window)
    n = cell.cell["probe"]
    rng = np.random.default_rng([int(seed), 5])
    own = sorted(set(rng.choice(len(texts), size=min(len(texts), n), replace=False).tolist()) | {len(texts) - 1})
    asked = corpus.queries(seed, n, cell.cell["probe_queries"], texts, stream=6) + [texts[i] for i in own]
    k = cell.config["retrieve_k"]
    reply = gen.call(cmd="requests", items=[["retrieve", {"query": t, "k": k}] for t in asked], parallel=4)
    return [[t, body if status == 200 else None] for t, (status, body) in zip(asked, reply["replies"])]


def numbers(cell, seed, sample, window, eparams, rparams, archive, setup_texts) -> dict:
    ref = check.Reference(cell.config, eparams, setup_texts + window_texts(cell, seed, window), archive)
    n = cell.cell["probe"]
    out = check.retrieve_numbers(ref, sample, cell.config["retrieve_k"])
    out["self_miss"] = float(sum(
        1 for text, body in sample[n:] if not (isinstance(body, list) and body and body[0].get("text") == text)
    ))
    return out


def in_flight(window, offset_ns: int) -> list:
    t0 = window["start_ns"] + offset_ns
    return [(t0, t0 + int(window["end_s"] * 1e9))]
