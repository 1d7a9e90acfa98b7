"""Retrieve-and-rerank: each served (text, score) against the reference
cross-encoder, each text's right to be a candidate, and the served top
against the reference's top among the candidates no rounding can push out."""

from chipbench import check
from chipbench.comparisons.retrieve import GAP_NAMES, collect, in_flight  # noqa: F401


def numbers(cell, seed, sample, window, eparams, rparams, archive, setup_texts) -> dict:
    ref = check.Reference(cell.config, eparams, setup_texts, archive)
    c = cell.config
    return check.rerank_numbers(
        ref, rparams, c["reranker"], sample, c["rerank_candidates"], c["rerank_top"],
        cell.cell["tie_margin"],
    )
