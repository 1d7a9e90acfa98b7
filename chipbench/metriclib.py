"""What the metric readers share: the useful operations of a window's work,
from the lengths the traffic has (never from the padded grid), and small
statistics."""

from __future__ import annotations

import numpy as np

from chipbench import corpus, flops


def percentile(values: list, pct: float) -> float:
    """Nearest-rank percentile; a request that failed or was never answered
    (None) counts as slower than any other."""
    vals = sorted(float("inf") if v is None else v for v in values)
    rank = max(0, int(np.ceil(pct / 100.0 * len(vals))) - 1)
    return vals[rank] if vals[rank] != float("inf") else 1e9


def block_flops(ctx) -> float:
    """Encoder FLOPs of one block of 512 documents at their real lengths."""
    c = ctx.config
    return sum(
        flops.encoder_flops(c, flops.text_tokens(int(w), c["max_position_embeddings"]))
        for w in corpus.block_lengths(c["documents"])
    )


def ingest_flops(ctx) -> float:
    return block_flops(ctx) * ctx.window["documents"] / corpus.BLOCK


def query_flops(ctx) -> float:
    """Embedding FLOPs of the window's queries, plus, where the cell
    reranks, the cross-encoder over ``rerank_candidates`` pairs a query: the
    document side at the corpus's mean over its fixed multiset of lengths."""
    c, n = ctx.config, ctx.window["attempted"]
    qwords = corpus.query_lengths(n, ctx.traffic["queries"])
    total = sum(flops.encoder_flops(c, flops.text_tokens(int(w), c["max_position_embeddings"])) for w in qwords)
    return total + rerank_flops(ctx)


def rerank_flops(ctx) -> float:
    c, n = ctx.config, ctx.window["attempted"]
    if "reranker" not in c:
        return 0.0
    r = c["reranker"]
    qwords = corpus.query_lengths(n, ctx.traffic["queries"])
    dwords = corpus.block_lengths(c["documents"])
    per_q = {}
    for qw in set(qwords.tolist()):
        per_q[qw] = float(np.mean([
            flops.encoder_flops(r, flops.pair_tokens(qw, int(dw), r["max_position_embeddings"])) for dw in dwords
        ]))
    return c["rerank_candidates"] * sum(per_q[qw] for qw in qwords.tolist())


def kernel_seconds(ctx, prefix: str) -> float:
    return sum(s for label, s in ctx.trace["seconds"].items() if label.startswith(prefix))


def kernel_launches(ctx, prefix: str) -> float:
    return sum(n for label, n in ctx.trace["launches"].items() if label.startswith(prefix))


def calls_delta(ctx, prefix: str) -> int:
    return sum(
        n - ctx.before["calls"].get(label, 0) for label, n in ctx.after["calls"].items() if label.startswith(prefix)
    )


def idle_pct(ctx) -> float:
    """1 - union of device-op intervals over the traced window."""
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])


def peak(ctx) -> dict:
    return flops.peaks(ctx.device_kind)
