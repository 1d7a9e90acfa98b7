"""Seeded texts: live documents and queries. numpy only: the load generator
imports this and must never touch JAX.

Every seed gets the same multiset of document lengths in every block of 512
documents and the same multiset of query lengths, in another order and with
other words: the seed changes the content, never the amount of work.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

BLOCK = 512  # documents per block: one full microbatcher launch
VOCAB_WORDS = 20000


def _rng(seed: int, *stream: int) -> np.random.Generator:
    # --seed may be a little over 2**31; SeedSequence takes any whole number
    return np.random.default_rng([int(seed), *stream])


def block_lengths(params: dict) -> np.ndarray:
    """The BLOCK document lengths (words) every block holds: quantiles of the
    log-normal in ``params`` (median, sigma), clipped to [min, max]."""
    nd = NormalDist()
    mu = math.log(params["median_words"])
    q = [(j + 0.5) / BLOCK for j in range(BLOCK)]
    raw = [math.exp(mu + params["sigma"] * nd.inv_cdf(x)) for x in q]
    return np.clip(np.rint(raw), params["min_words"], params["max_words"]).astype(np.int64)


def _texts(rng: np.random.Generator, lengths: np.ndarray) -> list[str]:
    words = rng.integers(0, VOCAB_WORDS, size=int(lengths.sum()))
    toks = [f"w{w}" for w in words.tolist()]
    out, at = [], 0
    for n in lengths.tolist():
        out.append(" ".join(toks[at : at + n]))
        at += n
    return out


def doc_block(seed: int, block: int, params: dict) -> list[str]:
    """Block ``block`` of the live corpus: BLOCK documents, the block's fixed
    lengths permuted by the seed."""
    rng = _rng(seed, 1, block)
    return _texts(rng, rng.permutation(block_lengths(params)))


def docs(seed: int, first_block: int, n_blocks: int, params: dict) -> list[str]:
    return [t for b in range(first_block, first_block + n_blocks) for t in doc_block(seed, b, params)]


def query_lengths(n: int, params: dict) -> np.ndarray:
    lo, hi = params["min_words"], params["max_words"]
    return lo + (np.arange(n) % (hi - lo + 1))


def queries(seed: int, n: int, params: dict, pool: list[str], stream: int = 2) -> list[str]:
    """``n`` distinct queries: query i takes its words from one pooled
    document, so it has near neighbours; lengths are a fixed cycle permuted by
    the seed. A leading counter word keeps any two from being alike."""
    rng = _rng(seed, stream)
    lengths = rng.permutation(query_lengths(n, params))
    picks = rng.integers(0, len(pool), size=n)
    out = []
    for i, (m, p) in enumerate(zip(lengths.tolist(), picks.tolist())):
        words = pool[p].split()
        take = rng.choice(len(words), size=min(m - 1, len(words)), replace=False)
        out.append(" ".join([f"q{i}"] + [words[j] for j in sorted(take.tolist())]))
    return out


def sample(seed: int, n: int, size: int, stream: int = 2) -> list[int]:
    """Which of n requests keep their bodies for the comparison."""
    return sorted(_rng(seed, 4, stream).choice(n, size=min(n, size), replace=False).tolist())


def arrival_offsets(seed: int, n: int, rate: float, stream: int = 2) -> np.ndarray:
    """Open-loop Poisson arrivals: the n quantile gaps of the exponential at
    ``rate`` (the same set for every seed), permuted by the seed; offsets in
    seconds from the window's start."""
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    return np.cumsum(_rng(seed, 3, stream).permutation(gaps))
