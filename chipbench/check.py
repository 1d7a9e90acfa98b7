"""The comparison that decides ``correct``: what the served path answered
against the plain reference, each number beside a limit of its own.

Scores cluster (any two texts of a random-weight encoder sit at cosine
0.92-0.99), so rank order among near-ties flips on rounding. What is compared
is therefore a value, never an identity: the sorted list of served scores
against the reference's sorted top-k, and each returned text's score against
the reference's score for that very text.
"""

from __future__ import annotations

import itertools

import jax.numpy as jnp
import numpy as np

from chipbench import reference as R


LIVE_BLOCK = 16384


class Reference:
    """Float32 embeddings of the live documents and the archive's recipe:
    everything the comparisons need, built once after the window."""

    def __init__(self, config: dict, params: dict, live_texts: list[str], archive, precision: str = "f32"):
        self.config, self.params, self.archive = config, params, archive
        self.precision = precision
        self.live_texts = live_texts
        self.row_of = {t: i for i, t in enumerate(live_texts)}
        self.live = R.embed_texts(params, live_texts, config, precision)

    def embed(self, texts: list[str]) -> np.ndarray:
        return R.embed_texts(self.params, texts, self.config, self.precision)

    def blocks(self):
        """Row blocks of the whole index: the archive first, live rows last,
        in equal blocks (the last filled up with zero rows, which score 0 and
        never reach a top-k) so that one compiled shape serves any count."""
        arch = self.archive.blocks() if self.archive is not None else ()
        pad = -len(self.live) % LIVE_BLOCK
        live = np.concatenate([self.live, np.zeros((pad, self.live.shape[1]), np.float32)])
        return itertools.chain(arch, (jnp.asarray(live[i : i + LIVE_BLOCK]) for i in range(0, len(live), LIVE_BLOCK)))

    @property
    def archive_rows(self) -> int:
        return self.archive.rows if self.archive is not None else 0

    def topk(self, q: np.ndarray, k: int):
        return R.topk_over_blocks(self.blocks(), q, k)

    def bodies(self, queries: list[str], k: int) -> list[list[dict]]:
        """What ``/v1/retrieve`` would answer if this reference (at its
        precision) stood in the program's place."""
        s, i = self.topk(self.embed(queries), k)
        out = []
        for srow, irow in zip(s, i):
            out.append([
                {"text": self.live_texts[j - self.archive_rows] if j >= self.archive_rows else None,
                 "dist": -float(x)}
                for x, j in zip(srow.tolist(), irow.tolist())
            ])
        return out


def retrieve_numbers(ref: Reference, sample: list, k: int) -> dict:
    """``sample``: [query text, served body] pairs. Returns the numbers:
    ``score_gap`` (sorted served scores against the reference's sorted
    top-k), ``text_gap`` (a returned text's served score against the
    reference's score of that text; a text the corpus never held reads 2),
    ``score_err_mean`` (the mean of those score differences: steadier than the
    widest), ``malformed`` (answers that are not k hits in order of distance)."""
    queries = [q for q, _ in sample]
    q = ref.embed(queries)
    top_s, _ = ref.topk(q, k)
    score_gap = text_gap = 0.0
    malformed = 0
    errs: list[float] = []
    for qi, (_query, body) in enumerate(sample):
        dists = [h.get("dist") for h in body] if isinstance(body, list) else None
        if not dists or len(dists) != min(k, top_s.shape[1]) or any(
            not isinstance(d, (int, float)) for d in dists
        ) or dists != sorted(dists):
            malformed += 1
            continue
        served = -np.asarray(dists, np.float64)
        score_gap = max(score_gap, float(np.abs(served - top_s[qi]).max()))
        errs.extend(np.abs(served - top_s[qi]).tolist())
        for h in body:
            if h.get("text") is None:
                continue
            row = ref.row_of.get(h["text"])
            if row is None:
                text_gap = 2.0
            else:
                text_gap = max(text_gap, abs(-h["dist"] - float(q[qi] @ ref.live[row])))
    return {"score_gap": score_gap, "text_gap": text_gap,
            "score_err_mean": float(np.mean(errs)) if errs else 0.0, "malformed": float(malformed)}


def rerank_numbers(ref: Reference, rparams: dict, rconfig: dict, sample: list, k: int, top: int,
                   tie: float, precision: str = "f32") -> dict:
    """``sample``: [query, served top list of {text, score}]. ``pair_gap``:
    each served score against the reference cross-encoder's score of that
    (query, text). ``knn_gap``: how far a served text's cosine lies below the
    reference's k-th best (it had to be a candidate). ``rank_gap``: how far
    the served j-th score lies below the reference's j-th best among the
    candidates no rounding can push out (cosine above the k-th by ``tie``)."""
    queries = [q for q, _ in sample]
    q = ref.embed(queries)
    top_s, top_i = ref.topk(q, k)
    pair_gap = knn_gap = rank_gap = 0.0
    malformed = 0
    pairs, where = [], []
    for qi, (query, body) in enumerate(sample):
        ok = isinstance(body, list) and len(body) == top and all(
            isinstance(h.get("score"), (int, float)) and h.get("text") in ref.row_of for h in body
        )
        if not ok or [h["score"] for h in body] != sorted((h["score"] for h in body), reverse=True):
            malformed += 1
            continue
        for h in body:
            pairs.append((query, h["text"]))
            where.append((qi, h["score"], "served"))
        for s, j in zip(top_s[qi].tolist(), top_i[qi].tolist()):
            if j >= ref.archive_rows and s >= top_s[qi][-1] + tie:
                pairs.append((query, ref.live_texts[j - ref.archive_rows]))
                where.append((qi, None, "sure"))
    scores = R.score_pairs(rparams, pairs, rconfig, precision)
    sure: dict[int, list] = {}
    served: dict[int, list] = {}
    for (qi, got, kind), (query, text), want in zip(where, pairs, scores.tolist()):
        if kind == "served":
            pair_gap = max(pair_gap, abs(got - want))
            cos = float(q[qi] @ ref.live[ref.row_of[text]])
            knn_gap = max(knn_gap, float(top_s[qi][-1]) - cos)
            served.setdefault(qi, []).append(got)
        else:
            sure.setdefault(qi, []).append(want)
    for qi, got in served.items():
        want = sorted(sure.get(qi, []), reverse=True)[:top]
        for g, w in zip(got, want):
            rank_gap = max(rank_gap, w - g)
    return {"pair_gap": pair_gap, "knn_gap": max(knn_gap, 0.0), "rank_gap": max(rank_gap, 0.0),
            "malformed": float(malformed)}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every number beside its limit; correct when none passes its limit. A
    number without a limit is an error in the cell's file, not a pass."""
    compared = {n: {"value": v, "limit": limits[n]} for n, v in numbers.items()}
    return all(c["value"] <= c["limit"] for c in compared.values()), compared
