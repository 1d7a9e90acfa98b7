"""Cross-encoder reranker on TPU.

Replaces the reference's per-row torch CrossEncoder (``xpacks/llm/rerankers.py:159-208``,
one ``model.predict([[query, doc]])`` per row) with a batched jitted forward pass:
query and doc are concatenated with a separator token, run through the same
transformer backbone as the sentence encoder, and a scalar relevance head scores the
pooled representation. Batching/padding discipline comes from
:mod:`pathway_tpu.ops.microbatch`.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from pathway_tpu.ops.encoder import (
    EncoderConfig,
    HashTokenizer,
    encode,
    init_params,
)
from pathway_tpu.observability import device as _dev_prof
from pathway_tpu.ops.microbatch import LENGTH_MAX_BUCKET, bucket_size

_SEP = 2  # reserved token id used between query and doc


def init_reranker_params(cfg: EncoderConfig, key: jax.Array) -> dict:
    k1, k2 = jax.random.split(key)
    params = init_params(cfg, k1)
    params["head"] = {
        "w": jax.random.normal(k2, (cfg.d_model, 1), jnp.float32) * (cfg.d_model ** -0.5),
        "b": jnp.zeros((1,)),
    }
    return params


def score(params: dict, cfg: EncoderConfig, token_ids: jax.Array, mask: jax.Array) -> jax.Array:
    """[B, L] paired-sequence tokens → [B] relevance scores (f32 logits)."""
    pooled = encode(params, cfg, token_ids, mask)  # [B, d], unit-norm
    return (pooled @ params["head"]["w"] + params["head"]["b"]).squeeze(-1)


@partial(jax.jit, static_argnames=("cfg",))
def _score_jit(params: dict, cfg: EncoderConfig, token_ids: jax.Array, mask: jax.Array):
    return score(params, cfg, token_ids, mask)


# device profiling plane: compile/shape telemetry per reranker launch
score_jit = _dev_prof.traced_jit("reranker.score", _score_jit)


class JaxCrossEncoder:
    """Batched (query, doc) → relevance score model."""

    def __init__(self, cfg: EncoderConfig | None = None, seed: int = 0):
        self.cfg = cfg or EncoderConfig(n_layers=4)
        self.params = init_reranker_params(self.cfg, jax.random.PRNGKey(seed))
        self.tokenizer = HashTokenizer(self.cfg.vocab_size, self.cfg.max_len)
        _dev_prof.register_memory(
            self,
            "reranker_params",
            lambda ce: int(sum(p.nbytes for p in jax.tree.leaves(ce.params))),
        )

    def score_pairs(self, pairs: list[tuple[str, str]]) -> np.ndarray:
        if not pairs:
            return np.zeros((0,), dtype=np.float32)
        texts_ids = []
        for q, d in pairs:
            qt = self.tokenizer._tok(q)
            dt = self.tokenizer._tok(d)
            budget = self.cfg.max_len - 2
            qt = qt[: budget // 2]
            dt = dt[: budget - len(qt)]
            texts_ids.append([1] + qt + [_SEP] + dt)
        L = min(
            self.cfg.max_len,
            bucket_size(
                max(len(t) for t in texts_ids), min_bucket=16, max_bucket=LENGTH_MAX_BUCKET
            ),
        )
        ids = np.zeros((len(pairs), L), dtype=np.int32)
        mask = np.zeros((len(pairs), L), dtype=bool)
        for i, t in enumerate(texts_ids):
            t = t[:L]
            ids[i, : len(t)] = t
            mask[i, : len(t)] = True
        stats = _dev_prof.stats()
        if stats.enabled:
            real = int(mask.sum())
            stats.note_pad_tokens("reranker", real, ids.size - real)
        return _dev_prof.fetch(score_jit(self.params, self.cfg, ids, mask), "reranker")
