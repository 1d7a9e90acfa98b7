"""The plain reference: what the served pipeline has to answer, written
straight from the published description in ``jax.numpy`` and float32 at
``highest`` matmul precision. It imports nothing of the program and takes
nothing the program made; weights and archive come from the benchmark's own
``weights.py`` / ``archive.py``.

- tokenizer: lower-case, split into ``[A-Za-z0-9]+`` runs and single other
  non-space characters, FNV-1a 64 of each piece, id ``3 + h % (vocab - 3)``
  (0 pad, 1 [CLS], 2 [SEP]); a text is ``[CLS] pieces``; a reranker pair is
  ``[CLS] query [SEP] document`` with the query cut to half the budget
- encoder: BERT post-LN block (arXiv:1810.04805) at the configuration's
  widths, masked mean pooling, L2 normalisation (sentence-transformers)
- reranker: the same block, pooled vector times a scalar head
- index: cosine of the query against every row, exact top-k

``precision`` lowers the matmul operands for the control: ``"f32"`` (the
reference), ``"bf16"`` (what the configuration states), ``"fp8"`` (e4m3, the
nearest step below bf16, the one a later PR would be tempted by).
"""

from __future__ import annotations

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_PIECES = re.compile(r"[A-Za-z0-9]+|[^\sA-Za-z0-9]")
PAD, CLS, SEP = 0, 1, 2


def pieces(text: str, vocab: int, limit: int) -> list[int]:
    out = []
    for w in _PIECES.findall(text.lower())[:limit]:
        h = 1469598103934665603
        for ch in w.encode():
            h = ((h ^ ch) * 1099511628211) & 0xFFFFFFFFFFFFFFFF
        out.append(3 + h % (vocab - 3))
    return out


def text_ids(text: str, vocab: int, max_len: int) -> list[int]:
    return ([CLS] + pieces(text, vocab, max_len))[:max_len]


def pair_ids(query: str, doc: str, vocab: int, max_len: int) -> list[int]:
    budget = max_len - 2
    q = pieces(query, vocab, max_len)[: budget // 2]
    d = pieces(doc, vocab, max_len)[: budget - len(q)]
    return [CLS] + q + [SEP] + d


def pad_ids(rows: list[list[int]], width: int) -> np.ndarray:
    out = np.zeros((len(rows), width), dtype=np.int32)
    for i, r in enumerate(rows):
        out[i, : len(r)] = r
    return out


def _lower(x, precision: str):
    if precision == "f32":
        return x.astype(jnp.float32)
    if precision == "bf16":
        return x.astype(jnp.bfloat16)
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    raise ValueError(f"unknown precision {precision!r}")


def _mm(spec: str, a, b, precision: str):
    return jnp.einsum(
        spec, _lower(a, precision), _lower(b, precision),
        preferred_element_type=jnp.float32, precision="highest",
    )


def _ln(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["g"] + p["b"]


def pooled(params: dict, ids: jax.Array, *, heads: int, eps: float, precision: str) -> jax.Array:
    """[B, L] ids (0 = pad) -> [B, d] masked-mean-pooled, L2-normalised."""
    mask = ids != PAD
    B, L = ids.shape
    x = params["embed"][ids] + params["pos"][:L][None] + params["tok_type"][0][None, None]
    x = _ln(x, params["emb_ln"], eps)
    d = x.shape[-1]
    hd = d // heads
    neg = jnp.where(mask, 0.0, -1e30)[:, None, None, :]
    for lp in params["layers"]:
        qkv = _mm("bld,de->ble", x, lp["wqkv"], precision) + lp["bqkv"]
        q, k, v = (t.reshape(B, L, heads, hd) for t in jnp.split(qkv, 3, axis=-1))
        s = _mm("bqhd,bkhd->bhqk", q, k, precision) * hd ** -0.5 + neg
        p = jax.nn.softmax(s, axis=-1)
        ctx = _mm("bhqk,bkhd->bqhd", p, v, precision).reshape(B, L, d)
        x = _ln(x + _mm("bld,de->ble", ctx, lp["wo"], precision) + lp["bo"], lp["ln1"], eps)
        h = jax.nn.gelu(_mm("bld,df->blf", x, lp["w1"], precision) + lp["b1"], approximate=False)
        x = _ln(x + _mm("blf,fd->bld", h, lp["w2"], precision) + lp["b2"], lp["ln2"], eps)
    m = mask[:, :, None].astype(jnp.float32)
    mean = jnp.sum(x * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0)
    return mean / jnp.maximum(jnp.linalg.norm(mean, axis=-1, keepdims=True), 1e-12)


@partial(jax.jit, static_argnames=("heads", "eps", "precision"))
def embed_ids(params, ids, *, heads, eps, precision="f32"):
    return pooled(params, ids, heads=heads, eps=eps, precision=precision)


@partial(jax.jit, static_argnames=("heads", "eps", "precision"))
def score_ids(params, ids, *, heads, eps, precision="f32"):
    v = pooled(params, ids, heads=heads, eps=eps, precision=precision)
    return (_mm("bd,do->bo", v, params["head"]["w"], "f32") + params["head"]["b"])[:, 0]


def _width(n: int) -> int:
    w = 16
    while w < n:
        w *= 2
    return w


def run_blocks(fn, params, rows: list[list[int]], model: dict, precision: str, block: int = 256) -> np.ndarray:
    """``fn`` over id rows in blocks of equal padded width (rows sorted by
    length so a block pads little); results in the rows' own order."""
    if not rows:
        return np.zeros((0,), np.float32)
    order = sorted(range(len(rows)), key=lambda i: len(rows[i]))
    out: list = [None] * len(rows)
    for lo in range(0, len(order), block):
        idx = order[lo : lo + block]
        chunk = [rows[i] for i in idx]
        chunk += [chunk[-1]] * (block - len(chunk))
        ids = pad_ids(chunk, _width(max(len(r) for r in chunk)))
        res = np.asarray(fn(params, jnp.asarray(ids), heads=model["num_attention_heads"],
                            eps=model["layer_norm_eps"], precision=precision))
        for j, i in enumerate(idx):
            out[i] = res[j]
    return np.stack(out)


def embed_texts(params, texts: list[str], model: dict, precision: str = "f32") -> np.ndarray:
    rows = [text_ids(t, model["vocab_size"], model["max_position_embeddings"]) for t in texts]
    return run_blocks(embed_ids, params, rows, model, precision)


def score_pairs(params, pairs: list[tuple[str, str]], model: dict, precision: str = "f32") -> np.ndarray:
    rows = [pair_ids(q, d, model["vocab_size"], model["max_position_embeddings"]) for q, d in pairs]
    return run_blocks(score_ids, params, rows, model, precision, block=64)


@partial(jax.jit, static_argnames=("k",))
def _block_topk(rows, queries, k):
    """cosine of [Q, d] queries against [n, d] rows -> top-k scores per query."""
    dots = jnp.einsum("qd,nd->qn", queries, rows, precision="highest")
    qn = jnp.linalg.norm(queries, axis=-1, keepdims=True)
    rn = jnp.linalg.norm(rows, axis=-1)[None, :]
    return jax.lax.top_k(dots / jnp.maximum(qn * rn, 1e-30), k)


def topk_over_blocks(blocks, queries: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Exact top-k scores (and global row numbers) of each query over row
    blocks given one at a time, so the whole matrix is never held twice."""
    q = jnp.asarray(queries, jnp.float32)
    best_s = np.full((len(queries), 0), -np.inf, np.float32)
    best_i = np.zeros((len(queries), 0), np.int64)
    base = 0
    for rows in blocks:
        s, i = _block_topk(rows, q, k=min(k, rows.shape[0]))
        best_s = np.concatenate([best_s, np.asarray(s)], axis=1)
        best_i = np.concatenate([best_i, np.asarray(i, dtype=np.int64) + base], axis=1)
        keep = np.argsort(-best_s, axis=1, kind="stable")[:, :k]
        best_s = np.take_along_axis(best_s, keep, axis=1)
        best_i = np.take_along_axis(best_i, keep, axis=1)
        base += rows.shape[0]
    return best_s, best_i
