"""A decoder-only language model on the chip: latent attention and a sparse
expert layer (the DeepSeek-V3 block, which Kimi-K2 shares), served by
``prefill`` and ``step`` through a latent cache.

Block ``l``: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``; a
final RMSNorm; ``logits = y W_head``; embedding and head untied.

- Latent attention. ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` gives every
  head a no-position part and a rotary part; ``[c_kv ; k_r] = x W_kva``,
  ``c_kv = RMSNorm(c_kv)``, ``k_r`` one rotary vector shared by all heads
  (YaRN frequencies); ``[k_nope ; v] = c_kv W_kvb``. **The cache holds
  ``c_kv`` after its norm and ``k_r`` after RoPE**, ``kv_lora_rank +
  qk_rope_head_dim`` numbers a token a layer. Prefill up-projects keys and
  values from the latents; a decode step never does: it folds ``W_kvb``'s key
  half into the query, scores against ``c_kv`` itself and applies the value
  half after the weighted sum. Both are the same function of the same weights.
- The first ``first_k_dense`` layers have a dense SwiGLU. Every other layer
  routes: ``s = sigmoid(x W_r)`` in float32 over ALL the published experts,
  the ``experts_per_token`` largest ``s + b`` are chosen (``b`` a selection
  bias that never enters the weights), ``w_e = s_e / sum(s_chosen) *
  routed_scaling_factor``. The layer is told which experts it holds
  (``first_expert``, ``n_held``: one chip's share of an expert-parallel
  deployment); it gathers the (token, expert) pairs whose expert is here,
  sorts them by expert into blocks of equal size and runs the blocks that
  hold a pair as one grouped product (no pair is dropped: the buffer is sized
  for every pair the tokens could make). What absent experts would add is
  left out. A shared expert runs for every token.

bfloat16 weights and matmul operands by default; float32 for the residual
stream, RMSNorm's statistics, the router, softmax, RoPE and the logits.

``JaxDecoder`` owns the parameters, the cache (``cache_rows x cache_len``
latents a layer, a free list of slots) and the bucketing: a prefill launch
pads to a row bucket and a length bucket, a step to a row bucket, so a server
asks for a small closed set of executables and ``warm()`` compiles them all.
``DecodeSession`` is what a dataflow node drives (``ops/microbatch.py``
``RowStepper``): rows join at a step boundary and leave when done.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from pathway_tpu.observability import device as _dev_prof

#: rows of one expert block in the grouped product: a full MXU tile for a
#: prefill; a step's few rows take the smallest tile
EXPERT_BLOCK = 128
#: queries per attention block in prefill (the score matrix is never whole)
QUERY_BLOCK = 256
#: prompt lengths pad to a multiple of this
LENGTH_STEP = 512
#: rows of one prefill launch while serving. A prompt of a thousand tokens
#: fills the chip by itself, and every further row bucket multiplies what
#: ``warm()`` compiles by the length buckets; a launch of several rows has not
#: been timed on the chip (PERF.md, PR 32)
PREFILL_ROWS = 1
BOS = 1  # HashTokenizer: 0 pad, 1 [CLS], 2 [SEP]


@dataclass(frozen=True)
class DecoderConfig:
    """Shapes of the model as this process holds it. ``n_routed_experts`` is
    the router's width (the published count); ``first_expert`` and ``n_held``
    say which of them live here; ``vocab_size`` is the rows of the embedding
    and of the head held here."""

    vocab_size: int = 256
    hidden_size: int = 64
    n_layers: int = 3
    n_heads: int = 4
    q_lora_rank: int = 48
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    intermediate_size: int = 128
    moe_intermediate_size: int = 32
    n_routed_experts: int = 16
    first_expert: int = 0
    n_held: int = 16
    n_shared_experts: int = 1
    experts_per_token: int = 4
    first_k_dense: int = 1
    routed_scaling_factor: float = 2.827
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 50000.0
    rope_factor: float = 32.0
    rope_original_len: int = 4096
    rope_beta_fast: float = 1.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_hf(cls, c: dict, dtype: Any = jnp.bfloat16) -> "DecoderConfig":
        """From a ``config.json`` of the ``deepseek_v3`` / ``kimi_k2`` kind.
        Where the file states a share (``n_routed_experts_published``,
        ``first_expert``), ``n_routed_experts`` is the count held here."""
        if c.get("n_group", 1) != 1 or c.get("topk_group", 1) != 1:
            raise ValueError("group-limited routing (n_group > 1) is not implemented")
        if c.get("scoring_func", "sigmoid") != "sigmoid" or c.get("hidden_act", "silu") != "silu":
            raise ValueError("only sigmoid scoring and silu experts are implemented")
        rs = c.get("rope_scaling") or {}
        return cls(
            vocab_size=c["vocab_size"], hidden_size=c["hidden_size"], n_layers=c["num_hidden_layers"],
            n_heads=c["num_attention_heads"], q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
            qk_nope_head_dim=c["qk_nope_head_dim"], qk_rope_head_dim=c["qk_rope_head_dim"],
            v_head_dim=c["v_head_dim"], intermediate_size=c["intermediate_size"],
            moe_intermediate_size=c["moe_intermediate_size"],
            n_routed_experts=c.get("n_routed_experts_published", c["n_routed_experts"]),
            first_expert=c.get("first_expert", 0), n_held=c["n_routed_experts"],
            n_shared_experts=c.get("n_shared_experts", 1), experts_per_token=c["num_experts_per_tok"],
            first_k_dense=c.get("first_k_dense_replace", 1),
            routed_scaling_factor=float(c.get("routed_scaling_factor", 1.0)),
            norm_topk_prob=bool(c.get("norm_topk_prob", True)), rms_norm_eps=float(c["rms_norm_eps"]),
            rope_theta=float(c["rope_theta"]), rope_factor=float(rs.get("factor", 1.0)),
            rope_original_len=int(rs.get("original_max_position_embeddings", c.get("max_position_embeddings", 4096))),
            rope_beta_fast=float(rs.get("beta_fast", 32)), rope_beta_slow=float(rs.get("beta_slow", 1)),
            rope_mscale=float(rs.get("mscale", 1)), rope_mscale_all_dim=float(rs.get("mscale_all_dim", 0)),
            dtype=dtype,
        )

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        """A cache entry's row in memory: the latent filled up with zeros to
        whole 128-lane tiles. At a width that is not one (576) the TPU's
        default layout makes the position the minor axis, and every step
        then transposes the whole cache to write one position and back (3 of
        a step's 9.8 ms at the published widths; my chip run, PR 32)."""
        return -(-self.latent_dim // 128) * 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def n_sparse_layers(self) -> int:
        return self.n_layers - self.first_k_dense


# ---------------------------------------------------------------- RoPE (YaRN)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_inv_freq(cfg: DecoderConfig) -> np.ndarray:
    """YaRN's blend of the base frequencies (kept where a dimension turns more
    than ``beta`` times over the original context) and the same divided by
    ``factor`` (interpolated where it turns less)."""
    dim = cfg.qk_rope_head_dim
    freq = cfg.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.rope_factor <= 1:
        return freq.astype(np.float32)

    def turns_dim(turns: float) -> float:
        return dim * math.log(cfg.rope_original_len / (turns * 2 * math.pi)) / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(turns_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(turns_dim(cfg.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (freq / cfg.rope_factor * (1.0 - keep) + freq * keep).astype(np.float32)


def softmax_scale(cfg: DecoderConfig) -> float:
    m = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) if cfg.rope_mscale_all_dim else 1.0
    return cfg.qk_head_dim ** -0.5 * m * m


def _rope_tables(cfg: DecoderConfig, positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    """cos and sin, float32, ``positions.shape + (rope/2,)``."""
    angle = positions.astype(jnp.float32)[..., None] * jnp.asarray(rope_inv_freq(cfg))
    scale = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale) / _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return jnp.cos(angle) * scale, jnp.sin(angle) * scale


def _rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate the pairs (2i, 2i+1) of the last axis; float32 in and out."""
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


# ------------------------------------------------------------------- pieces


def _rms(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _mm(spec: str, a: jax.Array, b: jax.Array, dtype: Any) -> jax.Array:
    """Operands in the compute type, float32 out."""
    precision = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype), preferred_element_type=jnp.float32,
                      precision=precision)


def _swiglu(x: jax.Array, p: dict, dtype: Any) -> jax.Array:
    h = jax.nn.silu(_mm("td,df->tf", x, p["w_gate"], dtype)) * _mm("td,df->tf", x, p["w_up"], dtype)
    return _mm("tf,fd->td", h, p["w_down"], dtype)


def route(lp: dict, x: jax.Array, cfg: DecoderConfig) -> tuple[jax.Array, jax.Array]:
    """``[T, k]`` chosen experts (of the published count) and their weights:
    selection by ``s + b``, weights from ``s``, normalised, scaled."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), lp["router"], precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + lp["router_bias"], cfg.experts_per_token)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg.routed_scaling_factor


def expert_rows(tokens: int, cfg: DecoderConfig, block: int) -> int:
    """Rows of the grouped product's buffer: every pair ``tokens`` could make
    with the experts held here, each expert's group padded to whole blocks."""
    pairs = tokens * min(cfg.experts_per_token, cfg.n_held)
    return -(-(pairs + cfg.n_held * (block - 1)) // block) * block


def _held_experts(lp: dict, x: jax.Array, valid: jax.Array, cfg: DecoderConfig, block: int):
    """The held experts' part of the layer for tokens ``x`` ``[T, d]``
    (``valid`` marks the real ones) and ``[pairs, padded rows, experts hit]``."""
    T, n = x.shape[0], cfg.n_held
    idx, w = route(lp, x, cfg)
    local = idx - cfg.first_expert
    held = (local >= 0) & (local < n) & valid[:, None]
    e = jnp.where(held, local, n).reshape(-1)  # n: not here; sorts last
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), cfg.experts_per_token)
    wf = jnp.where(held, w, 0.0).reshape(-1)
    counts = jnp.zeros((n + 1,), jnp.int32).at[e].add(1)
    padded = jnp.where(jnp.arange(n + 1) < n, -(-counts // block) * block, 0)
    start = jnp.cumsum(counts) - counts  # a group's first pair among the sorted pairs
    pstart = jnp.cumsum(padded) - padded  # and its first row in the buffer
    order = jnp.argsort(e, stable=True)
    es = e[order]
    rows = expert_rows(T, cfg, block)
    dest = jnp.where(es < n, pstart[es] + jnp.arange(es.shape[0], dtype=jnp.int32) - start[es], rows)
    tok_p = jnp.zeros((rows,), jnp.int32).at[dest].set(tok[order], mode="drop")
    w_p = jnp.zeros((rows,), jnp.float32).at[dest].set(wf[order], mode="drop")
    ends = jnp.cumsum(padded[:n])
    blk_expert = jnp.minimum(jnp.searchsorted(ends, jnp.arange(rows // block) * block, side="right"), n - 1)
    ex = lp["experts"]

    def one_block(b, y):
        ids = jax.lax.dynamic_slice(tok_p, (b * block,), (block,))
        ws = jax.lax.dynamic_slice(w_p, (b * block,), (block,))
        ep = {k: jax.lax.dynamic_index_in_dim(v, blk_expert[b], 0, keepdims=False) for k, v in ex.items()}
        return y.at[ids].add(_swiglu(x[ids], ep, cfg.dtype) * ws[:, None])

    y = jax.lax.fori_loop(0, ends[-1] // block, one_block, jnp.zeros((T, x.shape[1]), jnp.float32))
    stats = jnp.stack([jnp.sum(counts[:n]), ends[-1], jnp.sum(counts[:n] > 0)]).astype(jnp.int32)
    return y, stats


def _ffn(lp: dict, x: jax.Array, valid: jax.Array, cfg: DecoderConfig, block: int):
    """``x`` ``[T, d]`` after its norm -> the layer's feed-forward, float32."""
    if "router" not in lp:
        return _swiglu(x, lp, cfg.dtype), jnp.zeros((3,), jnp.int32)
    y, stats = _held_experts(lp, x, valid, cfg, block)
    return y + _swiglu(x, lp["shared"], cfg.dtype), stats


def _queries_and_latent(lp: dict, x: jax.Array, cos, sin, cfg: DecoderConfig):
    """``x [..., d]`` after its norm -> per-head queries (no-position part,
    rotated rotary part) and the token's cache entry ``[c_kv ; k_r ; 0...]``."""
    dt, H = cfg.dtype, cfg.n_heads
    cq = _rms(_mm("...d,dr->...r", x, lp["wq_a"], dt), lp["q_norm"], cfg.rms_norm_eps)
    q = _mm("...r,re->...e", cq, lp["wq_b"], dt).reshape(*x.shape[:-1], H, cfg.qk_head_dim)
    q_nope, q_rope = q[..., : cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]
    q_rope = _rope(q_rope, cos[..., None, :], sin[..., None, :])
    kv = _mm("...d,dr->...r", x, lp["wkv_a"], dt)
    c_kv = _rms(kv[..., : cfg.kv_lora_rank], lp["kv_norm"], cfg.rms_norm_eps)
    k_r = _rope(kv[..., cfg.kv_lora_rank:], cos, sin)
    fill = jnp.zeros(c_kv.shape[:-1] + (cfg.cache_width - cfg.latent_dim,), jnp.float32)
    return q_nope, q_rope, jnp.concatenate([c_kv, k_r, fill], axis=-1).astype(dt)


def _attend_prefill(lp: dict, x: jax.Array, cos, sin, cfg: DecoderConfig):
    """``x [R, L, d]`` -> attention output ``[R, L, d]`` float32 and the
    cache entries ``[R, L, cache_width]``. Keys and values are up-projected from the
    latents as the cache will hold them; queries go a block at a time
    against the keys at or before the block's end."""
    dt, H, kvr, nope = cfg.dtype, cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    R, L, _ = x.shape
    q_nope, q_rope, latent = _queries_and_latent(lp, x, cos, sin, cfg)
    kvb = _mm("rlc,ce->rle", latent[..., :kvr], lp["wkv_b"], dt).reshape(R, L, H, nope + cfg.v_head_dim)
    k_nope, v, k_r = kvb[..., :nope].astype(dt), kvb[..., nope:].astype(dt), latent[..., kvr : cfg.latent_dim]
    scale = softmax_scale(cfg)
    out = []
    for q0 in range(0, L, QUERY_BLOCK):
        q1 = min(L, q0 + QUERY_BLOCK)
        s = _mm("rqhd,rkhd->rhqk", q_nope[:, q0:q1], k_nope[:, :q1], dt)
        s = s + _mm("rqhd,rkd->rhqk", q_rope[:, q0:q1], k_r[:, :q1], dt)
        causal = jnp.arange(q1)[None, :] <= jnp.arange(q0, q1)[:, None]
        p = jax.nn.softmax(jnp.where(causal, s * scale, -1e30), axis=-1)
        out.append(_mm("rhqk,rkhd->rqhd", p, v[:, :q1], dt))
    ctx = jnp.concatenate(out, axis=1).reshape(R, L, H * cfg.v_head_dim)
    return _mm("rle,ed->rld", ctx, lp["wo"], dt), latent


def _attend_step(lp: dict, x: jax.Array, cache_l: jax.Array, slots, positions, cos, sin, cfg: DecoderConfig):
    """``x [R, d]``, one new token a row -> attention output ``[R, d]`` and
    the layer's cache with the rows' new entries. Scores are taken against the
    latents themselves: ``W_kvb``'s key half is folded into the query and its
    value half applied after the weighted sum."""
    dt, H, kvr, nope = cfg.dtype, cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q_nope, q_rope, latent = _queries_and_latent(lp, x, cos, sin, cfg)
    # the rows' slots are read as they were and the new entry set into the copy
    # read, so that the cache's own update has no other reader and stays in place
    # (a row at a time: a gather of the rows reads the whole layer)
    last = cache_l.shape[0] - 1
    lat = jnp.concatenate(
        [jax.lax.dynamic_slice_in_dim(cache_l, jnp.minimum(slots[r], last), 1) for r in range(x.shape[0])]
    )  # [R, cache_len, cache_width]
    lat = lat.at[jnp.arange(lat.shape[0]), positions].set(latent, mode="drop")
    cache_l = cache_l.at[slots, positions].set(latent, mode="drop")
    wkv_b = lp["wkv_b"].reshape(kvr, H, nope + cfg.v_head_dim)
    q_lat = _mm("rhd,chd->rhc", q_nope, wkv_b[..., :nope], dt)
    s = _mm("rhc,rkc->rhk", q_lat, lat[..., :kvr], dt)
    s = s + _mm("rhd,rkd->rhk", q_rope, lat[..., kvr : cfg.latent_dim], dt)
    seen = jnp.arange(lat.shape[1])[None, :] <= positions[:, None]
    p = jax.nn.softmax(jnp.where(seen[:, None, :], s * softmax_scale(cfg), -1e30), axis=-1)
    o_lat = _mm("rhk,rkc->rhc", p, lat[..., :kvr], dt)
    ctx = _mm("rhc,chd->rhd", o_lat, wkv_b[..., nope:], dt).reshape(x.shape[0], H * cfg.v_head_dim)
    return _mm("re,ed->rd", ctx, lp["wo"], dt), cache_l


def _head(params: dict, x: jax.Array, cfg: DecoderConfig):
    logits = _mm("rd,dv->rv", _rms(x, params["norm_f"], cfg.rms_norm_eps), params["head"], cfg.dtype)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits


def _decoder_prefill(params, cache, slots, ids, lengths, *, cfg: DecoderConfig):
    """``ids [R, L]`` prompts padded at the end, ``lengths [R]``, ``slots
    [R]`` (a slot past the cache drops the row's writes: padding rows) ->
    (``[R + 3]`` int32: the greedy next token of each row, then ``[pairs,
    padded rows, experts hit]`` over the sparse layers — what the host
    fetches, in one array; the tokens' logits ``[R, V]`` float32; the cache
    with the slots filled)."""
    R, L = ids.shape
    eps = cfg.rms_norm_eps
    cos, sin = _rope_tables(cfg, jnp.arange(L))
    valid = (jnp.arange(L)[None, :] < lengths[:, None]).reshape(-1)
    x = params["embed"][ids].astype(jnp.float32)
    cache = list(cache)
    stats = jnp.zeros((3,), jnp.int32)
    block = min(EXPERT_BLOCK, max(8, R * L))
    for l, lp in enumerate(params["layers"]):
        a, latent = _attend_prefill(lp, _rms(x, lp["attn_norm"], eps).astype(cfg.dtype), cos, sin, cfg)
        cache[l] = cache[l].at[slots, :L].set(latent, mode="drop")
        x = x + a
        h = _rms(x, lp["ffn_norm"], eps).astype(cfg.dtype).reshape(R * L, -1)
        f, st = _ffn(lp, h, valid, cfg, block)
        x = x + f.reshape(R, L, -1)
        stats = stats + st
    tokens, logits = _head(params, x[jnp.arange(R), lengths - 1], cfg)
    return jnp.concatenate([tokens, stats]), logits, cache


def _decoder_step(params, cache, rows, *, cfg: DecoderConfig):
    """One token a row. ``rows [3, R]`` int32 holds the rows' ``slots``, the
    ``ids`` they feed and their ``positions`` (one array: every transfer to
    the chip costs a step about a millisecond) -> (next tokens and expert
    counts ``[R + 3]``, the logits, the cache), as ``prefill`` gives them."""
    slots, ids, positions = rows
    eps = cfg.rms_norm_eps
    cos, sin = _rope_tables(cfg, positions)
    valid = slots < cache[0].shape[0]
    x = params["embed"][ids].astype(jnp.float32)
    cache = list(cache)
    stats = jnp.zeros((3,), jnp.int32)
    for l, lp in enumerate(params["layers"]):
        a, cache[l] = _attend_step(
            lp, _rms(x, lp["attn_norm"], eps).astype(cfg.dtype), cache[l], slots, positions, cos, sin, cfg
        )
        x = x + a
        f, st = _ffn(lp, _rms(x, lp["ffn_norm"], eps).astype(cfg.dtype), valid, cfg, 8)
        x = x + f
        stats = stats + st
    tokens, logits = _head(params, x, cfg)
    return jnp.concatenate([tokens, stats]), logits, cache


prefill = _dev_prof.traced_jit(
    "decoder.prefill", jax.jit(_decoder_prefill, static_argnames=("cfg",), donate_argnums=(1,))
)
step = _dev_prof.traced_jit(
    "decoder.step", jax.jit(_decoder_step, static_argnames=("cfg",), donate_argnums=(1,))
)


# ------------------------------------------------------------------- serving


def _row_buckets(n: int) -> tuple[int, ...]:
    out, b = [], 1
    while b < n:
        out.append(b)
        b *= 2
    return tuple(out + [n])


class JaxDecoder:
    """Parameters, cache and launch shapes of one served model.

    ``params`` is the tree ``prefill`` and ``step`` read (``embed``, ``head``,
    ``norm_f``, ``layers``: the names of ``_queries_and_latent``, ``_ffn`` and
    ``route``). A prefill launch pads its rows to a power of two (serving
    sends ``PREFILL_ROWS`` a launch) and its prompts to a multiple of
    ``LENGTH_STEP`` up to ``cache_len``; a step pads its rows to a power of
    two up to ``cache_rows``."""

    def __init__(self, cfg: DecoderConfig, params: dict, *, cache_rows: int = 16, cache_len: int = 4096):
        self.cfg, self.params = cfg, params
        self.cache_rows, self.cache_len = cache_rows, cache_len
        self.step_buckets = _row_buckets(cache_rows)
        step_len = min(LENGTH_STEP, cache_len)
        self.length_buckets = tuple(range(step_len, cache_len, step_len)) + (cache_len,)

    def new_cache(self) -> list:
        return [jnp.zeros((self.cache_rows, self.cache_len, self.cfg.cache_width), self.cfg.dtype)
                for _ in range(self.cfg.n_layers)]

    @staticmethod
    def _bucket(n: int, buckets: tuple[int, ...]) -> int:
        return next(b for b in buckets if b >= n)

    def run_prefill(self, cache: list, slots: list[int], rows: list[np.ndarray]):
        """One prefill launch. Returns (device ``[bucket + 3]`` tokens and
        expert counts, device logits, the new cache, padded length). The small
        arguments go up with the call; the ids have a ``device/put`` span."""
        R = 1 << (len(rows) - 1).bit_length()
        L = self._bucket(max(len(r) for r in rows), self.length_buckets)
        ids = np.zeros((R, L), np.int32)
        lengths = np.ones((R,), np.int32)
        for i, r in enumerate(rows):
            ids[i, : len(r)] = r
            lengths[i] = len(r)
        slot_arr = np.full((R,), self.cache_rows, np.int32)  # past the cache: a padding row writes nothing
        slot_arr[: len(rows)] = slots
        real = int(lengths[: len(rows)].sum())
        st = _dev_prof.stats()
        st.note_pad_tokens("decoder.prefill", real, R * L - real)
        # score entries a causal pass needs against those the query blocks compute
        need = sum(len(r) * (len(r) + 1) // 2 for r in rows)
        made = R * sum((min(L, q0 + QUERY_BLOCK) - q0) * min(L, q0 + QUERY_BLOCK) for q0 in range(0, L, QUERY_BLOCK))
        st.note_pad_tokens("decoder.prefill.scores", need, made - need)
        out, logits, cache = prefill(
            self.params, cache, slot_arr, _dev_prof.put(ids, "decoder.prompt_ids"), lengths, cfg=self.cfg
        )
        return out, logits, cache, L

    def run_step(self, cache: list, slots: list[int], ids: list[int], positions: list[int]):
        """One decode step for ``len(slots)`` live rows."""
        n = len(slots)
        R = self._bucket(n, self.step_buckets)
        arr = np.zeros((3, R), np.int32)
        arr[0] = self.cache_rows
        arr[0, :n], arr[1, :n], arr[2, :n] = slots, ids, positions
        st = _dev_prof.stats()
        st.note_pad_rows("decoder.step", n, R - n)
        seen = int(sum(positions)) + n  # cache entries the rows attend to, their new ones included
        st.note_pad_tokens("decoder.step", seen, n * self.cache_len - seen)
        out, logits, cache = step(self.params, cache, arr, cfg=self.cfg)
        return out, logits, cache, R

    def note_experts(self, counts: np.ndarray, tokens: int, label: str | None = None) -> None:
        """The grouped product's counters: pairs computed here (and the rows
        its blocks padded them to) over token-layers routed; under ``label``,
        the experts the launch touched and its own pairs."""
        st = _dev_prof.stats()
        st.note_pad_rows("decoder.experts", int(counts[0]), int(counts[1] - counts[0]))
        st.note_pad_tokens("decoder.experts", tokens * self.cfg.n_sparse_layers, 0)
        if label is not None:
            st.note_pad_rows(label, int(counts[2]), 0)
            st.note_pad_tokens(label, int(counts[0]), 0)

    def warm(self) -> None:
        """Compile every executable a server can ask for: each length bucket
        of a prefill of ``PREFILL_ROWS`` and each row bucket of a step, on a
        scratch cache with every slot past it, so nothing is written."""
        cache = self.new_cache()
        off = np.full((self.cache_rows,), self.cache_rows, np.int32)
        idle = np.zeros((3, self.cache_rows), np.int32)
        idle[0] = self.cache_rows
        R = PREFILL_ROWS
        for L in self.length_buckets:
            _o, _l, cache = prefill(self.params, cache, off[:R], np.zeros((R, L), np.int32),
                                    np.ones((R,), np.int32), cfg=self.cfg)
        for R in self.step_buckets:
            _o, _l, cache = step(self.params, cache, idle[:, :R], cfg=self.cfg)
        jax.block_until_ready(cache)


class _Row:
    __slots__ = ("handle", "slot", "position", "out", "max_tokens")

    def __init__(self, handle, slot: int, position: int, first: int, max_tokens: int):
        self.handle, self.slot, self.position = handle, slot, position
        self.out, self.max_tokens = [first], max_tokens


class DecodeSession:
    """Rows in flight over one cache: the ``RowStepper`` a dataflow node
    drives (``ops/microbatch.py``). ``admit`` prefills rows into free slots
    and they join the decoding rows at the next ``step``; a row leaves, and
    its slot is free again, when it has ``max_tokens`` tokens (greedy, no
    end-of-sequence id) or is cancelled. A row's tokens do not depend on
    which rows it shared its launches with."""

    def __init__(self, model: JaxDecoder):
        self.model = model
        self.cache = model.new_cache()
        self.free_slots = list(range(model.cache_rows))
        self.rows: dict[Any, _Row] = {}

    def free(self) -> int:
        return len(self.free_slots)

    def live(self) -> int:
        return len(self.rows)

    def admit(self, rows: list[tuple[Any, np.ndarray, int]]) -> list[tuple[Any, list[int]]]:
        """``rows``: (handle, prompt ids, max_tokens), at most ``free()`` of
        them. Returns the rows that are finished already (``max_tokens`` 1)."""
        from pathway_tpu import observability as _obs

        m = self.model
        limit = m.cache_len - 1
        done = []
        for lo in range(0, len(rows), PREFILL_ROWS):
            chunk = rows[lo : lo + PREFILL_ROWS]
            prompts = [np.asarray(ids, np.int32)[-limit:] for _h, ids, _n in chunk]
            slots = [self.free_slots.pop(0) for _ in chunk]
            tok = _obs.begin("generate/prefill")
            out, _logits, self.cache, L = m.run_prefill(self.cache, slots, prompts)
            got = _dev_prof.fetch(out, "decoder.first_tokens")
            real = sum(len(p) for p in prompts)
            if tok is not None:
                _obs.end(tok, {"pathway.rows": len(chunk), "pathway.real_tokens": real, "pathway.padded_len": L})
            m.note_experts(got[-3:], real)
            for (handle, _ids, max_tokens), slot, p, first in zip(chunk, slots, prompts, got.tolist()):
                row = _Row(handle, slot, len(p), first, min(max_tokens, m.cache_len - len(p)))
                self.rows[handle] = row
                if len(row.out) >= row.max_tokens:
                    done.append(self._leave(row))
        return done

    def step(self) -> list[tuple[Any, list[int]]]:
        """One decode step for every live row; returns the rows it finished."""
        from pathway_tpu import observability as _obs

        rows = list(self.rows.values())
        if not rows:
            return []
        tok = _obs.begin("generate/step")
        out, _logits, self.cache, R = self.model.run_step(
            self.cache, [r.slot for r in rows], [r.out[-1] for r in rows], [r.position for r in rows]
        )
        got = _dev_prof.fetch(out, "decoder.tokens")
        if tok is not None:
            _obs.end(tok, {"pathway.rows": len(rows), "pathway.bucket": R})
        self.model.note_experts(got[-3:], len(rows), "decoder.step.experts")
        done = []
        for row, t in zip(rows, got.tolist()):
            row.out.append(t)
            row.position += 1
            if len(row.out) >= row.max_tokens:
                done.append(self._leave(row))
        return done

    def cancel(self, handle) -> None:
        row = self.rows.get(handle)
        if row is not None:
            self._leave(row)

    def _leave(self, row: _Row) -> tuple[Any, list[int]]:
        del self.rows[row.handle]
        self.free_slots.append(row.slot)
        return row.handle, row.out


def generate(model: JaxDecoder, prompts: list[np.ndarray], max_tokens: list[int]) -> list[list[int]]:
    """Every prompt to its end, through a session of its own: the batch
    function of the chat's UDF, for a call that is not a stepping select
    (``chat.func``, an expression nested in another). No cell of the
    benchmark measures it."""
    session = DecodeSession(model)
    todo = list(enumerate(prompts))
    out: dict[int, list[int]] = {}
    while todo or session.live():
        take = min(session.free(), len(todo))
        finished = session.admit([(i, p, max_tokens[i]) for i, p in todo[:take]]) if take else []
        del todo[:take]
        for i, toks in finished + session.step():
            out[i] = toks
    return [out[i] for i in range(len(prompts))]


# --------------------------------------------------------------- the counts


def layer_params(cfg: DecoderConfig, sparse: bool, experts: int | None = None) -> int:
    """Parameters of one layer (``experts``: how many routed experts are
    counted; default the held ones)."""
    d, H = cfg.hidden_size, cfg.n_heads
    attn = (d * cfg.q_lora_rank + cfg.q_lora_rank * H * cfg.qk_head_dim + d * cfg.latent_dim
            + cfg.kv_lora_rank * H * (cfg.qk_nope_head_dim + cfg.v_head_dim) + H * cfg.v_head_dim * d)
    if not sparse:
        return attn + 3 * d * cfg.intermediate_size
    e = cfg.n_held if experts is None else experts
    return attn + d * cfg.n_routed_experts + 3 * d * cfg.moe_intermediate_size * (cfg.n_shared_experts + e)
