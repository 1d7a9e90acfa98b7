"""A decoder-only language model on the chip, served by ``prefill`` and
``step`` through a cache of slots: a layer is (a mixer, a feed-forward), each
read from ``DecoderConfig``.

Block ``l``: ``h = x + r * Mixer_l(RMSNorm(x))``, ``y = h + r * FFN_l(RMSNorm(
h))`` (``r`` the ``residual_multiplier``, 1 where a model has none); ``x_0 =
E[ids] * embedding_multiplier``; a final RMSNorm; ``logits = y W_head /
logits_scaling``, ``W_head`` the embedding's transpose where the two are tied.

- Mixers (``ops/mixers.py``, by ``DecoderConfig.mixers``): ``latent``
  (DeepSeek-V3's latent attention with YaRN), ``gqa`` (grouped-query attention
  without a position term), ``mamba2`` (a state-space layer: a recurrence over
  the positions, scanned in chunks by a prefill). Each kind declares what one
  cache slot holds for it: latents a position, keys and values a position, or
  a recurrent state and a convolution's tail that are constant in the
  prompt's length.
- Feed-forwards: a dense SwiGLU, or (a layer whose parameters hold a
  ``router``) routed experts: ``s = sigmoid(x W_r)`` in float32 over ALL the
  published experts, the ``experts_per_token`` largest ``s + b`` are chosen
  (``b`` a selection bias that never enters the weights), ``w_e = s_e /
  sum(s_chosen) * routed_scaling_factor``. The layer is told which experts
  it holds (``first_expert``, ``n_held``: one chip's share of an
  expert-parallel deployment); it gathers the (token, expert) pairs whose
  expert is here, sorts them by expert into blocks of equal size and runs the
  blocks that hold a pair as one grouped product (no pair is dropped: the
  buffer is sized for every pair the tokens could make). What absent experts
  would add is left out. A shared expert runs for every token.

bfloat16 weights and matmul operands by default; float32 for the residual
stream, RMSNorm's statistics, the router, softmax, RoPE, a recurrent state
with its update, and the logits.

``JaxDecoder`` owns the parameters, the cache (a layer's arrays are its
mixer's slot times ``cache_rows``; one free list of slots serves every layer,
so a row's latents, keys and values and recurrent states live under one slot
number, and so does its newest token, in one ``[cache_rows]`` array after the
layers' arrays) and the bucketing: a prefill launch pads to a row bucket and a
length bucket, a step to a row bucket, so a server asks for a small closed set
of executables and ``warm()`` compiles them all. A prefill writes all of a slot
that later steps read (a positional slot's stale entries past the row's
position are masked; a recurrent slot is overwritten whole, with the state
after the row's last real token), so a freed slot is handed on as it is.
``DecodeSession`` is what a dataflow node drives (``ops/microbatch.py``
``RowStepper``): rows join at a step boundary and leave when done; it keeps
one step of lookahead, launching step k+1 before it fetches step k.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from pathway_tpu.observability import device as _dev_prof
from pathway_tpu.ops.mixers import MIXERS, QUERY_BLOCK, _mm, _rms, rope_inv_freq, rope_tables, softmax_scale  # noqa: F401

#: rows of one expert block in the grouped product: a full MXU tile for a
#: prefill; a step's few rows take the smallest tile
EXPERT_BLOCK = 128
#: prompt lengths pad to a multiple of this (a multiple of a recurrent layer's scan chunk)
LENGTH_STEP = 512
#: rows of one prefill launch while serving. A prompt of a thousand tokens
#: fills the chip by itself, and every further row bucket multiplies what
#: ``warm()`` compiles by the length buckets; a launch of several rows has not
#: been timed on the chip (PERF.md, PR 32)
PREFILL_ROWS = 1
BOS = 1  # HashTokenizer: 0 pad, 1 [CLS], 2 [SEP]


@dataclass(frozen=True)
class DecoderConfig:
    """Shapes of the model as this process holds it. ``layer_types`` names
    each layer's mixer (empty: ``latent`` everywhere). ``n_routed_experts``
    is the router's width (the published count); ``first_expert`` and
    ``n_held`` say which of them live here; ``vocab_size`` is the rows of the
    embedding and of the head held here."""

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
    # a mixer a layer; the gqa mixer's shapes; the mamba2 mixer's
    layer_types: tuple[str, ...] = ()
    n_kv_heads: int = 0
    head_dim: int = 0
    attention_multiplier: float = 1.0
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # scalars on the residual stream, and whether the head is the embedding
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    tie_embeddings: bool = False

    @classmethod
    def from_hf(cls, c: dict, dtype: Any = jnp.bfloat16) -> "DecoderConfig":
        """From a ``config.json``, by its ``model_type``: ``granitemoehybrid``
        (state-space and attention layers by ``layer_types``), else the
        ``deepseek_v3`` / ``kimi_k2`` kind (latent attention everywhere)."""
        if c.get("model_type") == "granitemoehybrid":
            return cls._from_hybrid(c, dtype)
        return cls._from_latent(c, dtype)

    @classmethod
    def _from_hybrid(cls, c: dict, dtype: Any) -> "DecoderConfig":
        kinds = {"mamba": "mamba2", "attention": "gqa"}
        unknown = sorted(set(c["layer_types"]) - set(kinds))
        if unknown or len(c["layer_types"]) != c["num_hidden_layers"]:
            raise ValueError(f"layer_types names {unknown or 'another count than num_hidden_layers'}")
        if c.get("num_local_experts", 0) or c.get("num_experts_per_tok", 0):
            raise ValueError("routed experts beside recurrent layers are not implemented")
        if c.get("position_embedding_type", "nope") != "nope":
            raise ValueError("a position term in grouped-query attention (rope) is not implemented")
        if c.get("mamba_n_groups", 1) != 1 or c.get("mamba_proj_bias", False) or c.get("attention_bias", False):
            raise ValueError("grouped B/C (mamba_n_groups > 1) and projection biases are not implemented")
        if c.get("hidden_act", "silu") != "silu" or c.get("normalization_function", "rmsnorm") != "rmsnorm":
            raise ValueError("only silu and rmsnorm are implemented")
        if c["mamba_n_heads"] * c["mamba_d_head"] != c["mamba_expand"] * c["hidden_size"]:
            raise ValueError("mamba_n_heads * mamba_d_head is not mamba_expand * hidden_size")
        if not c.get("mamba_conv_bias", True):
            raise ValueError("a convolution without bias is not implemented")
        return cls(
            vocab_size=c["vocab_size"], hidden_size=c["hidden_size"], n_layers=c["num_hidden_layers"],
            layer_types=tuple(kinds[t] for t in c["layer_types"]), n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["hidden_size"] // c["num_attention_heads"],
            attention_multiplier=float(c["attention_multiplier"]), intermediate_size=c["shared_intermediate_size"],
            first_k_dense=c["num_hidden_layers"], n_routed_experts=0, n_held=0, n_shared_experts=0,
            experts_per_token=0, rms_norm_eps=float(c["rms_norm_eps"]), ssm_heads=c["mamba_n_heads"],
            ssm_head_dim=c["mamba_d_head"], ssm_state=c["mamba_d_state"], ssm_conv=c["mamba_d_conv"],
            ssm_chunk=c["mamba_chunk_size"], embedding_multiplier=float(c["embedding_multiplier"]),
            residual_multiplier=float(c["residual_multiplier"]), logits_scaling=float(c["logits_scaling"]),
            tie_embeddings=bool(c["tie_word_embeddings"]), dtype=dtype,
        )

    @classmethod
    def _from_latent(cls, c: dict, dtype: Any) -> "DecoderConfig":
        """Where the file states a share (``n_routed_experts_published``,
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
    def mixers(self) -> tuple[str, ...]:
        return self.layer_types or ("latent",) * self.n_layers

    @property
    def recurrent_layers(self) -> int:
        return sum(MIXERS[kind].recurrent for kind in self.mixers)

    @property
    def ssm_conv_dim(self) -> int:
        """Channels of the convolution: the heads' inputs, then ``B`` and ``C``."""
        return self.ssm_heads * self.ssm_head_dim + 2 * self.ssm_state

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


# ------------------------------------------------------------------- pieces


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


def _head(params: dict, x: jax.Array, cfg: DecoderConfig):
    h = _rms(x, params["norm_f"], cfg.rms_norm_eps)
    if cfg.tie_embeddings:
        logits = _mm("rd,vd->rv", h, params["embed"], cfg.dtype)
    else:
        logits = _mm("rd,dv->rv", h, params["head"], cfg.dtype)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits


def _embed(params: dict, ids: jax.Array, cfg: DecoderConfig) -> jax.Array:
    x = params["embed"][ids].astype(jnp.float32)
    return x * cfg.embedding_multiplier if cfg.embedding_multiplier != 1.0 else x


def _add(x: jax.Array, y: jax.Array, cfg: DecoderConfig) -> jax.Array:
    return x + (y * cfg.residual_multiplier if cfg.residual_multiplier != 1.0 else y)


def _decoder_prefill(params, cache, slots, ids, lengths, *, cfg: DecoderConfig):
    """``ids [R, L]`` prompts padded at the end, ``lengths [R]``, ``slots
    [R]`` (a slot past the cache drops the row's writes: padding rows) ->
    (``[R + 3]`` int32: the greedy next token of each row, then ``[pairs,
    padded rows, experts hit]`` over the sparse layers — what the host
    fetches, in one array; the tokens' logits ``[R, V]`` float32; the cache
    with the slots filled, the next token at each row's slot)."""
    R, L = ids.shape
    eps = cfg.rms_norm_eps
    rope = rope_tables(cfg, jnp.arange(L)) if "latent" in cfg.mixers else None
    valid = (jnp.arange(L)[None, :] < lengths[:, None]).reshape(-1)
    x = _embed(params, ids, cfg)
    cache = list(cache)
    stats = jnp.zeros((3,), jnp.int32)
    block = min(EXPERT_BLOCK, max(8, R * L))
    for l, (lp, kind) in enumerate(zip(params["layers"], cfg.mixers)):
        a, cache[l] = MIXERS[kind].prefill(
            lp, _rms(x, lp["attn_norm"], eps).astype(cfg.dtype), cache[l], slots, lengths, rope, cfg
        )
        x = _add(x, a, cfg)
        h = _rms(x, lp["ffn_norm"], eps).astype(cfg.dtype).reshape(R * L, -1)
        f, st = _ffn(lp, h, valid, cfg, block)
        x = _add(x, f.reshape(R, L, -1), cfg)
        stats = stats + st
    tokens, logits = _head(params, x[jnp.arange(R), lengths - 1], cfg)
    cache[-1] = cache[-1].at[slots].set(tokens, mode="drop")
    return jnp.concatenate([tokens, stats]), logits, cache


def _decoder_step(params, cache, rows, *, cfg: DecoderConfig):
    """One token a row. ``rows [3, R]`` int32 holds the rows' ``slots``, the
    ``ids`` they feed (below 0: the newest token at the row's slot, so a step
    can be launched before the one before it is fetched) and their
    ``positions`` (one array: every transfer to the chip costs a step about a
    millisecond) -> (next tokens and expert counts ``[R + 3]``, the logits,
    the cache), as ``prefill`` gives them."""
    slots, ids, positions = rows
    eps = cfg.rms_norm_eps
    rope = rope_tables(cfg, positions) if "latent" in cfg.mixers else None
    valid = slots < cache[0][0].shape[0]
    ids = jnp.where(ids < 0, cache[-1].at[slots].get(mode="clip"), ids)
    x = _embed(params, ids, cfg)
    cache = list(cache)
    stats = jnp.zeros((3,), jnp.int32)
    for l, (lp, kind) in enumerate(zip(params["layers"], cfg.mixers)):
        a, cache[l] = MIXERS[kind].step(
            lp, _rms(x, lp["attn_norm"], eps).astype(cfg.dtype), cache[l], slots, positions, rope, cfg
        )
        x = _add(x, a, cfg)
        f, st = _ffn(lp, _rms(x, lp["ffn_norm"], eps).astype(cfg.dtype), valid, cfg, 8)
        x = _add(x, f, cfg)
        stats = stats + st
    tokens, logits = _head(params, x, cfg)
    cache[-1] = cache[-1].at[slots].set(tokens, mode="drop")
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

    ``params`` is the tree ``prefill`` and ``step`` read (``embed``, ``head``
    unless the two are tied, ``norm_f``, ``layers``: a layer's ``attn_norm``
    and ``ffn_norm`` and the names of its mixer in ``ops/mixers.py``, of
    ``_ffn`` and of ``route``). The cache is a list with one tuple of arrays a
    layer, each ``cache_rows`` of what its mixer's ``slot`` declares: latents
    or keys and values over ``cache_len`` positions, or a recurrent state and
    a convolution's tail; after them one ``[cache_rows]`` int32 array, each
    slot's newest token. A prefill launch pads its rows to a power of two (serving
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
        return [tuple(jnp.zeros((self.cache_rows,) + shape, dtype)
                      for shape, dtype in MIXERS[kind].slot(self.cfg, self.cache_len))
                for kind in self.cfg.mixers] + [jnp.zeros((self.cache_rows,), jnp.int32)]

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
        if self.cfg.recurrent_layers:  # tokens a recurrent layer's chunks scan: the real ones, and their padding
            st.note_pad_tokens("decoder.prefill.scan", real, R * self.scan_chunks(L) * self.cfg.ssm_chunk - real)
        out, logits, cache = prefill(
            self.params, cache, slot_arr, _dev_prof.put(ids, "decoder.prompt_ids"), lengths, cfg=self.cfg
        )
        return out, logits, cache, L

    def scan_chunks(self, length: int) -> int:
        """Chunks a recurrent layer scans over a row of ``length`` positions
        (0 where the model has no such layer)."""
        return -(-length // self.cfg.ssm_chunk) if self.cfg.recurrent_layers else 0

    def run_step(self, cache: list, slots: list[int], ids: list[int], positions: list[int]):
        """One decode step for ``len(slots)`` live rows; an id below 0 feeds
        the newest token at the row's slot, as the launches before this one
        left it."""
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
    __slots__ = ("handle", "slot", "position", "out", "max_tokens", "due")

    def __init__(self, handle, slot: int, position: int, first: int, max_tokens: int):
        self.handle, self.slot, self.position = handle, slot, position  # position: what the next launch feeds
        self.out, self.max_tokens = [first], max_tokens
        self.due = 1  # tokens the row holds once every launched step is fetched


class DecodeSession:
    """Rows in flight over one cache: the ``RowStepper`` a dataflow node
    drives (``ops/microbatch.py``). ``admit`` prefills rows into free slots
    and they join the decoding rows at the next ``step``; a row leaves, and
    its slot is free again, when it has ``max_tokens`` tokens (greedy, no
    end-of-sequence id) or is cancelled. A row's tokens do not depend on
    which rows it shared its launches with.

    One step of lookahead: a step feeds each row the newest token at its slot
    on the chip, so ``step`` launches the next step for the rows whose budget
    goes on before it fetches the step launched last, and the chip runs it
    while the host books the tokens, yields and comes back. At most one
    launched step is unfetched. Every launch threads the cache through the
    one before it, so a row cancelled under that step is skipped when it is
    booked, and its slot can be prefilled again at once."""

    def __init__(self, model: JaxDecoder):
        self.model = model
        self.cache = model.new_cache()
        self.free_slots = list(range(model.cache_rows))
        self.rows: dict[Any, _Row] = {}
        #: the step launched and not yet fetched: (device tokens, rows stepped, bucket)
        self._pending: tuple[Any, list[_Row], int] | None = None

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
            m.note_experts(got[-3:], real)
            for (handle, _ids, max_tokens), slot, p, first in zip(chunk, slots, prompts, got.tolist()):
                row = _Row(handle, slot, len(p), first, min(max_tokens, m.cache_len - len(p)))
                self.rows[handle] = row
                if len(row.out) >= row.max_tokens:
                    done.append(self._leave(row))
            if tok is not None:
                _obs.end(tok, {"pathway.rows": len(chunk), "pathway.real_tokens": real, "pathway.padded_len": L,
                               "pathway.scan_chunks": len(chunk) * m.scan_chunks(L), "pathway.live": len(self.rows)})
        return done

    def step(self) -> list[tuple[Any, list[int]]]:
        """Fetch one decode step of the live rows, the next one launched
        before it; returns the rows the fetched step finished."""
        from pathway_tpu import observability as _obs

        if self._pending is None and not self.rows:
            return []
        tok = _obs.begin("generate/step")
        out, stepped, R = self._pending or self._launch(list(self.rows.values()), ahead=False)
        more = [r for r in self.rows.values() if r.due < r.max_tokens]
        self._pending = self._launch(more, ahead=True) if more else None
        got = _dev_prof.fetch(out, "decoder.tokens")
        self.model.note_experts(got[-3:], len(stepped), "decoder.step.experts")
        done = []
        for row, t in zip(stepped, got.tolist()):
            if self.rows.get(row.handle) is not row:
                continue  # cancelled while the step ran
            row.out.append(t)
            if len(row.out) >= row.max_tokens:
                done.append(self._leave(row))
        if tok is not None:
            _obs.end(tok, {"pathway.rows": len(stepped), "pathway.bucket": R, "pathway.live": len(self.rows),
                           "pathway.ahead": int(self._pending is not None)})
        return done

    def _launch(self, rows: list[_Row], ahead: bool) -> tuple[Any, list[_Row], int]:
        """One step of ``rows``, each fed the newest token at its slot;
        counted as launched while an earlier step was unfetched or not."""
        _dev_prof.stats().note_pad_rows("decoder.step.ahead", int(ahead), int(not ahead))
        out, _logits, self.cache, R = self.model.run_step(
            self.cache, [r.slot for r in rows], [-1] * len(rows), [r.position for r in rows]
        )
        for r in rows:
            r.position += 1
            r.due += 1
        return out, rows, R

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


def layer_params(cfg: DecoderConfig, sparse: bool = False, experts: int | None = None,
                 mixer: str = "latent") -> int:
    """Parameters of one layer's mixer and feed-forward (``experts``: how
    many routed experts are counted; default the held ones)."""
    d, H = cfg.hidden_size, cfg.n_heads
    if mixer == "latent":
        mix = (d * cfg.q_lora_rank + cfg.q_lora_rank * H * cfg.qk_head_dim + d * cfg.latent_dim
               + cfg.kv_lora_rank * H * (cfg.qk_nope_head_dim + cfg.v_head_dim) + H * cfg.v_head_dim * d)
    elif mixer == "gqa":
        mix = 2 * d * cfg.head_dim * (H + cfg.n_kv_heads)
    else:  # mamba2: the two projections; the convolution, dt_bias, A_log, D and the gated norm's gain
        inner = cfg.ssm_heads * cfg.ssm_head_dim
        mix = (d * (inner + cfg.ssm_conv_dim + cfg.ssm_heads) + inner * d
               + (cfg.ssm_conv + 1) * cfg.ssm_conv_dim + 3 * cfg.ssm_heads + inner)
    if not sparse:
        return mix + 3 * d * cfg.intermediate_size
    e = cfg.n_held if experts is None else experts
    return mix + d * cfg.n_routed_experts + 3 * d * cfg.moe_intermediate_size * (cfg.n_shared_experts + e)
