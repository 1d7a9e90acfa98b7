"""The mixers a decoder layer can have. ``ops/decoder.py`` reads a layer's
kind from ``DecoderConfig.mixers`` and goes through ``MIXERS[kind]``: the
arrays one cache slot holds (``slot``), a whole prompt (``prefill``) and one
new token a row (``step``). ``cfg`` is the ``DecoderConfig``; nothing here
knows a model by name.

- ``latent`` (DeepSeek-V3's): ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb``
  gives every head a no-position part and a rotary part; ``[c_kv ; k_r] = x
  W_kva``, ``c_kv = RMSNorm(c_kv)``, ``k_r`` one rotary vector shared by all
  heads (YaRN frequencies); ``[k_nope ; v] = c_kv W_kvb``. **The slot holds
  ``c_kv`` after its norm and ``k_r`` after RoPE**, ``kv_lora_rank +
  qk_rope_head_dim`` numbers a position. Prefill up-projects keys and values
  from the latents; a step never does: it folds ``W_kvb``'s key half into the
  query, scores against ``c_kv`` itself and applies the value half after the
  weighted sum. Both are the same function of the same weights.
- ``gqa``: grouped-query attention without a position term. ``q = x W_q``
  (``n_heads`` of ``head_dim``), ``k, v = x W_k, x W_v`` (``n_kv_heads``),
  causal softmax of ``q k^T * attention_multiplier``, each key-value head
  serving ``n_heads / n_kv_heads`` query heads, then ``W_o``. The slot holds
  ``[k ; v]`` a position, ``2 * n_kv_heads * head_dim`` wide; a step reads a
  row's slot where it lies and only up to the row's position (a Pallas
  kernel over blocks of positions).
- ``mamba2``: ``[z ; xBC ; dt] = x W_in``; ``xBC = silu(causal depthwise
  conv1d(xBC, width ssm_conv) + b)`` splits into ``x [H, P]``, ``B [N]``, ``C
  [N]`` (one group); ``D_t = softplus(dt + dt_bias)`` and ``A = -exp(A_log)``
  a head; ``S_t = exp(D_t A) S_{t-1} + D_t x_t (x) B_t``; ``y_t = S_t C_t + D
  x_t``; ``RMSNorm(y * silu(z))`` over all heads, then ``W_out``. **The slot
  holds ``S`` after the row's last token (``[H, P, N]``) and the last
  ``ssm_conv - 1`` inputs of the convolution**: constant in the prompt's
  length and all of the row's history, so unlike a positional slot it cannot
  be handed on dirty. A step is the recurrence itself, on the live rows'
  slots where they lie (two Pallas kernels whose blocks are the rows' own
  slots: nothing is gathered, nothing scattered, a padding row moves
  nothing). A prefill scans in chunks of ``ssm_chunk`` (the same function
  associated differently): within a chunk a masked matrix product, between
  chunks a short scan over the chunks' states; a padding position takes
  ``D_t = 0`` (decay 1, no input), so the state a padded prefill leaves is
  the one after the last real token. The slot keeps ``S`` as ``[N, H * P]``
  (the heads' numbers along the lanes), so that what varies by head is a row
  vector and ``B``, ``C`` are columns.

Matmul operands are in ``cfg.dtype``; the recurrent state, its update and its
read-out, ``D_t``, ``exp(D_t A)``, softmax, RoPE and every norm's statistics
are float32. A recurrent slot is stored as ``STATE_DTYPE``, float32: what the
configuration guarantees, so no configuration key and no argument lowers it
(a control or a test patches the name to show that a lower one is caught).
"""

from __future__ import annotations

import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: queries per attention block in prefill (the score matrix is never whole)
QUERY_BLOCK = 256
#: what a recurrent slot (the state and the convolution's tail) is stored as
STATE_DTYPE = jnp.float32
#: cache positions one grid step of ``attend_slots`` holds (1 MB of keys and values at 8 kv heads of 64, bfloat16)
KEY_BLOCK = 512
#: lanes of a recurrent state one grid step of ``state_step`` holds (``[N, lanes]`` float32: 0.5 MB at N = 128)
STATE_LANES = 1024


def _rms(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _mm(spec: str, a: jax.Array, b: jax.Array, dtype: Any) -> jax.Array:
    """Operands in the compute type, float32 out."""
    precision = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype), preferred_element_type=jnp.float32,
                      precision=precision)


def _interpret() -> bool:
    """Off the chip a kernel runs in Pallas's interpreter (the CPU tests)."""
    return jax.default_backend() != "tpu"


def _rows_of(arr: jax.Array, slots: jax.Array) -> jax.Array:
    """The rows' slots of one cache array as they are, ``[R, ...]``: a row at
    a time (a gather of the rows reads the whole array); a slot past the cache
    (a padding row) reads the last one."""
    last = arr.shape[0] - 1
    return jnp.concatenate(
        [jax.lax.dynamic_slice_in_dim(arr, jnp.minimum(slots[r], last), 1) for r in range(slots.shape[0])]
    )


def _causal_blocks(scores: Callable, weigh: Callable, L: int, scale: float) -> jax.Array:
    """Causal softmax attention a block of queries at a time against the keys
    at or before the block's end: ``scores(q0, q1)`` gives ``[..., q, k]``
    over keys ``[:q1]``, ``weigh(p, q1)`` the block's output, query axis 1."""
    out = []
    for q0 in range(0, L, QUERY_BLOCK):
        q1 = min(L, q0 + QUERY_BLOCK)
        causal = jnp.arange(q1)[None, :] <= jnp.arange(q0, q1)[:, None]
        out.append(weigh(jax.nn.softmax(jnp.where(causal, scores(q0, q1) * scale, -1e30), axis=-1), q1))
    return jnp.concatenate(out, axis=1)


# ---------------------------------------------------------------- RoPE (YaRN)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_inv_freq(cfg) -> np.ndarray:
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


def softmax_scale(cfg) -> float:
    m = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) if cfg.rope_mscale_all_dim else 1.0
    return cfg.qk_head_dim ** -0.5 * m * m


def rope_tables(cfg, positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    """cos and sin, float32, ``positions.shape + (rope/2,)``."""
    angle = positions.astype(jnp.float32)[..., None] * jnp.asarray(rope_inv_freq(cfg))
    scale = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale) / _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return jnp.cos(angle) * scale, jnp.sin(angle) * scale


def _rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate the pairs (2i, 2i+1) of the last axis; float32 in and out."""
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


# -------------------------------------------------------------------- latent


def _queries_and_latent(lp: dict, x: jax.Array, cos, sin, cfg):
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


def _latent_slot(cfg, cache_len: int):
    return (((cache_len, cfg.cache_width), cfg.dtype),)


def _latent_prefill(lp: dict, x: jax.Array, arrays, slots, lengths, rope, cfg):
    """``x [R, L, d]`` -> attention output ``[R, L, d]`` float32; the slots
    take the entries ``[R, L, cache_width]``. Keys and values are up-projected
    from the latents as the cache will hold them."""
    dt, H, kvr, nope = cfg.dtype, cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    (cache_l,) = arrays
    R, L, _ = x.shape
    q_nope, q_rope, latent = _queries_and_latent(lp, x, *rope, cfg)
    kvb = _mm("rlc,ce->rle", latent[..., :kvr], lp["wkv_b"], dt).reshape(R, L, H, nope + cfg.v_head_dim)
    k_nope, v, k_r = kvb[..., :nope].astype(dt), kvb[..., nope:].astype(dt), latent[..., kvr : cfg.latent_dim]
    ctx = _causal_blocks(
        lambda q0, q1: (_mm("rqhd,rkhd->rhqk", q_nope[:, q0:q1], k_nope[:, :q1], dt)
                        + _mm("rqhd,rkd->rhqk", q_rope[:, q0:q1], k_r[:, :q1], dt)),
        lambda p, q1: _mm("rhqk,rkhd->rqhd", p, v[:, :q1], dt), L, softmax_scale(cfg),
    ).reshape(R, L, H * cfg.v_head_dim)
    return _mm("rle,ed->rld", ctx, lp["wo"], dt), (cache_l.at[slots, :L].set(latent, mode="drop"),)


def _latent_step(lp: dict, x: jax.Array, arrays, slots, positions, rope, cfg):
    """``x [R, d]``, one new token a row -> attention output ``[R, d]``; the
    slots take the rows' new entries. Scores are taken against the latents
    themselves: ``W_kvb``'s key half is folded into the query and its value
    half applied after the weighted sum."""
    dt, H, kvr, nope = cfg.dtype, cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    (cache_l,) = arrays
    q_nope, q_rope, latent = _queries_and_latent(lp, x, *rope, cfg)
    # the rows' slots are read as they were and the new entry set into the copy
    # read, so that the cache's own update has no other reader and stays in place
    lat = _rows_of(cache_l, slots)  # [R, cache_len, cache_width]
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
    return _mm("re,ed->rd", ctx, lp["wo"], dt), (cache_l,)


# ----------------------------------------------------------------------- gqa


def _gqa_project(lp: dict, x: jax.Array, cfg):
    """``x [..., d]`` -> queries ``[..., kv head, queries of it, head_dim]``
    and the position's cache entry ``[k ; v]`` (one row of whole lane tiles)."""
    dt, KV = cfg.dtype, cfg.n_kv_heads
    q = _mm("...d,de->...e", x, lp["wq"], dt).reshape(*x.shape[:-1], KV, cfg.n_heads // KV, cfg.head_dim)
    kv = jnp.concatenate([_mm("...d,de->...e", x, lp["wk"], dt), _mm("...d,de->...e", x, lp["wv"], dt)], axis=-1)
    return q.astype(dt), kv.astype(dt)


def _keys_values(kv: jax.Array, cfg):
    k, v = jnp.split(kv, 2, axis=-1)
    shape = kv.shape[:-1] + (cfg.n_kv_heads, cfg.head_dim)
    return k.reshape(shape), v.reshape(shape)


def _gqa_slot(cfg, cache_len: int):
    return (((cache_len, 2 * cfg.n_kv_heads * cfg.head_dim), cfg.dtype),)


def _gqa_prefill(lp: dict, x: jax.Array, arrays, slots, lengths, rope, cfg):
    dt = cfg.dtype
    (cache_l,) = arrays
    R, L, _ = x.shape
    q, kv = _gqa_project(lp, x, cfg)
    k, v = _keys_values(kv, cfg)
    ctx = _causal_blocks(
        lambda q0, q1: _mm("rqgmd,rkgd->rgmqk", q[:, q0:q1], k[:, :q1], dt),
        lambda p, q1: _mm("rgmqk,rkgd->rqgmd", p, v[:, :q1], dt), L, cfg.attention_multiplier,
    ).reshape(R, L, cfg.n_heads * cfg.head_dim)
    return _mm("rle,ed->rld", ctx, lp["wo"], dt), (cache_l.at[slots, :L].set(kv, mode="drop"),)


def attend_slots(cache_l: jax.Array, slots: jax.Array, positions: jax.Array, q: jax.Array, scale: float, dtype: Any):
    """One query position a row against the keys and values its slot holds up
    to its position, read where they lie: ``cache_l [rows, cache_len, 2 E]``
    (``[k ; v]`` a position, ``E`` = kv heads x head_dim), ``q [R, heads, E]``
    with each head's query in its kv head's lanes and zeros elsewhere ->
    ``[R, heads, E]`` float32, each head's output in those same lanes. A grid
    step holds ``KEY_BLOCK`` positions of one row; blocks past the row's
    position are neither fetched nor computed (softmax runs over the blocks)."""
    rows, cache_len, width = cache_l.shape
    R, heads, E = q.shape
    block = min(KEY_BLOCK, cache_len)
    if cache_len % block:
        raise ValueError(f"a cache of {cache_len} positions does not divide into blocks of {block}")
    precision = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None

    def body(slots_ref, pos_ref, q_ref, kv_ref, o_ref, m_ref, l_ref, acc_ref):
        r, i = pl.program_id(0), pl.program_id(1)
        pos = pos_ref[r]

        @pl.when(i == 0)
        def _():
            m_ref[...] = jnp.full(m_ref.shape, -1e30, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        @pl.when(i * block <= pos)
        def _():
            k, v = kv_ref[0, :, :E], kv_ref[0, :, E:]
            sc = jax.lax.dot_general(q_ref[0], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
                                     precision=precision) * scale  # [heads, block]
            at = i * block + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            sc = jnp.where(at <= pos, sc, -1e30)
            m = jnp.maximum(m_ref[...], jnp.max(sc, axis=-1, keepdims=True))
            w = jnp.exp(sc - m)
            keep = jnp.exp(m_ref[...] - m)
            l_ref[...] = keep * l_ref[...] + jnp.sum(w, axis=-1, keepdims=True)
            acc_ref[...] = keep * acc_ref[...] + jax.lax.dot_general(
                w.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32, precision=precision)
            m_ref[...] = m

        @pl.when(i == pl.num_programs(1) - 1)
        def _():
            o_ref[0] = acc_ref[...] / l_ref[...]

    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(R, cache_len // block),
            in_specs=[
                pl.BlockSpec((1, heads, E), lambda r, i, s, p: (r, 0, 0)),
                pl.BlockSpec((1, block, width),
                             lambda r, i, s, p: (jnp.minimum(s[r], rows - 1), jnp.minimum(i, p[r] // block), 0)),
            ],
            out_specs=pl.BlockSpec((1, heads, E), lambda r, i, s, p: (r, 0, 0)),
            scratch_shapes=[pltpu.VMEM((heads, 1), jnp.float32), pltpu.VMEM((heads, 1), jnp.float32),
                            pltpu.VMEM((heads, E), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((R, heads, E), jnp.float32),
        interpret=_interpret(),
    )(slots, positions, q.astype(dtype), cache_l)


def _gqa_step(lp: dict, x: jax.Array, arrays, slots, positions, rope, cfg):
    dt, KV, hd = cfg.dtype, cfg.n_kv_heads, cfg.head_dim
    (cache_l,) = arrays
    R = x.shape[0]
    q, kv = _gqa_project(lp, x, cfg)  # q [R, kv head, queries of it, head_dim]
    cache_l = cache_l.at[slots, positions].set(kv, mode="drop")
    # a head's query in its kv head's lanes of the slot's row, zeros in the others': one product scores every head
    own = jnp.eye(KV, dtype=dt)
    spread = jnp.einsum("rgmd,gh->rgmhd", q, own).reshape(R, cfg.n_heads, KV * hd)
    out = attend_slots(cache_l, slots, jnp.minimum(positions, cache_l.shape[1] - 1), spread,
                       cfg.attention_multiplier, dt).reshape(R, KV, cfg.n_heads // KV, KV, hd)
    ctx = jnp.einsum("rgmhd,gh->rgmd", out, own.astype(jnp.float32)).reshape(R, cfg.n_heads * hd)
    return _mm("re,ed->rd", ctx, lp["wo"], dt), (cache_l,)


# -------------------------------------------------------------------- mamba2


def _ssm_project(lp: dict, x: jax.Array, cfg):
    """``x [..., d]`` -> ``z [..., H * P]``, the convolution's input ``xBC
    [..., H * P + 2 N]`` and ``dt [..., H]`` before its bias, float32."""
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    u = _mm("...d,de->...e", x, lp["w_in"], cfg.dtype)
    return u[..., :inner], u[..., inner : inner + cfg.ssm_conv_dim], u[..., inner + cfg.ssm_conv_dim:]


def _ssm_inputs(xbc: jax.Array, cfg):
    """After the convolution and silu: ``x [..., H, P]``, ``B``, ``C [..., N]``."""
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    return xbc[..., : H * P].reshape(*xbc.shape[:-1], H, P), xbc[..., H * P : H * P + N], xbc[..., H * P + N:]


def _gated_out(lp: dict, y: jax.Array, z: jax.Array, cfg) -> jax.Array:
    """Gate first, then the norm over all heads (one group), then ``W_out``."""
    g = y.reshape(z.shape) * jax.nn.silu(z)
    return _mm("...e,ed->...d", _rms(g, lp["gate_norm"], cfg.rms_norm_eps), lp["w_out"], cfg.dtype)


def scan_chunked(x, dt, A, B, C, chunk: int, dtype: Any):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t`` from
    ``S = 0`` over ``x [R, L, H, P]``, ``dt [R, L, H]``, ``A [H]``, ``B, C
    [R, L, N]`` (float32) -> ``y [R, L, H, P]`` and the state after position
    ``L - 1``, float32. Within a chunk ``y_q = sum_{s <= q} exp(a_q - a_s)
    (C_q . B_s) dt_s x_s`` (``a`` the running sum of ``dt A``) is one masked
    matrix product with operands in ``dtype``; the chunks' own states, the
    scan over them and their read-out stay float32."""
    R, L, H, P = x.shape
    f32 = jnp.float32
    pad = -L % chunk
    if pad:  # dt = 0: a position that decays nothing and adds nothing
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (x, dt, B, C))
    n = (L + pad) // chunk
    x, dt, B, C = (a.reshape(R, n, chunk, *a.shape[2:]) for a in (x, dt, B, C))
    run = jnp.cumsum(dt * A, axis=2)  # [R, n, Q, H], falling
    by_head = run.transpose(0, 1, 3, 2)  # [R, n, H, Q]
    xd = x * dt[..., None]
    # within a chunk
    later = jnp.tril(jnp.ones((chunk, chunk), bool))  # [q, s]: s at or before q
    decay = jnp.exp(jnp.where(later, by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
    weights = _mm("rnqk,rnsk->rnqs", C, B, dtype)[:, :, None] * decay  # [R, n, H, Q, Q]
    y = _mm("rnhqs,rnshp->rnqhp", weights, xd, dtype)
    # a chunk's own state at its end, then the states the chunks begin from
    to_end = jnp.exp(run[:, :, -1:, :] - run)  # [R, n, Q, H]
    own = _mm("rnshp,rnsk->rnhpk", xd * to_end[..., None], B, f32)

    def carry(S, chunk_in):
        decay_all, mine = chunk_in
        return S * decay_all[..., None, None] + mine, S

    last, begin = jax.lax.scan(
        carry, jnp.zeros((R, H, P, B.shape[-1]), f32),
        (jnp.exp(run[:, :, -1, :]).transpose(1, 0, 2), own.transpose(1, 0, 2, 3, 4)),
    )
    y = y + _mm("rnqk,rnhpk->rnqhp", C, begin.transpose(1, 0, 2, 3, 4), f32) * jnp.exp(run)[..., None]
    return y.reshape(R, L + pad, H, P)[:, :L], last


def _slot_blocks(slots_ref, live_ref, r, last: int):
    """The slot a grid step's blocks lie in: row ``r``'s; for a padding row
    (the live rows come first) the last live row's again, so that the
    pipeline neither fetches nor writes anything for it."""
    row = jnp.maximum(jnp.minimum(r, live_ref[0] - 1), 0)
    return jnp.minimum(slots_ref[row], last)


def _live_rows_only(update: Callable, row_axis: int) -> Callable:
    """The body of a kernel over the rows' slots: ``update(slot_ref, *inputs,
    out_ref, y_ref)`` runs for the live rows; a padding row's ``y`` is zeros
    and its revisited slot block stays as the last live row left it; a launch
    with no live row hands its one visited block back as it found it."""

    def body(slots_ref, live_ref, slot_ref, *refs):
        out_ref, y_ref = refs[-2:]
        r, live = pl.program_id(row_axis), live_ref[0]

        @pl.when(r < live)
        def _():
            update(slot_ref, *refs)

        @pl.when(r >= live)
        def _():
            y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

        @pl.when((live == 0) & (r == 0))
        def _():
            out_ref[...] = slot_ref[...]

    return body


def conv_step(conv: jax.Array, slots: jax.Array, live: jax.Array, x: jax.Array, w: jax.Array, b: jax.Array):
    """One position of the causal depthwise convolution for the live rows, on
    their slots: ``conv [rows, held, C]`` holds each row's last ``K - 1``
    inputs in its first rows (``_mamba2_slot``), ``x [R, C]`` the new ones,
    ``w [K, C]`` -> (the convolution's output ``[R, C]`` float32, before its
    activation; ``conv`` with the live rows' tails moved on by one position)."""
    rows, held, C = conv.shape
    R, tail = x.shape[0], w.shape[0] - 1

    def update(t_ref, x_ref, w_ref, b_ref, o_ref, y_ref):
        acc = x_ref[0] * w_ref[tail : tail + 1] + b_ref[...]
        for j in range(tail):
            acc = acc + t_ref[0, j : j + 1].astype(jnp.float32) * w_ref[j : j + 1]
        y_ref[0] = acc
        for j in range(tail - 1):
            o_ref[0, j : j + 1] = t_ref[0, j + 1 : j + 2]
        o_ref[0, tail - 1 : tail] = x_ref[0].astype(o_ref.dtype)
        if held > tail:  # the rest of the tile holds nothing
            o_ref[0, tail:] = jnp.zeros((held - tail, C), o_ref.dtype)

    slot = pl.BlockSpec((1, held, C), lambda r, s, n: (_slot_blocks(s, n, r, rows - 1), 0, 0))
    row = pl.BlockSpec((1, 1, C), lambda r, s, n: (r, 0, 0))
    out, y = pl.pallas_call(
        _live_rows_only(update, 0),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(R,),
            in_specs=[slot, row, pl.BlockSpec((tail + 1, C), lambda r, s, n: (0, 0)),
                      pl.BlockSpec((1, C), lambda r, s, n: (0, 0))],
            out_specs=[slot, row],
        ),
        out_shape=[jax.ShapeDtypeStruct(conv.shape, conv.dtype), jax.ShapeDtypeStruct((R, 1, C), jnp.float32)],
        input_output_aliases={2: 0},
        interpret=_interpret(),
    )(slots, live.reshape(1), conv, x[:, None, :], w, b[None, :])
    return y[:, 0], out


def state_step(ssm: jax.Array, slots: jax.Array, live: jax.Array, decay, u, B, C):
    """``S <- decay * S + B (x) u`` and ``y = S C`` for the live rows, on
    their slots: ``ssm [rows, N, W]`` (``W`` the heads' ``H * P`` numbers),
    ``decay, u [R, W]``, ``B, C [R, N]`` float32 -> (``y [R, W]`` float32,
    ``ssm`` with the live rows' states moved on). The state a row's next step
    reads is the one this step read out (rounded to the slot's type first)."""
    rows, N, W = ssm.shape
    R = decay.shape[0]
    lanes = min(W, STATE_LANES)
    if W % lanes:
        raise ValueError(f"a recurrent state of {W} lanes does not divide into blocks of {lanes}")

    def update(s_ref, decay_ref, u_ref, b_ref, c_ref, o_ref, y_ref):
        S = s_ref[0].astype(jnp.float32) * decay_ref[0] + b_ref[0] * u_ref[0]  # [N, lanes]
        S = S.astype(o_ref.dtype)
        o_ref[0] = S
        y_ref[0] = jnp.sum(S.astype(jnp.float32) * c_ref[0], axis=0, keepdims=True)

    slot = pl.BlockSpec((1, N, lanes), lambda j, r, s, n: (_slot_blocks(s, n, r, rows - 1), 0, j))
    row = pl.BlockSpec((1, 1, lanes), lambda j, r, s, n: (r, 0, j))
    column = pl.BlockSpec((1, N, 1), lambda j, r, s, n: (r, 0, 0))
    out, y = pl.pallas_call(
        _live_rows_only(update, 1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(W // lanes, R),  # the rows innermost: a padding row revisits the block before it
            in_specs=[slot, row, row, column, column], out_specs=[slot, row],
        ),
        out_shape=[jax.ShapeDtypeStruct(ssm.shape, ssm.dtype), jax.ShapeDtypeStruct((R, 1, W), jnp.float32)],
        input_output_aliases={2: 0},
        interpret=_interpret(),
    )(slots, live.reshape(1), ssm, decay[:, None, :], u[:, None, :], B[:, :, None], C[:, :, None])
    return y[:, 0], out


def _mamba2_slot(cfg, cache_len: int):
    """The state as ``[N, H * P]``; the convolution's last ``K - 1`` inputs in
    the first rows of a whole sublane tile (at 3 rows the TPU lays the array
    out with the slots second, and every step copies it whole there and back)."""
    return (((cfg.ssm_state, cfg.ssm_heads * cfg.ssm_head_dim), STATE_DTYPE),
            ((-(-(cfg.ssm_conv - 1) // 8) * 8, cfg.ssm_conv_dim), STATE_DTYPE))


def _mamba2_prefill(lp: dict, x: jax.Array, arrays, slots, lengths, rope, cfg):
    ssm, conv = arrays
    R, L, _ = x.shape
    K = cfg.ssm_conv
    z, xbc, dt = _ssm_project(lp, x, cfg)
    before = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))  # zeros before the row's start
    # the convolution's last K - 1 real inputs: positions lengths - (K - 1) ... lengths - 1
    tail = before[jnp.arange(R)[:, None], lengths[:, None] + jnp.arange(K - 1)[None, :]]
    xbc = sum(before[:, j : j + L] * lp["conv_w"][j] for j in range(K))
    xs, B, C = _ssm_inputs(jax.nn.silu(xbc + lp["conv_b"]), cfg)
    real = jnp.arange(L)[None, :] < lengths[:, None]
    dt = jnp.where(real[..., None], jax.nn.softplus(dt + lp["dt_bias"]), 0.0)
    y, last = scan_chunked(xs, dt, -jnp.exp(lp["A_log"]), B, C, cfg.ssm_chunk, cfg.dtype)
    out = _gated_out(lp, y + lp["D"][:, None] * xs, z, cfg)
    last = last.transpose(0, 3, 1, 2).reshape(R, *ssm.shape[1:])  # [R, N, H * P], as the slot keeps it
    return out, (ssm.at[slots].set(last.astype(ssm.dtype), mode="drop"),
                 conv.at[slots, : K - 1].set(tail.astype(conv.dtype), mode="drop"))


def _mamba2_step(lp: dict, x: jax.Array, arrays, slots, positions, rope, cfg):
    ssm, conv = arrays
    R, P = x.shape[0], cfg.ssm_head_dim
    live = jnp.sum(slots < ssm.shape[0]).astype(jnp.int32)  # the live rows come first (``JaxDecoder.run_step``)
    z, xbc, dt = _ssm_project(lp, x, cfg)
    xbc, conv = conv_step(conv, slots, live, xbc, lp["conv_w"], lp["conv_b"])
    xs, B, C = _ssm_inputs(jax.nn.silu(xbc), cfg)
    dt = jax.nn.softplus(dt + lp["dt_bias"])  # [R, H]
    decay = jnp.repeat(jnp.exp(dt * -jnp.exp(lp["A_log"])), P, axis=-1)
    y, ssm = state_step(ssm, slots, live, decay, (dt[..., None] * xs).reshape(R, -1), B, C)
    return _gated_out(lp, y + (lp["D"][:, None] * xs).reshape(R, -1), z, cfg), (ssm, conv)


# ------------------------------------------------------------------ registry


class Mixer(NamedTuple):
    """``slot(cfg, cache_len)``: ((shape, dtype), ...) of one row's arrays in
    a layer's cache (the cache gives each a leading ``cache_rows``).
    ``prefill(lp, x [R, L, d], arrays, slots, lengths, rope, cfg)`` and
    ``step(lp, x [R, d], arrays, slots, positions, rope, cfg)`` give the
    mixer's output, float32, and the arrays with the rows' slots written (a
    slot past the cache drops its writes: padding rows). ``recurrent``: the
    slot is all of the row's history, whatever the prompt's length."""

    slot: Callable
    prefill: Callable
    step: Callable
    recurrent: bool = False


MIXERS = {
    "latent": Mixer(_latent_slot, _latent_prefill, _latent_step),
    "gqa": Mixer(_gqa_slot, _gqa_prefill, _gqa_step),
    "mamba2": Mixer(_mamba2_slot, _mamba2_prefill, _mamba2_step, recurrent=True),
}
