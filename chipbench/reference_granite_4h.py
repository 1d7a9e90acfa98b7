"""The plain reference of a hybrid answer step: HF ``GraniteMoeHybrid`` with
no experts (granite-4.0-h-micro: Mamba-2 layers with a grouped-query
attention layer among every ten), written straight from the published
description, ``jax.numpy``, float32 at ``highest`` matmul precision. No
cache (every position is computed again from the whole sequence), full
causal attention a head at a time, **the recurrence a position at a time**
(a ``lax.scan`` over the positions; no chunks). It imports nothing of the
program. The repo's tests keep a copy of the same mathematics
(``tests/reference_hybrid.py``).

``x_0 = E[ids] * embedding_multiplier``. Block ``l``: ``h = x + r * Mixer_l(
RMSNorm(x))``, ``y = h + r * MLP(RMSNorm(h))`` (``r = residual_multiplier``);
``MLP(u) = (silu(u W_gate) * (u W_up)) W_down``; after the last block
``RMSNorm``, ``logits = (y E^T) / logits_scaling`` (tied).

- ``attention`` layers: ``q = u W_q``, ``k, v = u W_k, u W_v`` (no bias, no
  position term), causal softmax of ``q k^T * attention_multiplier``, each
  key-value head serving ``heads / kv_heads`` query heads, then ``W_o``.
- ``mamba`` layers: ``[z ; xBC ; dt] = u W_in``; ``xBC = silu(causal
  depthwise conv1d(xBC, width mamba_d_conv) + bias)`` splits into ``x [H,
  P]``, ``B [N]``, ``C [N]``; ``D_t = softplus(dt_t + dt_bias)``, ``A =
  -exp(A_log)`` a head; ``S_t = exp(D_t A) S_{t-1} + D_t x_t (x) B_t``; ``y_t =
  S_t C_t + D x_t``; ``RMSNorm(y * silu(z))`` over all heads, then ``W_out``.

The weights are the benchmark's own, made from a key a layer at a time in the
configuration's compute type (they are what the program is handed) and read
here as float32: the ``.llm.json``'s ``assumed`` lists their initialisation.
``precision`` lowers the matmul operands for the control, as in
``reference.py``; the recurrence stays float32, as the configuration states.

The interface is ``reference_kimi_k2``'s (``program_params``, ``llm_key``,
``build_prompt``, ``prompt_ids``, ``forward_rows``, ``greedy``): a
configuration's ``.llm.json`` names its reference module under ``reference``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import _mm
from chipbench.reference_kimi_k2 import _f32, _rms, _static, build_prompt, llm_key, prompt_ids  # noqa: F401

#: rows of one block of ``forward_rows`` (a block's float32 MLP products are 0.7 GB at 2,816 positions)
ROW_BLOCK = 4


# ------------------------------------------------------------------ weights


@partial(jax.jit, static_argnames=("llm", "kind", "dtype"))
def _layer(key, *, llm: tuple, kind: str, dtype: str) -> dict:
    c = dict(llm)
    d, H, dt = c["hidden_size"], c["num_attention_heads"], jnp.dtype(dtype)
    ks = iter(jax.random.split(key, 16))

    def mat(*shape):  # unit gain over the contraction axis
        return (jax.random.normal(next(ks), shape, jnp.float32) * shape[-2] ** -0.5).astype(dt)

    def gain(n):
        return 1.0 + 0.1 * jax.random.normal(next(ks), (n,), jnp.float32)

    f = c["shared_intermediate_size"]
    w = {"attn_norm": gain(d), "ffn_norm": gain(d), "w_gate": mat(d, f), "w_up": mat(d, f), "w_down": mat(f, d)}
    if kind == "attention":
        hd, KV = d // H, c["num_key_value_heads"]
        return {**w, "wq": mat(d, H * hd), "wk": mat(d, KV * hd), "wv": mat(d, KV * hd), "wo": mat(H * hd, d)}
    Hm, P, N, K = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"], c["mamba_d_conv"]
    conv_dim = Hm * P + 2 * N
    step = jnp.exp(jax.random.uniform(next(ks), (Hm,), jnp.float32, np.log(1e-3), np.log(1e-1)))
    return {
        **w, "w_in": mat(d, 2 * Hm * P + 2 * N + Hm), "w_out": mat(Hm * P, d),
        "conv_w": jax.random.normal(next(ks), (K, conv_dim), jnp.float32) * K ** -0.5,
        "conv_b": 0.1 * jax.random.normal(next(ks), (conv_dim,), jnp.float32),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus's inverse of the step size
        "A_log": jnp.log(jax.random.uniform(next(ks), (Hm,), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((Hm,), jnp.float32), "gate_norm": gain(Hm * P),
    }


def layer_weights(key, llm: dict, l: int, dtype: str) -> dict:
    """Layer ``l``: unit-gain normal matrices in ``dtype``, float32 gains ``1
    + N(0, 0.1)``, Mamba-2's initialisation of the recurrence (``A`` uniform
    in [1, 16], the step size log-uniform in [1e-3, 1e-1] through
    ``dt_bias``, ``D = 1``)."""
    return _layer(jax.random.fold_in(key, l), llm=_static(llm), kind=llm["layer_types"][l], dtype=dtype)


def top_weights(key, llm: dict, dtype: str) -> dict:
    """The tied embedding, ``N(0, 1) / (embedding_multiplier * sqrt(hidden))``
    (``|x_0|`` about 1: see the configuration's ``assumed``), and the last norm."""
    k_embed, k_norm = jax.random.split(jax.random.fold_in(key, 1 << 20))
    d, V = llm["hidden_size"], llm["vocab_size"]
    scale = 1.0 / (llm["embedding_multiplier"] * d ** 0.5)
    return {
        "embed": (jax.random.normal(k_embed, (V, d), jnp.float32) * scale).astype(jnp.dtype(dtype)),
        "norm_f": 1.0 + 0.1 * jax.random.normal(k_norm, (d,), jnp.float32),
    }


def program_params(key, llm: dict, dtype: str) -> dict:
    """The tree the program is handed (``ops/decoder.py``'s layout)."""
    return {**top_weights(key, llm, dtype),
            "layers": [layer_weights(key, llm, l, dtype) for l in range(llm["num_hidden_layers"])]}


# ---------------------------------------------------------------- the block


def attention(w: dict, u, c: dict, precision: str):
    """``u [L, d]`` after its norm -> ``[L, d]``."""
    L, H, KV = u.shape[0], c["num_attention_heads"], c["num_key_value_heads"]
    q = _mm("ld,de->le", u, w["wq"], precision).reshape(L, H, -1)
    k = _mm("ld,de->le", u, w["wk"], precision).reshape(L, KV, -1)
    v = _mm("ld,de->le", u, w["wv"], precision).reshape(L, KV, -1)
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)  # query head h reads kv head h // (H / KV)
    causal = jnp.tril(jnp.ones((L, L), bool))

    def one_head(qkv):  # a head at a time, so that the score matrix held is one head's
        qh, kh, vh = qkv
        s = jnp.where(causal, _mm("qd,kd->qk", qh, kh, precision) * c["attention_multiplier"], -jnp.inf)
        return _mm("qk,kd->qd", jax.nn.softmax(s, axis=-1), vh, precision)

    ctx = jax.lax.map(one_head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return _mm("le,ed->ld", ctx.transpose(1, 0, 2).reshape(L, -1), w["wo"], precision)


def mamba(w: dict, u, c: dict, precision: str):
    """``u [L, d]`` after its norm -> ``[L, d]``: the recurrence a position at
    a time, float32 whatever ``precision`` says of the projections."""
    L, H, P, N, K = u.shape[0], c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"], c["mamba_d_conv"]
    inner = H * P
    proj = _mm("ld,de->le", u, w["w_in"], precision)
    z, xbc, dt = proj[:, :inner], proj[:, inner : 2 * inner + 2 * N], proj[:, 2 * inner + 2 * N:]
    before = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), jnp.float32), xbc])
    xbc = jax.nn.silu(sum(before[j : j + L] * w["conv_w"][j] for j in range(K)) + w["conv_b"])
    x, B, C = xbc[:, :inner].reshape(L, H, P), xbc[:, inner : inner + N], xbc[:, inner + N:]
    step = jax.nn.softplus(dt + w["dt_bias"])  # [L, H]
    A = -jnp.exp(w["A_log"])

    def one(S, t):
        x_t, B_t, C_t, step_t = t
        S = jnp.exp(step_t * A)[:, None, None] * S + (step_t[:, None] * x_t)[:, :, None] * B_t[None, None, :]
        return S, jnp.sum(S * C_t[None, None, :], axis=-1) + w["D"][:, None] * x_t

    _last, y = jax.lax.scan(one, jnp.zeros((H, P, N), jnp.float32), (x, B, C, step))
    g = y.reshape(L, inner) * jax.nn.silu(z)
    return _mm("le,ed->ld", _rms(g, w["gate_norm"], c["rms_norm_eps"]), w["w_out"], precision)


def mlp(w: dict, u, precision: str):
    h = jax.nn.silu(_mm("ld,df->lf", u, w["w_gate"], precision)) * _mm("ld,df->lf", u, w["w_up"], precision)
    return _mm("lf,fd->ld", h, w["w_down"], precision)


@partial(jax.jit, static_argnames=("llm", "kind", "precision"))
def _layer_forward(w, xs, *, llm: tuple, kind: str, precision: str):
    c = dict(llm)
    eps, r = c["rms_norm_eps"], c["residual_multiplier"]
    mixer = attention if kind == "attention" else mamba

    def one_row(x):
        h = x + r * mixer(w, _rms(x, w["attn_norm"], eps), c, precision)
        return h + r * mlp(w, _rms(h, w["ffn_norm"], eps), precision)

    return jax.vmap(one_row)(xs)


def layer_forward(w: dict, xs, llm: dict, kind: str, precision: str = "f32"):
    """One block over a block of rows ``xs [rows, L, d]``; ``w`` float32."""
    return _layer_forward(w, xs, llm=_static(llm), kind=kind, precision=precision)


@partial(jax.jit, static_argnames=("eps", "scaling", "precision"))
def _logits(top, x, *, eps: float, scaling: float, precision: str):
    return _mm("ld,vd->lv", _rms(x, top["norm_f"], eps), top["embed"], precision) / scaling


def forward_rows(key, llm: dict, dtype: str, rows: list[list[int]], keep: list[int],
                 precision: str = "f32", width: int | None = None) -> list[np.ndarray]:
    """For each row of ids, the logits ``[keep_i, V]`` at its last ``keep_i``
    positions. Layer by layer, one layer's float32 weights on the device at a
    time, the rows in blocks of ``ROW_BLOCK``; every row is filled up at its
    end to one ``width`` (what follows a position reaches it neither through
    the causal mask nor through the recurrence), so that one compiled layer
    of each kind serves them all."""
    width = width or -(-max(len(r) for r in rows) // 256) * 256
    top = _f32(top_weights(key, llm, dtype))
    ids = [list(r) + [0] * (width - len(r)) for r in rows]
    size = min(ROW_BLOCK, len(ids))
    ids += [ids[-1]] * (-len(ids) % size)  # a last block filled up with a row again
    blocks = [top["embed"][jnp.asarray(ids[i : i + size], jnp.int32)] * llm["embedding_multiplier"]
              for i in range(0, len(ids), size)]
    for l, kind in enumerate(llm["layer_types"]):
        w = _f32(layer_weights(key, llm, l, dtype))
        blocks = [layer_forward(w, xs, llm, kind, precision) for xs in blocks]
        del w
    xs = [x for block in blocks for x in block]
    return [np.asarray(_logits(top, x[len(r) - k : len(r)], eps=llm["rms_norm_eps"],
                               scaling=float(llm["logits_scaling"]), precision=precision))
            for x, r, k in zip(xs, rows, keep)]


def greedy(key, llm: dict, dtype: str, ids: list[int], n: int, precision: str) -> list[int]:
    """``n`` greedy tokens after ``ids``, the whole sequence computed again
    for each: what the reference answers at ``precision`` (the control)."""
    ids, out = list(ids), []
    width = -(-(len(ids) + n) // 256) * 256
    for _ in range(n):
        (last,) = forward_rows(key, llm, dtype, [ids], [1], precision, width)
        out.append(int(np.argmax(last[0])))
        ids.append(out[-1])
    return out
