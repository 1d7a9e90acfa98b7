"""The repo's plain reference of a hybrid decoder (state-space layers with an
attention layer among every few: HF ``GraniteMoeHybrid`` with no experts; the
mixer is Mamba-2 as in Bamba), written from the published description in
straightforward ``jax.numpy``: float32 at ``highest`` matmul precision, one
row at a time, no cache, no batching, full causal attention, **the recurrence
token by token** (a ``lax.scan`` over the positions; no chunks). It imports
nothing of the program; it takes the program's parameter tree as data and the
configuration as the ``config.json`` dict. The benchmark keeps a copy of the
same mathematics in ``chipbench/reference_granite_4h.py``.

``x_0 = E[ids] * embedding_multiplier``. Block ``l``: ``h = x + r * Mixer_l(
RMSNorm(x))``, ``y = h + r * MLP(RMSNorm(h))`` (``r = residual_multiplier``);
``MLP(u) = (silu(u W_gate) * (u W_up)) W_down``; after the last block
``RMSNorm``, ``logits = (y E^T) / logits_scaling`` (tied).

- ``attention`` layers: ``q = u W_q`` (``num_attention_heads`` of ``hidden /
  heads``), ``k, v = u W_k, u W_v`` (``num_key_value_heads``), no bias, no
  position term (``position_embedding_type: "nope"``), causal softmax of ``q
  k^T * attention_multiplier``, each key-value head serving ``heads /
  kv_heads`` query heads, then ``W_o``.
- ``mamba`` layers: ``[z ; xBC ; dt] = u W_in``; ``xBC = silu(causal depthwise
  conv1d(xBC, width mamba_d_conv) + bias)``, split into ``x [H, P]``, ``B
  [N]``, ``C [N]`` (one group); ``D_t = softplus(dt_t + dt_bias)``, ``A =
  -exp(A_log)`` a head; ``S_t = exp(D_t A) S_{t-1} + D_t x_t (x) B_t``; ``y_t =
  S_t C_t + D x_t``; ``g = y * silu(z)``, ``RMSNorm(g)`` over all heads with
  its gain, then ``W_out``.

Departures from the published model, each because this image has no
checkpoint and no tokenizer file: the weights are random (``init_params``),
the ids come from the repo's ``HashTokenizer``; HF keeps ``W_gate`` and
``W_up`` as one matrix ``[a ; b] = u W_in`` (the same function, two names).

``precision`` lowers the matmul operands (``"bf16"``, ``"fp8"`` e4m3): what a
run below the stated precision would give. The recurrence stays float32.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from reference_decoder import _mm, _rms

#: one whole period of the published layer pattern at a size the CPU tests can hold
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
TINY = {
    "model_type": "granitemoehybrid", "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 10,
    "layer_types": PERIOD, "num_attention_heads": 4, "num_key_value_heads": 2, "attention_multiplier": 0.0625,
    "attention_bias": False, "position_embedding_type": "nope", "rope_theta": 10000, "rope_scaling": None,
    "intermediate_size": 128, "shared_intermediate_size": 128, "num_local_experts": 0, "num_experts_per_tok": 0,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_chunk_size": 4, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "embedding_multiplier": 12, "residual_multiplier": 0.22, "logits_scaling": 8, "tie_word_embeddings": True,
    "rms_norm_eps": 1e-5, "hidden_act": "silu", "normalization_function": "rmsnorm",
}


# ------------------------------------------------------------------ weights


def _init_layer(key, c: dict, kind: str, dt) -> dict:
    d, H = c["hidden_size"], c["num_attention_heads"]
    ks = iter(jax.random.split(key, 16))

    def mat(*shape):  # unit gain: the contraction axis is the one before last
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
    # Mamba-2's own initialisation: A uniform in [1, 16], dt log-uniform in
    # [1e-3, 1e-1] through dt_bias (softplus's inverse), D = 1
    step = jnp.exp(jax.random.uniform(next(ks), (Hm,), jnp.float32, np.log(1e-3), np.log(1e-1)))
    return {
        **w, "w_in": mat(d, 2 * Hm * P + 2 * N + Hm), "w_out": mat(Hm * P, d),
        "conv_w": jax.random.normal(next(ks), (K, conv_dim), jnp.float32) * K ** -0.5,
        "conv_b": 0.1 * jax.random.normal(next(ks), (conv_dim,), jnp.float32),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.log(jax.random.uniform(next(ks), (Hm,), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((Hm,), jnp.float32), "gate_norm": gain(Hm * P),
    }


def init_params(llm: dict, seed: int = 0, dtype=jnp.float32) -> dict:
    """Seeded parameters in the program's layout: unit-gain normal matrices
    in ``dtype``, float32 gains ``1 + N(0, 0.1)``, Mamba-2's initialisation of
    ``A``, ``dt`` and ``D``. The embedding is ``N(0, 1) / (embedding_multiplier
    * sqrt(hidden))``, so that ``|x_0|`` is about 1: the head is tied, and a
    unit-variance embedding would make every position's own token its argmax
    by twenty spreads and the greedy chain one token repeated."""
    key = jax.random.PRNGKey(seed)
    k_embed, k_norm = jax.random.split(jax.random.fold_in(key, 1 << 20))
    d, V = llm["hidden_size"], llm["vocab_size"]
    scale = 1.0 / (llm["embedding_multiplier"] * d ** 0.5)
    return {
        "embed": (jax.random.normal(k_embed, (V, d), jnp.float32) * scale).astype(dtype),
        "norm_f": 1.0 + 0.1 * jax.random.normal(k_norm, (d,), jnp.float32),
        "layers": [_init_layer(jax.random.fold_in(key, l), llm, kind, dtype)
                   for l, kind in enumerate(llm["layer_types"])],
    }


# ---------------------------------------------------------------- the block


def attention(w: dict, u, c: dict, precision: str):
    """``u [L, d]`` after its norm -> ``[L, d]``: full causal attention."""
    L, H, KV = u.shape[0], c["num_attention_heads"], c["num_key_value_heads"]
    q = _mm("ld,de->le", u, w["wq"], precision).reshape(L, H, -1)
    k = _mm("ld,de->le", u, w["wk"], precision).reshape(L, KV, -1)
    v = _mm("ld,de->le", u, w["wv"], precision).reshape(L, KV, -1)
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)  # query head h reads kv head h // (H / KV)
    s = _mm("qhd,khd->hqk", q, k, precision) * c["attention_multiplier"]
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    ctx = _mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, precision).reshape(L, -1)
    return _mm("le,ed->ld", ctx, w["wo"], precision)


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
        return S, jnp.einsum("hpn,n->hp", S, C_t, precision="highest") + w["D"][:, None] * x_t

    _last, y = jax.lax.scan(one, jnp.zeros((H, P, N), jnp.float32), (x, B, C, step))
    g = y.reshape(L, inner) * jax.nn.silu(z)
    return _mm("le,ed->ld", _rms(g, w["gate_norm"], c["rms_norm_eps"]), w["w_out"], precision)


def mlp(w: dict, u, precision: str):
    h = jax.nn.silu(_mm("ld,df->lf", u, w["w_gate"], precision)) * _mm("ld,df->lf", u, w["w_up"], precision)
    return _mm("lf,fd->ld", h, w["w_down"], precision)


def layer(w: dict, x, c: dict, kind: str, precision: str = "f32"):
    eps, r = c["rms_norm_eps"], c["residual_multiplier"]
    mixer = attention if kind == "attention" else mamba
    h = x + r * mixer(w, _rms(x, w["attn_norm"], eps), c, precision)
    return h + r * mlp(w, _rms(h, w["ffn_norm"], eps), precision)


@functools.lru_cache(maxsize=None)
def _jitted(llm_json: str, precision: str):
    c = json.loads(llm_json)

    def run(p, ids):
        x = p["embed"][ids] * c["embedding_multiplier"]
        for w, kind in zip(p["layers"], c["layer_types"]):
            x = layer(w, x, c, kind, precision)
        y = _rms(x, p["norm_f"], c["rms_norm_eps"])
        return _mm("ld,vd->lv", y, p["embed"], precision) / c["logits_scaling"]

    return jax.jit(run)


def forward(params: dict, llm: dict, ids, precision: str = "f32", width: int = 64) -> np.ndarray:
    """Logits ``[L, V]`` of one row of ids: the whole sequence, every
    position. The row is filled up at its end to a multiple of ``width``
    (what follows a position reaches it neither through the causal mask nor
    through the recurrence), so that one compilation serves many lengths."""
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    ids = list(ids)
    padded = ids + [0] * (-len(ids) % width)
    out = _jitted(json.dumps(llm, sort_keys=True), precision)(p, jnp.asarray(padded, jnp.int32))
    return np.asarray(out)[: len(ids)]
