"""The repo's own plain reference of ``ops/decoder.py`` (the benchmark keeps a
copy of the same mathematics in ``chipbench/reference_kimi_k2.py``): the
DeepSeek-V3 / Kimi-K2 block in straightforward ``jax.numpy``, float32 at
``highest`` matmul precision, one row at a time, no cache, keys and values
up-projected from the latents, the experts held here in a plain loop over all
the tokens. It imports nothing of the program; it takes the program's
parameter tree as data and the configuration as the ``config.json`` dict.

``precision`` lowers the matmul operands (``"bf16"``, ``"fp8"`` e4m3): what a
run below the stated precision would give. The router stays float32.

``init_params`` makes the tests' seeded weights in the program's layout (the
program has no initialiser of its own: a chat is handed its ``params``), and
``TINY`` is the size the tests run at.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np


def _lower(x, precision: str):
    if precision == "f32":
        return x.astype(jnp.float32)
    if precision == "bf16":
        return x.astype(jnp.bfloat16)
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    raise ValueError(f"unknown precision {precision!r}")


def _mm(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _lower(a, precision), _lower(b, precision),
                      preferred_element_type=jnp.float32, precision="highest")


#: a ``config.json`` of the ``kimi_k2`` kind at a size the CPU tests can hold
TINY = {
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32, "n_routed_experts": 16, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "first_k_dense_replace": 1, "routed_scaling_factor": 2.827, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "rope_theta": 50000, "n_group": 1, "topk_group": 1, "scoring_func": "sigmoid",
    "hidden_act": "silu", "topk_method": "noaux_tc",
    "rope_scaling": {"beta_fast": 1, "beta_slow": 1, "factor": 32, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64, "type": "yarn"},
}


# ------------------------------------------------------------------ weights


def _init_layer(key, c: dict, sparse: bool, dt) -> dict:
    d, H = c["hidden_size"], c["num_attention_heads"]
    qk, kvr = c["qk_nope_head_dim"] + c["qk_rope_head_dim"], c["kv_lora_rank"]
    ks = iter(jax.random.split(key, 20))

    def mat(*shape):  # unit gain: the contraction axis is the one before last
        return (jax.random.normal(next(ks), shape, jnp.float32) * shape[-2] ** -0.5).astype(dt)

    def gain(n):
        return 1.0 + 0.1 * jax.random.normal(next(ks), (n,), jnp.float32)

    def swiglu_w(width, *lead):
        return {"w_gate": mat(*lead, d, width), "w_up": mat(*lead, d, width), "w_down": mat(*lead, width, d)}

    w = {
        "attn_norm": gain(d), "wq_a": mat(d, c["q_lora_rank"]), "q_norm": gain(c["q_lora_rank"]),
        "wq_b": mat(c["q_lora_rank"], H * qk), "wkv_a": mat(d, kvr + c["qk_rope_head_dim"]),
        "kv_norm": gain(kvr), "wkv_b": mat(kvr, H * (c["qk_nope_head_dim"] + c["v_head_dim"])),
        "wo": mat(H * c["v_head_dim"], d), "ffn_norm": gain(d),
    }
    if not sparse:
        return {**w, **swiglu_w(c["intermediate_size"])}
    published = c.get("n_routed_experts_published", c["n_routed_experts"])
    w["router"] = jax.random.normal(next(ks), (d, published), jnp.float32) * d ** -0.5
    w["router_bias"] = 0.01 * jax.random.normal(next(ks), (published,), jnp.float32)
    w["experts"] = swiglu_w(c["moe_intermediate_size"], c["n_routed_experts"])
    w["shared"] = swiglu_w(c["moe_intermediate_size"] * c["n_shared_experts"])
    return w


def init_params(llm: dict, seed: int = 0, dtype=jnp.float32) -> dict:
    """Seeded parameters in the program's layout: unit-gain normal matrices
    in ``dtype``, float32 RMSNorm gains ``1 + N(0, 0.1)``, a float32 router
    with selection bias ``N(0, 0.01)``."""
    key = jax.random.PRNGKey(seed)
    k_embed, k_head, k_norm = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    d, V = llm["hidden_size"], llm["vocab_size"]
    return {
        "embed": jax.random.normal(k_embed, (V, d), jnp.float32).astype(dtype),
        "head": (jax.random.normal(k_head, (d, V), jnp.float32) * d ** -0.5).astype(dtype),
        "norm_f": 1.0 + 0.1 * jax.random.normal(k_norm, (d,), jnp.float32),
        "layers": [_init_layer(jax.random.fold_in(key, l), llm, l >= llm["first_k_dense_replace"], dtype)
                   for l in range(llm["num_hidden_layers"])],
    }


# ---------------------------------------------------------------- the block


def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _yarn(llm: dict) -> tuple[np.ndarray, float, float]:
    """(inverse frequencies, the cos/sin multiplier, the softmax scale)."""
    dim, theta, rs = llm["qk_rope_head_dim"], llm["rope_theta"], llm["rope_scaling"]
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def dim_of(turns):  # the dimension that makes `turns` rotations over the original context
        return dim * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(dim_of(rs["beta_fast"])), 0), min(math.ceil(dim_of(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / ((high + 0.001 if low == high else high) - low), 0, 1)
    inv_freq = (base / factor) * ramp + base * (1 - ramp)

    def mscale(m):
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0

    head_dim = llm["qk_nope_head_dim"] + dim
    scale = head_dim ** -0.5 * (mscale(rs["mscale_all_dim"]) ** 2 if rs["mscale_all_dim"] else 1.0)
    return inv_freq.astype(np.float32), mscale(rs["mscale"]) / mscale(rs["mscale_all_dim"]), scale


def _rope(x, inv_freq, mult):
    """Pairs (2i, 2i+1) of the last axis as complex numbers, turned by
    ``position * inv_freq``; the first axis is the position."""
    angle = jnp.arange(x.shape[0], dtype=jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) * jnp.asarray(inv_freq)
    z = jax.lax.complex(x[..., 0::2], x[..., 1::2]) * jax.lax.complex(jnp.cos(angle) * mult, jnp.sin(angle) * mult)
    return jnp.stack([jnp.real(z), jnp.imag(z)], axis=-1).reshape(x.shape)


def attention(w: dict, x, llm: dict, precision: str):
    """``x [L, d]`` after its norm -> ``[L, d]``."""
    L, H, eps = x.shape[0], llm["num_attention_heads"], llm["rms_norm_eps"]
    nope, kvr = llm["qk_nope_head_dim"], llm["kv_lora_rank"]
    inv_freq, mult, scale = _yarn(llm)
    c_q = _rms(_mm("ld,dr->lr", x, w["wq_a"], precision), w["q_norm"], eps)
    q = _mm("lr,re->le", c_q, w["wq_b"], precision).reshape(L, H, -1)
    kv = _mm("ld,dr->lr", x, w["wkv_a"], precision)
    c_kv = _rms(kv[:, :kvr], w["kv_norm"], eps)
    k_r = _rope(kv[:, kvr:], inv_freq, mult)
    kvb = _mm("lc,ce->le", c_kv, w["wkv_b"], precision).reshape(L, H, -1)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(k_r[:, None, :], (L, H, k_r.shape[-1]))], axis=-1)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv_freq, mult)], axis=-1)
    causal = jnp.tril(jnp.ones((L, L), bool))

    def one_head(qkv):  # a head at a time, so that the score matrix held is one head's
        qh, kh, vh = qkv
        s = jnp.where(causal, _mm("qd,kd->qk", qh, kh, precision) * scale, -jnp.inf)
        return _mm("qk,kd->qd", jax.nn.softmax(s, axis=-1), vh, precision)

    heads = (q.transpose(1, 0, 2), k.transpose(1, 0, 2), kvb[..., nope:].transpose(1, 0, 2))
    ctx = jax.lax.map(one_head, heads).transpose(1, 0, 2).reshape(L, -1)
    return _mm("le,ed->ld", ctx, w["wo"], precision)


def swiglu(w: dict, x, precision: str):
    h = jax.nn.silu(_mm("ld,df->lf", x, w["w_gate"], precision)) * _mm("ld,df->lf", x, w["w_up"], precision)
    return _mm("lf,fd->ld", h, w["w_down"], precision)


def routing(w: dict, x, llm: dict):
    """``[L, published]`` weights: ``w_e`` where expert ``e`` is among the
    token's chosen, 0 elsewhere. Float32 whatever the precision."""
    s = jax.nn.sigmoid(jnp.einsum("ld,de->le", x, w["router"], precision="highest"))
    chosen = jnp.argsort(-(s + w["router_bias"]), axis=-1)[:, : llm["num_experts_per_tok"]]
    picked = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], chosen].set(1.0) * s
    if llm["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return picked * llm["routed_scaling_factor"]


def ffn(w: dict, x, llm: dict, precision: str):
    if "router" not in w:
        return swiglu(w, x, precision)
    gates = routing(w, x, llm)
    out = swiglu(w["shared"], x, precision)
    first = llm.get("first_expert", 0)
    for e in range(llm["n_routed_experts"]):  # the experts held here
        one = {k: v[e] for k, v in w["experts"].items()}
        out = out + gates[:, first + e, None] * swiglu(one, x, precision)
    return out


def layer(w: dict, x, llm: dict, precision: str = "f32"):
    h = x + attention(w, _rms(x, w["attn_norm"], llm["rms_norm_eps"]), llm, precision)
    return h + ffn(w, _rms(h, w["ffn_norm"], llm["rms_norm_eps"]), llm, precision)


@functools.lru_cache(maxsize=None)
def _jitted(llm_json: str, precision: str):
    llm = json.loads(llm_json)

    def run(p, ids):
        x = p["embed"][ids]
        for w in p["layers"]:
            x = layer(w, x, llm, precision)
        return _mm("ld,dv->lv", _rms(x, p["norm_f"], llm["rms_norm_eps"]), p["head"], precision)

    return jax.jit(run)


def forward(params: dict, llm: dict, ids, precision: str = "f32", width: int = 64) -> np.ndarray:
    """Logits ``[L, V]`` of one row of ids: the whole sequence, every
    position. The row is filled up at its end to a multiple of ``width``
    (under a causal mask what follows a position does not reach it), so that
    one compilation serves rows of many lengths."""
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    ids = list(ids)
    padded = ids + [0] * (-len(ids) % width)
    out = _jitted(json.dumps(llm, sort_keys=True), precision)(p, jnp.asarray(padded, jnp.int32))
    return np.asarray(out)[: len(ids)]
