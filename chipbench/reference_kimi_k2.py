"""The plain reference of the answer step: the Kimi-K2 / DeepSeek-V3 block
written straight from the published description, ``jax.numpy``, float32 at
``highest`` matmul precision. One row at a time, no cache (every position is
computed again from the whole sequence), keys and values up-projected from
the latents (no absorbed products), the experts held here in a plain loop
over all the tokens. It imports nothing of the program.

Block ``l``: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``,
final RMSNorm, ``logits = y W_head`` over the rows of the vocabulary held.

- Attn: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` (heads of nope + rope),
  ``[c_kv ; k_r] = x W_kva``, ``c_kv = RMSNorm(c_kv)``, RoPE (YaRN) on q's
  rope part and on ``k_r`` (one vector shared by all heads), ``[k_nope ; v] =
  c_kv W_kvb``, ``score = (q_nope.k_nope + q_rope.k_r) * head_dim^-1/2 *
  m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``, causal softmax,
  ``concat_h(softmax v) W_o``.
- FFN of the first ``first_k_dense_replace`` layers: SwiGLU. Of the others:
  ``s = sigmoid(x W_r)``, the ``num_experts_per_tok`` largest ``s + b``,
  ``w_e = s_e / sum_chosen s * routed_scaling_factor``, ``sum over the chosen
  experts that are held here of w_e SwiGLU_e(x)`` plus the shared expert. The
  share is ``(first_expert, n_routed_experts)`` of the ``llm`` group: what
  the absent experts would add is left out, as in the program.

The weights are the benchmark's own, made from a key a layer at a time in the
configuration's compute type (they are what the program is handed) and read
here as float32. ``precision`` lowers the matmul operands for the control, as
in ``reference.py``; the router stays float32, as the configuration states.
"""

from __future__ import annotations

import hashlib
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import _mm, pieces

BOS = 1


# ------------------------------------------------------------------ weights


def llm_key(eparams: dict) -> jax.Array:
    """The language model's key. The harness hands a pipeline its embedder,
    not ``--seed``; the embedder's weights are made from the seed, so a digest
    of one of them is a seed of its own, the same on both sides."""
    digest = hashlib.sha256(np.asarray(eparams["tok_type"], np.float32).tobytes()).digest()
    return jax.random.PRNGKey(int.from_bytes(digest[:4], "little") & 0x7FFFFFFF)


def _static(llm: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in llm.items() if isinstance(v, (int, float, str, bool))))


@partial(jax.jit, static_argnames=("llm", "sparse", "dtype"))
def _layer(key, *, llm: tuple, sparse: bool, dtype: str) -> dict:
    c = dict(llm)
    d, H, dt = c["hidden_size"], c["num_attention_heads"], jnp.dtype(dtype)
    qk, kvr = c["qk_nope_head_dim"] + c["qk_rope_head_dim"], c["kv_lora_rank"]
    ks = iter(jax.random.split(key, 20))

    def mat(*shape):  # unit gain over the contraction axis
        return (jax.random.normal(next(ks), shape, jnp.float32) * shape[-2] ** -0.5).astype(dt)

    def gain(n):
        return 1.0 + 0.1 * jax.random.normal(next(ks), (n,), jnp.float32)

    def swiglu(width, *lead):
        return {"w_gate": mat(*lead, d, width), "w_up": mat(*lead, d, width), "w_down": mat(*lead, width, d)}

    w = {
        "attn_norm": gain(d), "wq_a": mat(d, c["q_lora_rank"]), "q_norm": gain(c["q_lora_rank"]),
        "wq_b": mat(c["q_lora_rank"], H * qk), "wkv_a": mat(d, kvr + c["qk_rope_head_dim"]),
        "kv_norm": gain(kvr), "wkv_b": mat(kvr, H * (c["qk_nope_head_dim"] + c["v_head_dim"])),
        "wo": mat(H * c["v_head_dim"], d), "ffn_norm": gain(d),
    }
    if not sparse:
        return {**w, **swiglu(c["intermediate_size"])}
    published = c.get("n_routed_experts_published", c["n_routed_experts"])
    w["router"] = jax.random.normal(next(ks), (d, published), jnp.float32) * d ** -0.5
    w["router_bias"] = 0.01 * jax.random.normal(next(ks), (published,), jnp.float32)
    w["experts"] = swiglu(c["moe_intermediate_size"], c["n_routed_experts"])
    w["shared"] = swiglu(c["moe_intermediate_size"] * c["n_shared_experts"])
    return w


def layer_weights(key, llm: dict, l: int, dtype: str) -> dict:
    """Layer ``l``: unit-gain normal matrices in ``dtype``, float32 RMSNorm
    gains ``1 + N(0, 0.1)``, float32 router with selection bias ``N(0,
    0.01)``."""
    return _layer(jax.random.fold_in(key, l), llm=_static(llm), sparse=l >= llm["first_k_dense_replace"], dtype=dtype)


def top_weights(key, llm: dict, dtype: str) -> dict:
    k_embed, k_head, k_norm = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    d, V, dt = llm["hidden_size"], llm["vocab_size"], jnp.dtype(dtype)
    return {
        "embed": jax.random.normal(k_embed, (V, d), jnp.float32).astype(dt),
        "head": (jax.random.normal(k_head, (d, V), jnp.float32) * d ** -0.5).astype(dt),
        "norm_f": 1.0 + 0.1 * jax.random.normal(k_norm, (d,), jnp.float32),
    }


def program_params(key, llm: dict, dtype: str) -> dict:
    """The tree the program is handed (``ops/decoder.py``'s layout)."""
    return {**top_weights(key, llm, dtype),
            "layers": [layer_weights(key, llm, l, dtype) for l in range(llm["num_hidden_layers"])]}


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


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


@partial(jax.jit, static_argnames=("llm", "precision"))
def _layer_forward(w, x, *, llm: tuple, precision: str):
    c = dict(llm)
    c["rope_scaling"] = dict(c.pop("_rope_scaling"))
    h = x + attention(w, _rms(x, w["attn_norm"], c["rms_norm_eps"]), c, precision)
    return h + ffn(w, _rms(h, w["ffn_norm"], c["rms_norm_eps"]), c, precision)


def layer_forward(w: dict, x, llm: dict, precision: str = "f32"):
    """One block over one row ``x [L, d]``; ``w`` float32."""
    key = _static(llm) + (("_rope_scaling", tuple(sorted(llm["rope_scaling"].items()))),)
    return _layer_forward(w, x, llm=key, precision=precision)


@partial(jax.jit, static_argnames=("eps", "precision"))
def _logits(top, x, *, eps: float, precision: str):
    return _mm("ld,dv->lv", _rms(x, top["norm_f"], eps), top["head"], precision)


def forward_rows(key, llm: dict, dtype: str, rows: list[list[int]], keep: list[int],
                 precision: str = "f32", width: int | None = None) -> list[np.ndarray]:
    """For each row of ids, the logits ``[keep_i, V]`` at its last ``keep_i``
    positions. Layer by layer, one layer's float32 weights on the device at a
    time, each row on its own; every row is filled up at its end to one
    ``width`` (under a causal mask what follows a position does not reach it),
    so that one compiled layer serves them all."""
    width = width or -(-max(len(r) for r in rows) // 256) * 256
    top = _f32(top_weights(key, llm, dtype))
    xs = [top["embed"][jnp.asarray(list(r) + [0] * (width - len(r)), jnp.int32)] for r in rows]
    for l in range(llm["num_hidden_layers"]):
        w = _f32(layer_weights(key, llm, l, dtype))
        xs = [layer_forward(w, x, llm, precision) for x in xs]
        del w
    return [np.asarray(_logits(top, x[len(r) - k : len(r)], eps=llm["rms_norm_eps"], precision=precision))
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


# --------------------------------------------------------------- the prompt


def build_prompt(question: str, docs: list[str]) -> str:
    """The template's prompt (``xpacks/llm/prompts.py``
    ``prompt_qa_geometric_rag``), copied."""
    context = "\n".join(f"- {d}" for d in docs)
    return (
        "Answer the question based only on the documents below. "
        "If the documents don't contain the answer, reply with exactly "
        "'No information found.'\n"
        f"\nDocuments:\n{context}\n\nQuestion: {question}\nAnswer:"
    )


def prompt_ids(text: str, vocab: int, limit: int) -> list[int]:
    """[BOS] and the text's pieces under the hashing tokenizer at ``vocab``;
    a prompt longer than ``limit`` keeps its end (the question)."""
    return ([BOS] + pieces(text, vocab, 1 << 30))[-limit:]
