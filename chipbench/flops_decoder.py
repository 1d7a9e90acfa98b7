"""The answer step's arithmetic: operations and bytes the model needs for
the real tokens, from the ``llm`` group's shapes and the program's counters
(``observability.device.stats().pad``, which the harness snapshots before and
after the window). ``tests/test_flops_decoder.py`` holds the shapes to the
program's parameter tree.

Counters read (label: [real rows, pad rows, real tokens, pad tokens]):
``decoder.prefill`` tokens = prompt tokens (real, padding);
``decoder.prefill.scores`` tokens = (query, key) score entries a causal pass
needs; ``decoder.step`` rows = rows stepped, tokens = cache entries they
attended to; ``decoder.experts`` rows = (token, expert) pairs computed here,
tokens = token-layers routed; ``decoder.step.experts`` rows = held experts a
step touched (summed over layers and steps), tokens = the steps' pairs.
"""

from __future__ import annotations

import functools
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.lru_cache(maxsize=None)
def _llm_file(path: str) -> dict:
    with open(os.path.join(ROOT, path), encoding="utf-8") as f:
        return json.load(f)


def llm_config(config: dict) -> dict | None:
    """The language model's configuration, or nothing where the deployment
    has none. ``llm`` names its file from the repo's root: the ``file`` of
    the configuration's entry in ``BENCHMARK.json``, which holds the source's
    ``config.json`` key for key at its top level (the deployment's own top
    level is the embedder's, where ``run.build_models`` reads it). A test's
    stand-in puts the group itself there."""
    llm = config.get("llm")
    return _llm_file(llm) if isinstance(llm, str) else llm



def attention_params(c: dict) -> int:
    d, H = c["hidden_size"], c["num_attention_heads"]
    return (d * c["q_lora_rank"] + c["q_lora_rank"] * H * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"])
            + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * H * (c["qk_nope_head_dim"] + c["v_head_dim"]) + H * c["v_head_dim"] * d)


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: dict) -> int:
    return c["hidden_size"] * c.get("n_routed_experts_published", c["n_routed_experts"])


def layers(c: dict) -> tuple[int, int]:
    """(dense layers, sparse layers) held."""
    dense = min(c["first_k_dense_replace"], c["num_hidden_layers"])
    return dense, c["num_hidden_layers"] - dense


def token_params(c: dict) -> int:
    """Matmul parameters every token passes, whatever it is routed to: the
    attention blocks, the dense feed-forward, router and shared expert of
    each sparse layer. Neither the routed experts nor the head."""
    dense, sparse = layers(c)
    return ((dense + sparse) * attention_params(c) + dense * 3 * c["hidden_size"] * c["intermediate_size"]
            + sparse * (router_params(c) + c["n_shared_experts"] * expert_params(c)))


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def delta(ctx, label: str) -> list[int]:
    """The window's part of a pad counter; zeros where the program has none."""
    a, b = ctx.after["pad"].get(label), ctx.before["pad"].get(label, [0, 0, 0, 0])
    return [x - y for x, y in zip(a, b)] if a is not None else [0, 0, 0, 0]


def prefill_flops(c: dict, tokens: int, scores: int, pairs: int, rows: int) -> float:
    """Real prompt tokens through the blocks, ``scores`` causal (query, key)
    entries over every head's 192-wide keys and 128-wide values in every
    layer, the held experts' pairs, and a row's last position through the head."""
    H = c["num_attention_heads"]
    per_entry = 2 * H * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]) * c["num_hidden_layers"]
    return (2.0 * tokens * token_params(c) + float(scores) * per_entry + 2.0 * pairs * expert_params(c)
            + 2.0 * rows * head_params(c))


def step_flops(c: dict, rows: int, attended: int, pairs: int) -> float:
    """One token a row: the same blocks (the absorbed products use ``W_kvb``'s
    parameters once, as an up-projection of the token would), every head's
    scores and weighted sum against ``attended`` latents (576 and 512 wide),
    the pairs, the head."""
    H = c["num_attention_heads"]
    per_entry = 2 * H * (2 * c["kv_lora_rank"] + c["qk_rope_head_dim"]) * c["num_hidden_layers"]
    return (2.0 * rows * (token_params(c) + head_params(c)) + float(attended) * per_entry
            + 2.0 * pairs * expert_params(c))


def step_bytes(c: dict, steps: int, experts_hit: int, attended: int, weight_bytes: int = 2) -> float:
    """What ``steps`` decode steps have to read whatever implements them: each
    the non-expert weights of every layer and the head's slice (the router in
    float32), the held experts its rows routed to, and the latent cache of
    its live rows."""
    _dense, sparse = layers(c)
    fixed = weight_bytes * (token_params(c) + head_params(c)) + (4 - weight_bytes) * sparse * router_params(c)
    latent = (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * weight_bytes * c["num_hidden_layers"]
    return float(steps) * fixed + float(experts_hit) * expert_params(c) * weight_bytes + float(attended) * latent


def window_counts(ctx) -> dict | None:
    """The window's counters by what the formulas take; None where the
    program has no decoder (an older commit) or it did not run."""
    pre, scores, step = delta(ctx, "decoder.prefill"), delta(ctx, "decoder.prefill.scores"), delta(ctx, "decoder.step")
    experts, step_experts = delta(ctx, "decoder.experts"), delta(ctx, "decoder.step.experts")
    if not pre[2] or not step[0]:
        return None
    return {
        "prompt_tokens": pre[2], "scores": scores[2], "rows_stepped": step[0], "attended": step[2],
        "pairs": experts[0], "step_pairs": step_experts[2], "experts_hit": step_experts[0],
    }
