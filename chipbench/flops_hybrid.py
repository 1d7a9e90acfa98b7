"""The arithmetic of a hybrid answer step (state-space layers with a
grouped-query attention layer among every few, no experts): operations and
bytes the model's equations need for the real tokens, from the ``.llm.json``'s
shapes and the program's counters (``observability.device.stats().pad``,
which the harness snapshots before and after the window) — never from how
the scan or the cache is implemented. ``tests/test_flops_hybrid.py`` holds
the shapes to the program's parameter tree.

Counters read (label: [real rows, pad rows, real tokens, pad tokens]):
``decoder.prefill`` tokens = prompt tokens (real, padding);
``decoder.prefill.scores`` tokens = (query, key) score entries a causal pass
needs in one attention layer; ``decoder.step`` rows = rows stepped (each
updates its own recurrent slots and no other), tokens = cache positions they
attended to. ``window_work`` is what ``chipbench.arithmetic`` asks of the
file a ``.llm.json`` names under ``arithmetic``.
"""

from __future__ import annotations

from chipbench.flops_decoder import delta, llm_config  # noqa: F401
from chipbench.metriclib import calls_delta


def layers(c: dict) -> tuple[int, int]:
    """(state-space layers, attention layers)."""
    kinds = c["layer_types"]
    return kinds.count("mamba"), kinds.count("attention")


def state_size(c: dict) -> int:
    """Numbers of one row's recurrent state in one layer: ``[H, P, N]``."""
    return c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"]


def conv_dim(c: dict) -> int:
    return c["mamba_n_heads"] * c["mamba_d_head"] + 2 * c["mamba_n_groups"] * c["mamba_d_state"]


def mamba_params(c: dict) -> int:
    """Matmul parameters of one state-space mixer: ``W_in`` and ``W_out``."""
    inner = c["mamba_n_heads"] * c["mamba_d_head"]
    return c["hidden_size"] * (inner + conv_dim(c) + c["mamba_n_heads"]) + inner * c["hidden_size"]


def attention_params(c: dict) -> int:
    d, hd = c["hidden_size"], c["hidden_size"] // c["num_attention_heads"]
    return 2 * d * hd * (c["num_attention_heads"] + c["num_key_value_heads"])


def mlp_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["shared_intermediate_size"]


def token_params(c: dict) -> int:
    """Matmul parameters every token passes: every layer's mixer and MLP; not the head."""
    m, a = layers(c)
    return m * mamba_params(c) + a * attention_params(c) + (m + a) * mlp_params(c)


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def recurrence_flops(c: dict) -> int:
    """One token through one state-space layer's own products and sums: the
    state's decay, the outer product ``dt x (x) B`` and its sum into the state
    (3 a state number), the read-out ``S C`` (2), and the convolution."""
    return 5 * state_size(c) + 2 * c["mamba_d_conv"] * conv_dim(c)


def score_flops(c: dict) -> int:
    """One (query, key) entry in one attention layer: every head's score and
    its share of the weighted sum, ``head_dim`` wide each."""
    return 4 * c["hidden_size"]


def prefill_flops(c: dict, tokens: int, scores: int, rows: int) -> float:
    """Real prompt tokens through every layer's matrices, the recurrence of
    the state-space layers, ``scores`` causal entries in each attention
    layer, and a row's last position through the head."""
    m, a = layers(c)
    return (2.0 * tokens * token_params(c) + float(tokens) * m * recurrence_flops(c)
            + float(scores) * a * score_flops(c) + 2.0 * rows * head_params(c))


def step_flops(c: dict, rows: int, attended: int) -> float:
    """One token a row: the same, against ``attended`` cache positions."""
    m, a = layers(c)
    return (2.0 * rows * (token_params(c) + head_params(c)) + float(rows) * m * recurrence_flops(c)
            + float(attended) * a * score_flops(c))


def step_bytes(c: dict, steps: int, rows: int, attended: int, weight_bytes: int = 2,
               state_bytes: int = 4) -> float:
    """What ``steps`` decode steps have to move whatever implements them: each
    every matrix once and the (tied) head; the live rows' recurrent state read
    and written in every state-space layer, with their convolution tails; the
    keys and values of the positions the rows hold, in every attention layer.
    The float32 vectors (norms, the convolution's weights: 1.6 MB of 6.4 GB)
    are left out, so that the share can only read low."""
    m, a = layers(c)
    state = 2 * state_bytes * (state_size(c) + (c["mamba_d_conv"] - 1) * conv_dim(c)) * m
    kv = 2 * c["num_key_value_heads"] * (c["hidden_size"] // c["num_attention_heads"]) * weight_bytes * a
    return (float(steps) * weight_bytes * (token_params(c) + head_params(c)) + float(rows) * state
            + float(attended) * kv)


def window_work(ctx, c: dict) -> dict | None:
    """The window's launches by kind and what the equations need for them;
    None where no decoder ran."""
    pre, scores, step = delta(ctx, "decoder.prefill"), delta(ctx, "decoder.prefill.scores"), delta(ctx, "decoder.step")
    if not pre[2] or not step[0]:
        return None
    prefills, steps = calls_delta(ctx, "decoder.prefill"), calls_delta(ctx, "decoder.step")
    return {"prefills": prefills, "steps": steps,
            "prefill_flops": prefill_flops(c, pre[2], scores[2], prefills),
            "step_flops": step_flops(c, step[0], step[2]),
            "step_bytes": step_bytes(c, steps, step[0], step[2])}
