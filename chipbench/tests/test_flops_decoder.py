"""``flops_decoder``'s shapes against the parameter tree the program is
handed at the published widths (shapes only: nothing is allocated), and its formulas by
hand at a size a head can hold."""

import jax
import jax.numpy as jnp
import pytest

from chipbench import flops, flops_decoder as F, run

LLM = F.llm_config(run.load_json(run.HERE, "configs", "adaptive-rag-kimi-k2.json"))


def tree():
    from chipbench.reference_kimi_k2 import program_params
    from pathway_tpu.ops.decoder import DecoderConfig

    cfg = DecoderConfig.from_hf(LLM, jnp.bfloat16)
    return cfg, jax.eval_shape(lambda: program_params(jax.random.PRNGKey(0), LLM, "bfloat16"))


def test_the_parameter_counts_are_the_programs():
    from pathway_tpu.ops.decoder import layer_params

    cfg, params = tree()
    matrices = lambda lp: sum(v.size for k, v in lp.items() if k.startswith("w"))  # noqa: E731
    dense, sparse = params["layers"][0], params["layers"][1]
    assert F.attention_params(LLM) == matrices(sparse) == 101_122_048
    assert F.expert_params(LLM) == sum(v.size for v in sparse["shared"].values()) == 44_040_192
    assert F.expert_params(LLM) * 12 == sum(v.size for v in sparse["experts"].values())
    assert F.router_params(LLM) == sparse["router"].size == 7168 * 384
    assert matrices(dense) == F.attention_params(LLM) + 3 * 7168 * 18432
    assert F.token_params(LLM) + 5 * 12 * F.expert_params(LLM) == (
        layer_params(cfg, sparse=False) + 5 * layer_params(cfg, sparse=True))
    assert F.head_params(LLM) == params["head"].size == params["embed"].size


def test_a_steps_bytes_are_the_leaves_it_reads():
    """No expert hit, nothing attended: the bytes of every leaf but the
    embedding, the routed experts and the norms' gains (which a step reads
    too: 0.2 MB of 2.8 GB, left out so that the share can only read low)."""
    _cfg, params = tree()
    leaves = 0
    for lp in params["layers"]:
        leaves += sum(v.size * v.dtype.itemsize for k, v in lp.items() if k.startswith("w") or k == "router")
        leaves += sum(v.size * v.dtype.itemsize for v in lp.get("shared", {}).values())
    leaves += params["head"].size * params["head"].dtype.itemsize
    assert F.step_bytes(LLM, 1, 0, 0) == leaves
    assert F.step_bytes(LLM, 3, 5, 1000) == 3 * leaves + 5 * F.expert_params(LLM) * 2 + 1000 * 576 * 2 * 6
    assert 2.7e9 < leaves < 2.9e9


def test_the_flops_by_hand():
    c = {**LLM, "hidden_size": 2, "num_attention_heads": 1, "q_lora_rank": 1, "kv_lora_rank": 1,
         "qk_nope_head_dim": 1, "qk_rope_head_dim": 1, "v_head_dim": 1, "intermediate_size": 1,
         "moe_intermediate_size": 1, "num_hidden_layers": 2, "vocab_size": 5, "n_routed_experts_published": 3}
    attn = 2 * 1 + 1 * 2 + 2 * 2 + 1 * 2 + 1 * 2  # 12
    assert F.attention_params(c) == attn and F.expert_params(c) == 6 and F.router_params(c) == 6
    assert F.token_params(c) == 2 * attn + 6 + (6 + 6) == 42
    # 10 tokens, 55 causal entries over (1 + 1 + 1) per head in 2 layers, 4 pairs, one row's head
    assert F.prefill_flops(c, 10, 55, 4, 1) == 2 * 10 * 42 + 55 * 2 * 3 * 2 + 2 * 4 * 6 + 2 * 10
    assert F.step_flops(c, 3, 20, 2) == 2 * 3 * (42 + 10) + 20 * 2 * 3 * 2 + 2 * 2 * 6


def test_the_median_prompt_is_a_few_tflop_and_a_step_is_memory_bound():
    peak = flops.peaks("TPU v5 lite")
    prompt = F.prefill_flops(LLM, 1350, 1350 * 1351 // 2, 1350 * 5 // 4, 1)
    assert 3.0e12 < prompt < 4.5e12
    _pct, bound = flops.roofline_pct(F.step_flops(LLM, 4, 4 * 1400, 5), F.step_bytes(LLM, 1, 5, 4 * 1400), 0.005, peak)
    assert bound == "memory"


@pytest.mark.parametrize("name", ["answer_mfu", "decoder_prefill_roofline.answer", "decoder_step_roofline.answer",
                                  "rows_per_decode_step.answer", "prefill_pad_share.answer",
                                  "expert_pairs_per_token.answer"])
def test_a_reader_finds_nothing_where_the_program_has_no_decoder(name):
    import types

    ctx = types.SimpleNamespace(
        config={"hidden_size": 384}, window={"latency_ms": [1.0], "end_s": 1.0, "attempted": 1},
        before={"pad": {}, "calls": {}}, after={"pad": {"encoder": [0, 0, 5, 1]}, "calls": {"encoder.encode_ids": 3}},
        trace={"seconds": {"encoder.encode_ids": 1.0}, "launches": {}}, device_kind="TPU v5 lite",
    )
    assert run.load_metric(name).read(ctx) is None
