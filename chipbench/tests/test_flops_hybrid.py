"""``flops_hybrid``'s shapes against the parameter tree the program is handed
at the published widths (shapes only: nothing is allocated) and against the
slots the program's mixers declare, and its formulas by hand at a size a head
can hold."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import arithmetic, flops, flops_hybrid as F, run

LLM = F.llm_config(run.load_json(run.HERE, "configs", "adaptive-rag-granite-4h-micro.json"))
NEW = ["llm_answer_mfu", "llm_prefill_roofline.answer", "llm_step_roofline.answer"]


def tree():
    from chipbench.reference_granite_4h import program_params
    from pathway_tpu.ops.decoder import DecoderConfig

    cfg = DecoderConfig.from_hf(LLM, jnp.bfloat16)
    return cfg, jax.eval_shape(lambda: program_params(jax.random.PRNGKey(0), LLM, "bfloat16"))


def matrices(lp: dict) -> int:
    return sum(v.size for k, v in lp.items() if k.startswith("w"))


def test_the_parameter_counts_are_the_programs():
    from pathway_tpu.ops.decoder import layer_params

    cfg, params = tree()
    mamba, attention = params["layers"][0], params["layers"][5]
    assert F.layers(LLM) == (36, 4) and [i for i, k in enumerate(cfg.mixers) if k == "gqa"] == [5, 15, 25, 35]
    assert F.mlp_params(LLM) == 3 * 2048 * 8192 == 50_331_648
    assert F.mamba_params(LLM) == matrices(mamba) - F.mlp_params(LLM) == 2048 * 8512 + 4096 * 2048
    assert F.attention_params(LLM) == matrices(attention) - F.mlp_params(LLM) == 10_485_760
    assert F.head_params(LLM) == params["embed"].size == 100352 * 2048 and "head" not in params
    small = sum(v.size for k, v in mamba.items() if not k.startswith("w") and "norm" not in k or k == "gate_norm")
    assert small == 5 * 4352 + 3 * 64 + 4096  # the convolution, dt_bias, A_log, D, the gated norm's gain
    assert F.token_params(LLM) + 36 * small == sum(layer_params(cfg, mixer=k) for k in cfg.mixers)
    assert 3.18e9 < F.token_params(LLM) + F.head_params(LLM) < 3.20e9


def test_a_steps_bytes_are_the_leaves_it_reads_and_the_slots_of_its_rows():
    """No row: every matrix and the tied embedding once. A row: its recurrent
    slots read and written, and one position of keys and values a layer."""
    from pathway_tpu.ops.mixers import MIXERS

    cfg, params = tree()
    leaves = sum(v.size * v.dtype.itemsize for lp in params["layers"] for k, v in lp.items() if k.startswith("w"))
    leaves += params["embed"].size * params["embed"].dtype.itemsize
    assert F.step_bytes(LLM, 1, 0, 0) == leaves and 6.3e9 < leaves < 6.45e9
    slot = {kind: sum(int(np.prod(shape)) * jnp.dtype(dt).itemsize for shape, dt in MIXERS[kind].slot(cfg, 1))
            for kind in ("mamba2", "gqa")}
    # the program keeps the convolution's 3 inputs in a whole tile of 8 rows; the model needs the 3
    assert slot == {"mamba2": 4 * (64 * 64 * 128 + 8 * 4352), "gqa": 2 * 2 * 8 * 64}
    needed = slot["mamba2"] - 4 * 5 * 4352
    assert F.step_bytes(LLM, 3, 5, 1000) == 3 * leaves + 5 * 36 * 2 * needed + 1000 * 4 * slot["gqa"]
    assert round(36 * 2 * needed / 1e6, 1) == 154.8  # a live row's state traffic a step, MB


def test_the_flops_by_hand():
    c = {**LLM, "hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1, "shared_intermediate_size": 3,
         "mamba_n_heads": 2, "mamba_d_head": 4, "mamba_d_state": 3, "mamba_d_conv": 2, "vocab_size": 5,
         "layer_types": ["mamba", "attention", "mamba"]}
    assert F.conv_dim(c) == 14 and F.state_size(c) == 24
    assert F.mamba_params(c) == 4 * (8 + 14 + 2) + 8 * 4 == 128
    assert F.attention_params(c) == 2 * 4 * 2 * (2 + 1) == 48 and F.mlp_params(c) == 36
    assert F.token_params(c) == 2 * 128 + 48 + 3 * 36 == 412
    assert F.recurrence_flops(c) == 5 * 24 + 2 * 2 * 14 == 176 and F.score_flops(c) == 16
    # 10 tokens, 55 causal entries in the one attention layer, one row's head
    assert F.prefill_flops(c, 10, 55, 1) == 2 * 10 * 412 + 10 * 2 * 176 + 55 * 16 + 2 * 20
    assert F.step_flops(c, 3, 20) == 2 * 3 * (412 + 20) + 3 * 2 * 176 + 20 * 16
    state, kv = 2 * 4 * (24 + 1 * 14) * 2, 2 * 1 * 2 * 2 * 1
    assert F.step_bytes(c, 2, 3, 20) == 2 * 2 * (412 + 20) + 3 * state + 20 * kv


def test_the_median_prompt_is_some_nine_tflop_and_a_step_is_memory_bound():
    peak = flops.peaks("TPU v5 lite")
    prompt = F.prefill_flops(LLM, 1350, 1350 * 1351 // 2, 1)
    assert 8.0e12 < prompt < 9.5e12
    assert 2.0 * 1350 * F.token_params(LLM) / prompt > 0.95  # plain products are nearly all of it
    _pct, bound = flops.roofline_pct(F.step_flops(LLM, 4, 4 * 1400), F.step_bytes(LLM, 1, 4, 4 * 1400), 0.01, peak)
    assert bound == "memory"


def ctx_of(pad_after: dict, config: dict):
    return types.SimpleNamespace(
        config=config, window={"latency_ms": [1.0], "end_s": 2.0, "attempted": 1},
        before={"pad": {}, "calls": {}}, after={"pad": pad_after, "calls": {"decoder.prefill": 2, "decoder.step": 10}},
        trace={"seconds": {"decoder.prefill": 0.5, "decoder.step": 0.25},
               "launches": {"decoder.prefill": 2, "decoder.step": 10}}, device_kind="TPU v5 lite",
    )


def test_the_readers_read_the_counters():
    pad = {"decoder.prefill": [2, 0, 3000, 1096], "decoder.prefill.scores": [0, 0, 2_000_000, 500_000],
           "decoder.step": [30, 10, 45_000, 77_880], "decoder.prefill.scan": [0, 0, 3000, 1096]}
    ctx = ctx_of(pad, {"llm": LLM})
    assert LLM["arithmetic"] == F.__name__ and arithmetic.window_work(ctx) == F.window_work(ctx, LLM)
    peak = flops.peaks("TPU v5 lite")
    pre, step = F.prefill_flops(LLM, 3000, 2_000_000, 2), F.step_flops(LLM, 30, 45_000)
    got = {name: run.load_metric(name).read(ctx) for name in NEW}
    assert got["llm_answer_mfu"] == pytest.approx(100 * (pre + step) / 2.0 / peak["bf16_flops"])
    assert got["llm_prefill_roofline.answer"] == pytest.approx(100 * pre / peak["bf16_flops"] / 0.5)
    assert got["llm_step_roofline.answer"] == pytest.approx(
        100 * F.step_bytes(LLM, 10, 30, 45_000) / peak["hbm_bytes_per_s"] / 0.25)
    assert all(0 < v < 100 for v in got.values())
    # a trace that holds half the window's launches in half the time reads the same shares
    ctx.trace = {"seconds": {"decoder.prefill": 0.25, "decoder.step": 0.125},
                 "launches": {"decoder.prefill": 1, "decoder.step": 5}}
    for name in NEW[1:]:
        assert run.load_metric(name).read(ctx) == pytest.approx(got[name])


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_where_no_arithmetic_is_named_or_no_decoder_ran(name):
    """A language model whose configuration names no arithmetic (the Kimi
    cell's), a window in which no decoder ran, a trace that holds no decoder
    launch, and a cell with no language model."""
    kimi = F.llm_config(run.load_json(run.HERE, "configs", "adaptive-rag-kimi-k2.json"))
    ran = {"decoder.prefill": [2, 0, 3000, 1096], "decoder.prefill.scores": [0, 0, 2_000_000, 500_000],
           "decoder.step": [30, 10, 45_000, 77_880]}
    assert run.load_metric(name).read(ctx_of(ran, {"llm": kimi})) is None
    assert run.load_metric(name).read(ctx_of({"encoder": [0, 0, 5, 1]}, {"llm": LLM})) is None
    assert run.load_metric(name).read(ctx_of({"encoder": [0, 0, 5, 1]}, {"hidden_size": 384})) is None
    unheld = ctx_of(ran, {"llm": LLM})
    unheld.trace = {"seconds": {}, "launches": {}}
    assert (run.load_metric(name).read(unheld) is None) == ("roofline" in name)
