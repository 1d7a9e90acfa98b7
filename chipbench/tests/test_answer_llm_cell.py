"""The hybrid answer cell end to end on the CPU at a cut scale, the look for
a chip skipped: the new files load, the cell builds without a parent, a sound
run is ``correct`` and the comparison returns every number; the lowered
reference and the program with its recurrent state lowered are not correct,
nor is a timed path broken underneath."""

import copy
import json

from chipbench import check, control_answer_llm, run
from chipbench.flops_decoder import llm_config

CELL = "granite-4h-micro.answer-rag-overlap"
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]
#: one whole period of the published pattern, 64 wide
TINY_LLM = {
    "hidden_size": 64, "intermediate_size": 128, "shared_intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "attention_multiplier": 0.0625, "num_hidden_layers": 10,
    "layer_types": ["mamba"] * 5 + ["attention"] + ["mamba"] * 4, "mamba_n_heads": 8, "mamba_d_head": 16,
    "mamba_d_state": 16, "mamba_chunk_size": 8, "vocab_size": 512,
}


def tiny_cell(rate: float = 6.0):
    """The real cell's traffic, comparison and pipeline over a stand-in: a
    two-layer 64-wide embedder, a ten-layer 64-wide hybrid decoder, float32,
    512 short documents."""
    cell = run.load_cell(CELL)
    tiny = run.load_json(run.HERE, "tests", "data", "tiny.json")
    real = cell.config
    cell.config = {
        **tiny, "pipeline": real["pipeline"], "archive_rows": 0, "search_topk": 6, "max_tokens": 6,
        "cache_rows": 4, "cache_len": 512, "serve_max_inflight": 8, "embed_max_batch": 128,
        "documents": {"median_words": 20, "sigma": 0.5, "min_words": 8, "max_words": 40},
        "llm": {**copy.deepcopy(llm_config(real)), **TINY_LLM},
    }
    # the stand-in states float32, so its readings are rounding and its limits are its own
    cell.cell["limits"] = {n: (0 if n == "malformed" else 2e-4 if "logit" in n or "token" in n else 2e-5)
                           for n in cell.cell["limits"]}
    cell.cell["warm"] = {}
    cell.traffic.update(rate=rate, sample=8, warm_seconds=0)
    return cell


def test_the_new_files_are_found_by_name():
    from chipbench.comparisons import answer_llm as comparison
    from chipbench.pipelines import answer_llm as pipeline

    cell = run.load_cell(CELL)
    llm = llm_config(cell.config)
    assert cell.config["pipeline"] == "answer_llm" and cell.traffic["comparison"] == "answer_llm"
    assert cell.traffic["generator"] == "answer" and cell.config["max_tokens"] == 96
    assert pipeline.reference_module(llm).__name__ == "chipbench.reference_granite_4h"
    assert set(comparison.DECODER_NUMBERS) <= set(cell.cell["limits"])
    assert {m["name"] for m in cell.end_to_end} == {"query_p50_ms", "setup_s"}
    new = {"llm_answer_mfu", "llm_prefill_roofline.answer", "llm_step_roofline.answer"}
    assert new <= {m["name"] for m in cell.per_layer} and all(run.load_metric(n).read for n in new)
    kimi = run.load_json(run.HERE, "configs", "adaptive-rag-kimi-k2.json")
    same = ["reserved_space", "live_documents", "documents", "retrieve_k", "search_topk", "serve_max_inflight",
            "cache_rows", "cache_len", "embed_max_batch", "hidden_size", "num_hidden_layers", "compute_dtype"]
    assert all(cell.config[k] == kimi[k] for k in same)


def test_a_sound_run_is_correct_and_prints_the_contracts_keys():
    cell = tiny_cell()
    res = run.run_cell(CELL, 2**31 + 9, 2.0, False, cell=cell)
    assert list(res) == CONTRACT_KEYS
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 12, res["compared"]
    assert set(res["metrics"]) == {"query_p50_ms", "setup_s"}
    assert set(res["compared"]) == set(cell.cell["limits"])
    json.dumps(res)


def test_the_controls_come_out_not_correct():
    """One precision below what the stand-in states (bfloat16 for float32):
    the reference's matmul operands, and the program's recurrent state; at
    their own precision both pass."""
    cell = tiny_cell()
    limits = cell.cell["limits"]
    assert check.verdict(control_answer_llm.control_numbers(cell, 3, "f32", 2), limits)[0]
    ok, compared = check.verdict(control_answer_llm.control_numbers(cell, 3, "bf16", 2), limits)
    assert not ok, compared
    assert check.verdict(control_answer_llm.control_numbers(cell, 3, "f32", 2, state_dtype="float32"), limits)[0]
    ok, compared = check.verdict(control_answer_llm.control_numbers(cell, 3, "f32", 2, state_dtype="bfloat16"), limits)
    assert not ok, compared


def test_a_recurrent_slot_handed_on_unwritten_comes_out_not_correct(monkeypatch):
    """A prefill that leaves the recurrent state of a slot as it found it (a
    slot handed on dirty, as a positional slot may be): the next answer in
    that slot starts from its predecessor's history."""
    from pathway_tpu.ops import decoder, mixers

    sound = mixers.MIXERS["mamba2"]

    def prefill(lp, x, arrays, slots, lengths, rope, cfg):
        out, (_ssm, conv) = sound.prefill(lp, x, arrays, slots, lengths, rope, cfg)
        return out, (arrays[0], conv)

    def retrace():
        decoder.prefill.fn.clear_cache()
        decoder.step.fn.clear_cache()

    monkeypatch.setitem(mixers.MIXERS, "mamba2", sound._replace(prefill=prefill))
    retrace()
    try:
        res = run.run_cell(CELL, 11, 2.0, False, cell=tiny_cell(rate=12.0))
    finally:
        monkeypatch.undo()
        retrace()
    assert res["correct"] is False, res["compared"]
