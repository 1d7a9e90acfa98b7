"""The answer cell end to end on the CPU at a cut scale, the look for a chip
skipped: a sound run is ``correct``, the lowered reference in the program's
place is not, and a timed path broken underneath is not."""

import copy
import json

import pytest

from chipbench import check, control, control_answer, run
from chipbench.flops_decoder import llm_config

CELL = "kimi-k2.answer-rag"
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]
TINY_LLM = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32, "num_attention_heads": 4,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "num_hidden_layers": 3, "n_routed_experts": 4, "n_routed_experts_published": 16, "first_expert": 4,
    "num_experts_per_tok": 4, "vocab_size": 512,
}


def tiny_answer_cell(rate: float = 6.0):
    """The real cell's traffic, comparison and pipeline over a stand-in: a
    two-layer 64-wide embedder, a three-layer 64-wide decoder that holds
    experts 4-7 of 16, float32, 512 short documents."""
    cell = run.load_cell(CELL)
    tiny = run.load_json(run.HERE, "tests", "data", "tiny.json")
    real = cell.config
    cell.config = {
        **tiny, "pipeline": real["pipeline"], "archive_rows": 0, "search_topk": 6, "max_tokens": 6,
        "cache_rows": 4, "cache_len": 512, "serve_max_inflight": 8, "embed_max_batch": 128,
        "documents": {"median_words": 20, "sigma": 0.5, "min_words": 8, "max_words": 40},
        "llm": {**copy.deepcopy(llm_config(real)), **TINY_LLM},
    }
    cell.config["llm"]["rope_scaling"]["original_max_position_embeddings"] = 64
    # the stand-in states float32, so its readings are rounding and its limits are its own
    cell.cell["limits"] = {n: (0 if n == "malformed" else 2e-4 if "logit" in n or "token" in n else 2e-5)
                           for n in cell.cell["limits"]}
    cell.cell["warm"] = {}
    cell.traffic.update(rate=rate, sample=8, warm_seconds=0)
    return cell


def test_a_sound_run_is_correct_and_prints_the_contracts_keys():
    cell = tiny_answer_cell()
    res = run.run_cell(CELL, 2**31 + 9, 2.0, False, cell=cell)
    assert list(res) == CONTRACT_KEYS
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 12, res["compared"]
    assert set(res["metrics"]) == {"query_p50_ms", "setup_s"}
    assert set(res["compared"]) == set(cell.cell["limits"])
    json.dumps(res)


def test_the_controls_come_out_not_correct():
    """The reference in the program's place one precision below what the
    stand-in states (bfloat16 for float32): the language model's control
    fails the answer's limits, the kept control (embedder and index) the
    retrieval's; at the reference's own precision both pass."""
    cell = tiny_answer_cell()
    limits = cell.cell["limits"]
    assert check.verdict(control_answer.control_numbers(cell, 3, "f32", 2), limits)[0]
    ok, compared = check.verdict(control_answer.control_numbers(cell, 3, "bf16", 2), limits)
    assert not ok, compared
    assert check.verdict(control.control_numbers(cell, 3, "f32", 8), limits)[0]
    assert not check.verdict(control.control_numbers(cell, 3, "bf16", 8), limits)[0]


def _reuse_a_live_slot(monkeypatch):
    """The free list hands out slot 0 whatever is in it: a second answer's
    prefill overwrites the first one's cache while it decodes."""
    from pathway_tpu.ops import decoder

    class Careless(list):
        def pop(self, _i=0):
            super().pop()
            return 0

    sound = decoder.DecodeSession.__init__

    def init(self, model):
        sound(self, model)
        self.free_slots = Careless(self.free_slots)

    monkeypatch.setattr(decoder.DecodeSession, "__init__", init)


def _compute_absent_experts_here(monkeypatch):
    """Tokens routed to experts this chip does not hold are computed with a
    held expert in their place (the share is taken for the whole layer)."""
    from pathway_tpu.ops import decoder

    sound = decoder.route

    def route(lp, x, cfg):
        idx, w = sound(lp, x, cfg)
        return cfg.first_expert + idx % cfg.n_held, w

    monkeypatch.setattr(decoder, "route", route)


def _retrace():
    """A fault inside a traced function shows only in a fresh trace."""
    from pathway_tpu.ops import decoder

    decoder.prefill.fn.clear_cache()
    decoder.step.fn.clear_cache()


@pytest.mark.parametrize("fault", [_reuse_a_live_slot, _compute_absent_experts_here])
def test_a_broken_timed_path_comes_out_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    _retrace()
    try:
        # a burst, so that answers overlap in the cache
        res = run.run_cell(CELL, 11, 2.0, False, cell=tiny_answer_cell(rate=12.0))
    finally:
        monkeypatch.undo()
        _retrace()
    assert res["correct"] is False, res["compared"]


def test_the_replay_runs_the_rows_side_by_side_and_a_missing_reading_is_not_a_pass():
    """The comparison's replay of the sampled rows fills the cache from one
    row up to all of its slots (every step bucket is run) and reuses a slot a
    row has left; each row's logits are what the row gives alone. With no
    well-formed answer every decoder number still comes back, past its limit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import reference_kimi_k2 as K
    from chipbench.comparisons import answer as A
    from pathway_tpu.ops.decoder import DecoderConfig, JaxDecoder

    cell = tiny_answer_cell()
    llm = cell.config["llm"]
    m = JaxDecoder(DecoderConfig.from_hf(llm, jnp.float32),
                   K.program_params(jax.random.PRNGKey(5), llm, "float32"), cache_rows=4, cache_len=64)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, llm["vocab_size"], size=n).tolist() for n in (9, 30, 17, 5, 22, 12)]
    answers = [rng.integers(3, llm["vocab_size"], size=6).tolist() for _ in prompts]
    seen, sound = [], m.run_step

    def run_step(cache, slots, ids, positions):
        seen.append(tuple(slots))
        return sound(cache, slots, ids, positions)

    m.run_step = run_step
    together = A.replay(m, prompts, answers)
    assert {len(s) for s in seen} == {1, 2, 3, 4}  # buckets 1, 2 and 4
    assert any(s[-1] == 0 and len(s) > 1 for s in seen)  # slot 0 again, beside rows still decoding
    for i, got in enumerate(together):
        (alone,) = A.replay(m, prompts[i : i + 1], answers[i : i + 1])
        assert got.shape == (6, llm["vocab_size"])
        np.testing.assert_allclose(got, alone, atol=2e-5, rtol=0)
    unread = A.answer_numbers([], [], [])
    assert set(unread) == set(A.DECODER_NUMBERS) <= set(cell.cell["limits"])
    assert not check.verdict(unread, run.load_cell(CELL).cell["limits"])[0]
