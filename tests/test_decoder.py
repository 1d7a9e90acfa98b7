"""``ops/decoder.py`` against the plain reference (``reference_decoder.py``)
on seeded weights at a small size: prefill and decode steps through the
latent cache against the full forward pass, the absorbed decode path against
the up-projected one, one chip's share of the experts against the uncut
layer, the router by hand, the bucketing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_decoder as ref
import reference_hybrid
from pathway_tpu.ops import decoder as D

LLM = ref.TINY
#: the two kinds of model a session serves: latent attention with experts, and state-space with grouped-query layers
REFERENCES = {"latent": ref, "hybrid": reference_hybrid}
#: Kimi-K2-Instruct's published shapes (the catalog's row), one chip's share of 32
K2 = {
    **LLM, "vocab_size": 20480, "hidden_size": 7168, "num_hidden_layers": 6, "num_attention_heads": 64,
    "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "intermediate_size": 18432, "moe_intermediate_size": 2048, "n_routed_experts": 12,
    "n_routed_experts_published": 384, "first_expert": 0, "num_experts_per_tok": 8,
    "rope_scaling": {**LLM["rope_scaling"], "original_max_position_embeddings": 4096},
}
LENGTHS, STEPS = (37, 90, 5, 64), 6


def model(dtype, **kw) -> D.JaxDecoder:
    cfg = D.DecoderConfig.from_hf(LLM, dtype)
    return D.JaxDecoder(cfg, ref.init_params(LLM, 3, dtype), cache_rows=4, cache_len=128, **kw)


def prompts(seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, LLM["vocab_size"], size=n).astype(np.int32) for n in LENGTHS]


def served_logits(m: D.JaxDecoder, rows, steps):
    """Greedy tokens and the logits behind each, ``[row][step]``: one prefill
    launch of all the rows, then ``steps - 1`` decode steps through the cache."""
    cache, slots = m.new_cache(), list(range(len(rows)))
    out, logits, cache, _L = m.run_prefill(cache, slots, rows)
    toks, logs = [np.asarray(out)[: len(rows)]], [np.asarray(logits)[: len(rows)]]
    pos = [len(r) for r in rows]
    for _ in range(steps - 1):
        out, logits, cache, _R = m.run_step(cache, slots, toks[-1].tolist(), pos)
        toks.append(np.asarray(out)[: len(rows)])
        logs.append(np.asarray(logits)[: len(rows)])
        pos = [p + 1 for p in pos]
    return np.stack(toks, 1), np.stack(logs, 1)


def reference_logits(m: D.JaxDecoder, rows, toks, precision="f32"):
    """The reference's full forward pass over prompt + generated tokens: the
    logits at every generated position."""
    out = []
    for r, t in zip(rows, toks):
        full = ref.forward(m.params, LLM, list(r) + t[:-1].tolist(), precision, width=128)
        out.append(full[len(r) - 1:])
    return np.stack(out)


def test_prefill_and_decode_through_the_cache_agree_with_the_full_forward_pass():
    m = model(jnp.float32)
    rows = prompts()
    toks, logs = served_logits(m, rows, STEPS)
    want = reference_logits(m, rows, toks)
    assert logs.shape == want.shape == (len(rows), STEPS, LLM["vocab_size"])
    np.testing.assert_allclose(logs, want, atol=2e-5, rtol=0)
    assert (np.argmax(want, -1) == toks).all()


def test_the_bfloat16_program_holds_a_tolerance_that_an_fp8_reference_fails():
    """Error over the generated positions relative to the logits' spread. A
    position's largest error turns on whether a token's chosen experts flip
    on rounding (every expert is held here, so every flip shows: one position
    in a hundred reads 0.4-0.9), so what is held is the mean and the median
    of the positions' largest: bfloat16 reads 0.006-0.014 and 0.02, an
    fp8-e4m3 run of the reference 0.14-0.19 and 0.5."""
    m = model(jnp.bfloat16)
    rows = prompts(1)
    toks, logs = served_logits(m, rows, STEPS)
    want = reference_logits(m, rows, toks)
    low = reference_logits(m, rows, toks, "fp8")
    spread = want.std(-1, keepdims=True)
    err, err_low = np.abs(logs - want) / spread, np.abs(low - want) / spread
    assert err.mean() < 0.04 < err_low.mean(), (err.mean(), err_low.mean())
    assert np.median(err.max(-1)) < 0.15 < np.median(err_low.max(-1)), (err.max(-1), err_low.max(-1))


def test_the_absorbed_decode_path_equals_the_up_projected_path():
    """A step scores against the latents with ``W_kvb`` folded into the query;
    a prefill of the same tokens up-projects keys and values. Same logits."""
    m = model(jnp.float32)
    rows = prompts(2)
    toks, logs = served_logits(m, rows, STEPS)
    for n in (1, STEPS - 1):
        longer = [np.concatenate([r, t[:n]]).astype(np.int32) for r, t in zip(rows, toks)]
        _o, logits, _cache, _L = m.run_prefill(m.new_cache(), list(range(len(rows))), longer)
        np.testing.assert_allclose(np.asarray(logits)[: len(rows)], logs[:, n], atol=2e-5, rtol=0)


def test_a_rows_tokens_do_not_depend_on_its_batch():
    m = model(jnp.float32)
    rows = prompts(3)
    together = D.generate(m, rows, [5, 9, 3, 7])
    alone = [D.generate(m, [r], [n])[0] for r, n in zip(rows, [5, 9, 3, 7])]
    assert together == alone and [len(t) for t in together] == [5, 9, 3, 7]


def sparse_layer(seed=5):
    cfg = D.DecoderConfig.from_hf(LLM, jnp.float32)
    lp = ref.init_params(LLM, seed)["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(seed), (50, LLM["hidden_size"]), jnp.float32)
    return cfg, lp, x


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Four chips of four experts each: the parts all the shares give, the
    shared expert (which every chip computes alike) counted once, are what the
    uncut reference gives for the whole layer."""
    cfg, lp, x = sparse_layer()
    valid = jnp.ones((x.shape[0],), bool)
    shared = D._swiglu(x, lp["shared"], jnp.float32)
    total = shared
    for first in range(0, 16, 4):
        share = D.DecoderConfig.from_hf({**LLM, "n_routed_experts": 4, "n_routed_experts_published": 16,
                                         "first_expert": first}, jnp.float32)
        held = {**lp, "experts": {k: v[first : first + 4] for k, v in lp["experts"].items()}}
        part, stats = D._ffn(held, x, valid, share, 8)
        total = total + (part - shared)
        assert 0 < int(stats[0]) < x.shape[0] * 4 and int(stats[2]) <= 4
    np.testing.assert_allclose(total, ref.ffn(lp, x, LLM, "f32"), atol=2e-5, rtol=0)
    one, stats = D._ffn(lp, x, valid, cfg, 8)
    np.testing.assert_allclose(one, total, atol=2e-5, rtol=0)
    assert int(stats[0]) == x.shape[0] * 4  # every pair is here when every expert is


@pytest.mark.parametrize("block", [8, 128])
def test_no_pair_is_dropped_when_every_token_picks_the_same_experts(block):
    """The grouped product's buffer holds every pair the tokens could make:
    a selection bias that sends all tokens to experts 0-3 fills four groups
    with a row a token each, and the layer still equals the reference."""
    cfg, lp, x = sparse_layer(6)
    lp = {**lp, "router_bias": jnp.where(jnp.arange(16) < 4, 10.0, 0.0)}
    valid = jnp.arange(x.shape[0]) < 40  # ten padding tokens: no pair of theirs is computed
    got, stats = D._ffn(lp, x, valid, cfg, block)
    assert stats.tolist() == [160, 4 * -(-40 // block) * block, 4]
    np.testing.assert_allclose(got[:40], ref.ffn(lp, x, LLM, "f32")[:40], atol=2e-5, rtol=0)
    np.testing.assert_allclose(got[40:], D._swiglu(x[40:], lp["shared"], jnp.float32), atol=2e-5, rtol=0)
    assert D.expert_rows(50, cfg, block) >= 4 * -(-50 // block) * block


def test_the_router_selects_by_score_plus_bias_and_weighs_by_score():
    """By hand: scores sigmoid(0, ln 3, -ln 3, ln 9) = 0.5, 0.75, 0.25, 0.9;
    with the bias (0.3, 0, 0, -0.6) the two largest ``s + b`` are experts 0
    (0.8) and 1 (0.75) though expert 3 scores highest; the weights come from
    the scores alone: 0.5 and 0.75 over their sum, times the scaling 2."""
    cfg = D.DecoderConfig(hidden_size=2, n_routed_experts=4, n_held=4, experts_per_token=2,
                          routed_scaling_factor=2.0, dtype=jnp.float32)
    lp = {"router": jnp.asarray([[0.0, np.log(3.0), -np.log(3.0), np.log(9.0)], [0.0] * 4], jnp.float32),
          "router_bias": jnp.asarray([0.3, 0.0, 0.0, -0.6], jnp.float32)}
    idx, w = D.route(lp, jnp.asarray([[1.0, 0.0]]), cfg)
    assert idx.tolist() == [[0, 1]]
    np.testing.assert_allclose(w, [[0.8, 1.2]], rtol=1e-6)
    gates = ref.routing(lp, jnp.asarray([[1.0, 0.0]]), {"num_experts_per_tok": 2, "norm_topk_prob": True,
                                                        "routed_scaling_factor": 2.0})
    np.testing.assert_allclose(gates, [[0.8, 1.2, 0.0, 0.0]], rtol=1e-6)


def test_yarn_keeps_fast_dimensions_and_divides_slow_ones():
    """Published Kimi-K2 numbers: of the 32 rotary frequencies those that turn
    more than once over the original 4,096 positions (the first 19) are kept,
    the rest divided by the factor 32; the scores are scaled by 192^-1/2 m^2,
    m = 0.1 ln 32 + 1 = 1.3466; cos and sin by mscale / mscale_all_dim = 1."""
    cfg = D.DecoderConfig.from_hf(K2)
    f = D.rope_inv_freq(cfg)
    base = 50000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(f[:19], base[:19], rtol=1e-6)
    np.testing.assert_allclose(f[20:], base[20:] / 32, rtol=1e-6)
    assert D.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * 1.3466 ** 2, rel=1e-4)
    inv_freq, mult, scale = ref._yarn(K2)
    np.testing.assert_allclose(inv_freq, f, rtol=1e-6)
    assert mult == 1.0 and scale == pytest.approx(D.softmax_scale(cfg))


def test_the_share_is_the_arithmetic_of_the_cut():
    """One latent-attention block 101.1M, a sparse layer with 12 of 384
    experts 676.4M (whole: 17.06B), the dense layer 497.5M parameters."""
    cfg = D.DecoderConfig.from_hf(K2)
    attn = 7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256 + 64 * 128 * 7168
    assert (cfg.n_routed_experts, cfg.n_held, cfg.latent_dim, cfg.n_sparse_layers) == (384, 12, 576, 5)
    assert D.layer_params(cfg, sparse=False) == attn + 3 * 7168 * 18432 == 497_483_776
    assert D.layer_params(cfg, sparse=True) == attn + 7168 * 384 + 13 * 3 * 7168 * 2048 == 676_397_056
    assert round(D.layer_params(cfg, sparse=True, experts=384) / 1e9, 2) == 17.06


def test_warm_compiles_every_shape_serving_asks_for():
    m = model(jnp.float32)
    m.warm()
    before = len(D.prefill._seen), len(D.step._seen)
    assert m.length_buckets == (128,) and m.step_buckets == (1, 2, 4) and D.PREFILL_ROWS == 1
    D.generate(m, prompts(4) + prompts(5), [3, 4, 5, 6, 7, 2, 1, 4])
    assert (len(D.prefill._seen), len(D.step._seen)) == before


def test_a_long_prompt_keeps_its_end_and_the_cache_bounds_the_answer():
    m = model(jnp.float32)
    long = np.arange(3, 3 + 200, dtype=np.int32) % 250 + 3
    (out,) = D.generate(m, [long], [8])
    (tail,) = D.generate(m, [long[-127:]], [8])
    assert out == tail and len(out) == 1  # 127 of 128 positions hold the prompt: one token fits


def tiny(kind: str, cache_rows: int = 2) -> D.JaxDecoder:
    r = REFERENCES[kind]
    cfg = D.DecoderConfig.from_hf(r.TINY, jnp.float32)
    return D.JaxDecoder(cfg, r.init_params(r.TINY, 3, jnp.float32), cache_rows=cache_rows, cache_len=128)


def host_fed(m: D.JaxDecoder, prompt, n: int) -> list[int]:
    """A row alone, every step fed on the host the token the step before it gave."""
    out, _l, cache, _L = m.run_prefill(m.new_cache(), [0], [prompt])
    toks = [int(np.asarray(out)[0])]
    for i in range(n - 1):
        out, _l, cache, _R = m.run_step(cache, [0], [toks[-1]], [len(prompt) + i])
        toks.append(int(np.asarray(out)[0]))
    return toks


@pytest.mark.parametrize("kind", sorted(REFERENCES))
def test_a_session_with_lookahead_gives_the_tokens_of_steps_fed_from_the_host(kind, monkeypatch):
    """Two slots. ``a`` (5 tokens) and ``c`` (2) decode; ``b`` (1) is done at
    its prefill; ``d`` joins while a step is pending and is cancelled under the
    next one, and ``e`` is prefilled into its slot before that step is
    fetched; then a drain. Every row's tokens are what it gives alone with
    host ids, ``d`` is never returned, and each launch is counted as launched
    ahead of an unfetched step or not: only the first found none."""
    from pathway_tpu import observability as obs
    from pathway_tpu.observability import device as prof

    m = tiny(kind)
    r = REFERENCES[kind]
    rng = np.random.default_rng(8)
    p = {h: rng.integers(3, r.TINY["vocab_size"], size=n).astype(np.int32)
         for h, n in zip("abcde", (37, 9, 21, 50, 4))}
    want = {h: host_fed(m, p[h], n) for h, n in zip("abce", (5, 1, 2, 3))}
    monkeypatch.setenv("PATHWAY_TRACE", "on")
    tracer = obs.install_from_env()  # the run's counters start afresh
    before = list(prof.stats().pad.get("decoder.step.ahead", [0, 0, 0, 0]))
    try:
        tracer.begin_tick(0)
        s, got = D.DecodeSession(m), {}
        assert s.admit([("a", p["a"], 5), ("c", p["c"], 2)]) == []
        got.update(s.step())  # launches a and c, then a again before it fetches: c is done
        assert set(got) == {"c"} and s.live() == 1 and s.free() == 1
        got.update(s.admit([("b", p["b"], 1)]))  # done at its prefill, its slot free again
        assert s.admit([("d", p["d"], 6)]) == [] and s.free() == 0
        got.update(s.step())  # launches a and d, fetches a's
        s.cancel("d")  # under the pending step
        assert s.free() == 1 and s.admit([("e", p["e"], 3)]) == []  # d's slot, prefilled before d's step is fetched
        while s.live():
            got.update(s.step())
        spans, _ = tracer.buffer.since(0)
    finally:
        obs.shutdown()
    assert got == want
    assert np.subtract(prof.stats().pad["decoder.step.ahead"], before).tolist() == [4, 1, 0, 0]
    ahead = [{a["key"]: a["value"] for a in x["attributes"]}["pathway.ahead"] for x in spans
             if x["name"] == "generate/step"]
    assert ahead == [{"intValue": "1"}] * 4 + [{"intValue": "0"}]  # the last fetch left nothing to launch


@pytest.mark.parametrize("kind", sorted(REFERENCES))
def test_a_host_id_is_fed_as_given_and_a_negative_one_is_the_slots_newest_token(kind):
    """Steps fed tokens that are not the greedy ones (as the benchmark's
    replay feeds the served answer) give the full forward pass over those
    tokens; fed -1 they give what the greedy tokens fed from the host give."""
    m = tiny(kind, cache_rows=4)
    r = REFERENCES[kind]
    rows = [np.asarray(x, np.int32) for x in np.random.default_rng(9).integers(3, r.TINY["vocab_size"], (3, 12))]
    fed = np.random.default_rng(10).integers(3, r.TINY["vocab_size"], (3, 4))
    slots = [2, 0, 3]

    def steps(ids_of):
        out, _l, cache, _L = m.run_prefill(m.new_cache(), slots, rows)
        toks, logs = np.asarray(out)[:3], []
        for i in range(4):
            out, logits, cache, _R = m.run_step(cache, slots, ids_of(i, toks), [12 + i] * 3)
            toks = np.asarray(out)[:3]
            logs.append(np.asarray(logits)[:3])
        return np.stack(logs, 1)

    given = steps(lambda i, _t: fed[:, i].tolist())
    for j, row in enumerate(rows):
        full = r.forward(m.params, r.TINY, list(row) + fed[j].tolist(), "f32", width=128)
        np.testing.assert_allclose(given[j], full[len(row):], atol=2e-5, rtol=0)
    np.testing.assert_array_equal(steps(lambda _i, _t: [-1] * 3), steps(lambda _i, t: t.tolist()))
