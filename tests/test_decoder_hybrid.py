"""``ops/decoder.py`` with state-space and grouped-query layers against the
plain reference (``reference_hybrid.py``) on seeded weights at a small size
with one whole period of the published pattern (ten layers: five ``mamba``,
one ``attention``, four ``mamba``): prefill and steps through the two kinds
of slot against the full forward pass, the chunked scan against the
recurrence, a row against its launch, its length bucket and its slot's past,
and the published configuration's layer order and parameter count."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_hybrid as ref
from pathway_tpu.ops import decoder as D
from pathway_tpu.ops import mixers as M

LLM = ref.TINY
#: not multiples of the chunk (4); one shorter than ``d_conv - 1``; one a multiple
LENGTHS, STEPS = (37, 2, 90, 7, 64), 6
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = os.path.join(ROOT, "chipbench", "configs", "adaptive-rag-granite-4h-micro.llm.json")


def model(dtype=jnp.float32, cache_rows=8, cache_len=128, **cfg_kw) -> D.JaxDecoder:
    cfg = dataclasses.replace(D.DecoderConfig.from_hf(LLM, dtype), **cfg_kw)
    return D.JaxDecoder(cfg, ref.init_params(LLM, 3, dtype), cache_rows=cache_rows, cache_len=cache_len)


def prompts(seed=0, lengths=LENGTHS):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, LLM["vocab_size"], size=n).astype(np.int32) for n in lengths]


def served_logits(m: D.JaxDecoder, rows, steps, cache=None, slots=None, one_launch=True):
    """Greedy tokens and the logits behind each, ``[row][step]``: the rows
    prefilled (in one launch, or a launch each), then ``steps - 1`` decode
    steps through the cache."""
    cache = m.new_cache() if cache is None else cache
    slots = list(range(len(rows))) if slots is None else slots
    if one_launch:
        out, logits, cache, _L = m.run_prefill(cache, slots, rows)
        first, logs = np.asarray(out)[: len(rows)], [np.asarray(logits)[: len(rows)]]
    else:
        first, each = [], []
        for slot, row in zip(slots, rows):
            out, logits, cache, _L = m.run_prefill(cache, [slot], [row])
            first.append(np.asarray(out)[0])
            each.append(np.asarray(logits)[0])
        first, logs = np.asarray(first), [np.stack(each)]
    toks, pos = [first], [len(r) for r in rows]
    for _ in range(steps - 1):
        out, logits, cache, _R = m.run_step(cache, slots, toks[-1].tolist(), pos)
        toks.append(np.asarray(out)[: len(rows)])
        logs.append(np.asarray(logits)[: len(rows)])
        pos = [p + 1 for p in pos]
    return np.stack(toks, 1), np.stack(logs, 1), cache


def reference_logits(m: D.JaxDecoder, rows, toks, precision="f32"):
    """The reference's full forward pass over prompt + generated tokens: the
    logits at every generated position."""
    out = []
    for r, t in zip(rows, toks):
        full = ref.forward(m.params, LLM, list(r) + t[:-1].tolist(), precision, width=128)
        out.append(full[len(r) - 1:])
    return np.stack(out)


#: float32 program against float32 reference, in absolute logits (their spread is 0.010)
ATOL = 1e-6


def test_prefill_and_steps_through_both_kinds_of_slot_agree_with_the_full_forward_pass():
    m = model()
    rows = prompts()
    toks, logs, _cache = served_logits(m, rows, STEPS)
    want = reference_logits(m, rows, toks)
    assert logs.shape == want.shape == (len(rows), STEPS, LLM["vocab_size"])
    assert 0.005 < want.std(-1).mean() < 0.05  # the spread ATOL is a ten-thousandth of
    np.testing.assert_allclose(logs, want, atol=ATOL, rtol=0)
    assert (np.argmax(want, -1) == toks).all()
    assert len({tuple(t) for t in toks.tolist()}) == len(rows) and all(len(set(t)) > 2 for t in toks.tolist())


def test_a_state_held_in_bfloat16_fails_that_tolerance(monkeypatch):
    """The control: the recurrent slots stored as bfloat16 (a cast no
    configuration can ask for: the slot's declared type is patched),
    everything else float32."""
    monkeypatch.setattr(M, "STATE_DTYPE", jnp.bfloat16)
    m = model()
    rows = prompts()
    toks, logs, _cache = served_logits(m, rows, STEPS)
    err = np.abs(logs - reference_logits(m, rows, toks))
    assert err[:, 0].max() < ATOL  # a prefill's own logits do not pass through a slot
    assert err[:, 1:].max() > 20 * ATOL


@pytest.mark.parametrize("chunk", [4, 16, 64])
def test_the_chunked_scan_is_the_recurrence(chunk):
    """``scan_chunked`` against the recurrence a position at a time, at a
    length that is no multiple of the chunk, with a stretch of padding
    (``dt = 0``) inside: the output everywhere and the state at the end, which
    the padding leaves as it was."""
    R, L, H, P, N = 2, 45, 3, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(chunk), 5)
    x, B, C = (jax.random.normal(k, s) for k, s in zip(ks, [(R, L, H, P), (R, L, N), (R, L, N)]))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (R, L, H)) - 2.0).at[0, 30:].set(0.0)
    A = -jnp.exp(jax.random.uniform(ks[4], (H,), minval=0.0, maxval=2.5))
    y, last = M.scan_chunked(x, dt, A, B, C, chunk, jnp.float32)

    def one(S, t):
        x_t, B_t, C_t, dt_t = t
        S = jnp.exp(dt_t * A)[:, None, None] * S + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :]
        return S, jnp.einsum("hpn,n->hp", S, C_t, precision="highest")

    for r in range(R):
        want_last, want = jax.lax.scan(one, jnp.zeros((H, P, N)), (x[r], B[r], C[r], dt[r]))
        np.testing.assert_allclose(y[r], want, atol=2e-5, rtol=0)
        np.testing.assert_allclose(last[r], want_last, atol=2e-5, rtol=0)
    short, at30 = M.scan_chunked(x[:1, :30], dt[:1, :30], A, B[:1, :30], C[:1, :30], chunk, jnp.float32)
    np.testing.assert_allclose(last[0], at30[0], atol=1e-6, rtol=0)


def test_a_rows_logits_depend_neither_on_its_launch_nor_on_its_length_bucket():
    """The same row alone, in a launch of four, and at two length buckets
    (a cache of 128 pads a prompt of 37 to 128; one of 1024 to 512)."""
    m = model()
    rows = prompts(1, (37, 90, 2, 21))
    _t, together, _c = served_logits(m, rows, STEPS)
    wide = model(cache_len=1024)
    assert m.length_buckets == (128,) and wide.length_buckets == (512, 1024)
    for i, row in enumerate(rows):
        _t, alone, _c = served_logits(m, [row], STEPS)
        _t, padded, _c = served_logits(wide, [row], STEPS)
        np.testing.assert_allclose(together[i], alone[0], atol=ATOL, rtol=0)
        np.testing.assert_allclose(padded[0], alone[0], atol=ATOL, rtol=0)


def test_a_steps_padding_row_moves_no_slot_not_even_the_last_one():
    """Three live rows in a step bucket of four, one of them in the cache's
    last slot (where a padding row's slot number is clamped to): each row's
    logits are what it gives alone, and the slot no row holds stays untouched."""
    m = model(cache_rows=4)
    rows, slots = prompts(7, (11, 30, 5)), [3, 0, 2]
    _t, logs, cache = served_logits(m, rows, STEPS, slots=slots, one_launch=False)
    for i, row in enumerate(rows):
        _t, alone, _c = served_logits(m, [row], STEPS)
        np.testing.assert_allclose(logs[i], alone[0], atol=ATOL, rtol=0)
    recurrent = [a for arrays, kind in zip(cache, m.cfg.mixers) if kind == "mamba2" for a in arrays]
    assert len(recurrent) == 18 and all(float(jnp.abs(a[1]).max()) == 0 < float(jnp.abs(a[3]).max()) for a in recurrent)


def test_a_step_attends_over_several_blocks_of_positions_and_stops_at_the_rows_own():
    """A row past the first block of cache positions beside a short one: the
    step's attention reads each row's slot up to its own position."""
    m = model(cache_len=1024)
    rows = prompts(6, (600, 9))
    toks, logs, _cache = served_logits(m, rows, 4, one_launch=False)
    assert M.KEY_BLOCK == 512 and m.cache_len // M.KEY_BLOCK == 2
    for i, (r, t) in enumerate(zip(rows, toks)):
        want = ref.forward(m.params, LLM, list(r) + t[:-1].tolist(), "f32", width=128)[len(r) - 1:]
        np.testing.assert_allclose(logs[i], want, atol=ATOL, rtol=0)


def test_a_slot_a_row_has_left_gives_a_new_row_what_a_fresh_cache_gives():
    m = model(cache_rows=2)
    first, second = prompts(2, (64, 90)), prompts(3, (7, 2))
    _t, _l, used = served_logits(m, first, STEPS)  # both slots hold a finished row's state and tail
    ssm = [arrays[0] for arrays, kind in zip(used, m.cfg.mixers) if kind == "mamba2"]
    assert len(ssm) == 9 and all(float(jnp.abs(s).max()) > 0 for s in ssm)
    _t, again, _c = served_logits(m, second, STEPS, cache=used, one_launch=False)
    _t, fresh, _c = served_logits(m, second, STEPS, one_launch=False)
    np.testing.assert_array_equal(again, fresh)


def test_generate_and_a_session_with_rows_joining_and_leaving_agree():
    m = model(cache_rows=2)
    rows, budgets = prompts(4), [5, 9, 3, 7, 4]
    together = D.generate(m, rows, budgets)
    alone = [D.generate(m, [r], [n])[0] for r, n in zip(rows, budgets)]
    assert together == alone and [len(t) for t in together] == budgets
    session, out = D.DecodeSession(m), {}
    session.admit([(0, rows[0], 5)])
    out.update(session.step())
    session.admit([(1, rows[1], 9)])  # joins a running row
    while session.live():
        for handle, toks in session.step():
            out[handle] = toks
            if handle == 0:  # the slot row 0 left is taken at once
                session.admit([(2, rows[2], 3)])
    assert [out[i] for i in range(3)] == alone[:3]


def test_the_counter_says_what_a_prefills_chunks_scanned():
    from pathway_tpu.observability import device as prof

    m = model(cache_len=1024)
    pad = prof.stats().pad
    before = list(pad.get("decoder.prefill.scan", [0, 0, 0, 0]))
    D.generate(m, prompts(5, (37, 90, 600)), [3, 3, 2])
    scan = np.subtract(pad["decoder.prefill.scan"], before).tolist()
    assert scan == [0, 0, 727, 512 + 512 + 1024 - 727]  # real tokens; what the chunks of the length buckets add
    assert m.scan_chunks(512) == 128 and model(ssm_chunk=256).scan_chunks(512) == 2
    assert D.LENGTH_STEP % 256 == 0


def test_the_published_configuration_is_36_and_4_layers_and_3_19_billion_parameters():
    with open(PUBLISHED, encoding="utf-8") as f:
        llm = json.load(f)
    cfg = D.DecoderConfig.from_hf(llm)
    period = ("mamba2",) * 5 + ("gqa",) + ("mamba2",) * 4
    assert cfg.mixers == period * 4 and cfg.recurrent_layers == 36 and cfg.n_sparse_layers == 0
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
            cfg.ssm_conv_dim, cfg.ssm_chunk) == (32, 8, 64, 64, 64, 128, 4352, 256)
    assert M.STATE_DTYPE == jnp.float32 and cfg.dtype == jnp.bfloat16 and cfg.tie_embeddings
    total = sum(D.layer_params(cfg, mixer=kind) for kind in cfg.mixers) + cfg.vocab_size * cfg.hidden_size
    assert abs(total / 3.19e9 - 1) < 0.01, total
    assert D.layer_params(cfg, mixer="gqa") == 10_485_760 + 50_331_648
    slot_bytes = sum(int(np.prod(shape)) * jnp.dtype(dt).itemsize for kind in cfg.mixers
                     for shape, dt in M.MIXERS[kind].slot(cfg, 4096))
    assert round(slot_bytes / 1e6) == 114  # 80.5 MB of state and tails (in whole tiles), 33.6 MB of keys and values a row


@pytest.mark.parametrize("change", [{"num_local_experts": 8}, {"position_embedding_type": "rope"},
                                    {"mamba_n_groups": 2}, {"layer_types": ["mamba"] * 9 + ["window"]}])
def test_what_the_decoder_cannot_run_is_refused_by_name(change):
    with pytest.raises(ValueError, match="not implemented|layer_types names"):
        D.DecoderConfig.from_hf({**LLM, **change})
