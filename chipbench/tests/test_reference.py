"""The plain reference against the program's own float32 BERT path at a tiny
size: two independent writings of one model have to agree to rounding."""

import jax.numpy as jnp
import numpy as np

from chipbench import corpus, reference, weights

MODEL = {"vocab_size": 2048, "hidden_size": 64, "num_attention_heads": 4, "num_hidden_layers": 2,
         "intermediate_size": 128, "max_position_embeddings": 512, "layer_norm_eps": 1e-12}
DOCS = {"median_words": 56, "sigma": 0.5, "min_words": 12, "max_words": 240}


def _cfg():
    from pathway_tpu.ops.encoder import EncoderConfig

    return EncoderConfig(vocab_size=2048, d_model=64, n_heads=4, n_layers=2, d_ff=128, max_len=512,
                         dtype=jnp.float32, arch="bert", ln_eps=1e-12)


def test_embedder_agrees_with_the_program():
    from pathway_tpu.ops.encoder import JaxSentenceEncoder

    params = weights.make_params(MODEL, 2**31 + 11, 0)
    texts = corpus.doc_block(3, 0, DOCS)[:48]
    got = JaxSentenceEncoder(_cfg(), params=params).encode_texts(texts)
    want = reference.embed_texts(params, texts, MODEL)
    assert np.abs(got - want).max() < 2e-5


def test_reranker_agrees_with_the_program():
    from pathway_tpu.ops.reranker import JaxCrossEncoder

    params = weights.make_params(MODEL, 7, 1, head=True)
    docs = corpus.doc_block(3, 0, DOCS)[:24]
    pairs = [(q, d) for q, d in zip(corpus.queries(3, 24, {"min_words": 3, "max_words": 12}, docs), docs)]
    ce = JaxCrossEncoder(_cfg())
    ce.params = params
    assert np.abs(ce.score_pairs(pairs) - reference.score_pairs(params, pairs, MODEL)).max() < 2e-4


def test_lower_precisions_move_away_in_order():
    params = weights.make_params(MODEL, 5, 0)
    texts = corpus.doc_block(3, 0, DOCS)[:32]
    f32 = reference.embed_texts(params, texts, MODEL)
    bf16 = np.abs(reference.embed_texts(params, texts, MODEL, "bf16") - f32).max()
    fp8 = np.abs(reference.embed_texts(params, texts, MODEL, "fp8") - f32).max()
    assert 0 < bf16 < fp8 and fp8 > 4 * bf16


def test_seed_changes_content_never_the_work():
    a, b = corpus.doc_block(1, 0, DOCS), corpus.doc_block(2**31 + 9, 0, DOCS)
    assert a != b and sorted(len(t.split()) for t in a) == sorted(len(t.split()) for t in b)
    assert corpus.doc_block(1, 0, DOCS) == a
    qa = corpus.queries(1, 50, {"min_words": 3, "max_words": 12}, a)
    assert len(set(qa)) == 50 and all(3 <= len(q.split()) <= 12 for q in qa)
    ga, gb = corpus.arrival_offsets(1, 200, 50.0), corpus.arrival_offsets(2, 200, 50.0)
    assert np.allclose(sorted(np.diff(ga, prepend=0)), sorted(np.diff(gb, prepend=0))) and not np.allclose(ga, gb)
