"""BERT-layout parameters made on the device from ``--seed`` in one jitted
call, in the layout ``JaxSentenceEncoder.from_pretrained`` builds (float32
leaves, cast to the compute type at use). There is no checkpoint offline;
weights from a seed are enough for speed and for agreement with the
reference, which is handed the same tree."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def seed_key(seed: int, stream: int) -> jax.Array:
    """A PRNG key from a seed that may not fit 32 signed bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


@partial(jax.jit, static_argnames=("vocab", "d", "layers", "ff", "positions", "head"))
def _make(key, *, vocab, d, layers, ff, positions, head):
    ks = iter(jax.random.split(key, 8 + 16 * layers))

    def normal(shape, std):
        return jax.random.normal(next(ks), shape, jnp.float32) * std

    def ln():
        return {"g": 1.0 + normal((d,), 0.1), "b": normal((d,), 0.1)}

    params = {
        "embed": normal((vocab, d), d ** -0.5),
        "pos": normal((positions, d), d ** -0.5),
        "tok_type": normal((2, d), d ** -0.5),
        "emb_ln": ln(),
        "layers": [
            {
                "wqkv": normal((d, 3 * d), d ** -0.5), "bqkv": normal((3 * d,), 0.02),
                "wo": normal((d, d), d ** -0.5), "bo": normal((d,), 0.02),
                "ln1": ln(),
                "w1": normal((d, ff), d ** -0.5), "b1": normal((ff,), 0.02),
                "w2": normal((ff, d), ff ** -0.5), "b2": normal((d,), 0.02),
                "ln2": ln(),
            }
            for _ in range(layers)
        ],
        # from_pretrained's tree carries it; the BERT block never reads it
        "ln_f": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
    }
    if head:
        params["head"] = {"w": normal((d, 1), 1.0), "b": jnp.zeros((1,))}
    return params


def make_params(model: dict, seed: int, stream: int, head: bool = False) -> dict:
    return _make(
        seed_key(seed, stream),
        vocab=model["vocab_size"], d=model["hidden_size"], layers=model["num_hidden_layers"],
        ff=model["intermediate_size"], positions=model["max_position_embeddings"], head=head,
    )
