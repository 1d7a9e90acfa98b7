"""The archive: the corpus a running store already holds, as vectors made on
the device from ``--seed`` and bulk-loaded into the index the node builds.

Row r of the archive is ``normalise(w * base[r % len(base)] + noise_r)`` with
``|noise_r| ~ archive_noise``: ``w = 1`` ("near") puts ~183 rows around every
live document, so a query's exact top-k mixes archive and live rows and a scan
that skipped the archive would answer differently; ``w = 0`` ("far") gives
random directions, scanned but ranked below every live row. ``base`` is the
benchmark's own bf16 embedding of the set-up documents (``reference.py`` run
at the stated precision), so the archive is data handed to program and
reference alike, as the weights are.

Archive rows have a key and a vector and no row in the docs table: a hit on
one comes back with ``text: null`` and its ``dist``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

#: first archive key; document keys are 64-bit hashes, a clash is not expected
KEY0 = 1 << 40


@partial(jax.jit, static_argnames=("rows",))
def block(key, base, index, weight, noise, rows: int):
    """Rows [index*rows, (index+1)*rows) of the archive, float32 unit vectors."""
    d = base.shape[1]
    n = jax.random.normal(jax.random.fold_in(key, index), (rows, d), jnp.float32)
    pick = (index * rows + jnp.arange(rows)) % base.shape[0]
    v = weight * base[pick] + n * (noise / jnp.sqrt(d))
    return v / jnp.linalg.norm(v, axis=-1, keepdims=True)


class Archive:
    """The archive's recipe; ``blocks()`` yields it a block at a time, for the
    index at set-up and for the reference after the window."""

    def __init__(self, key, base, rows: int, block_rows: int, mode: str, noise: float):
        if rows % block_rows:
            raise ValueError(f"archive_rows {rows} is not a multiple of the block {block_rows}")
        self.key, self.base = key, jnp.asarray(base, jnp.float32)
        self.rows, self.block_rows = rows, block_rows
        self.weight = {"near": 1.0, "far": 0.0}[mode]
        self.noise = float(noise)

    def blocks(self):
        for i in range(self.rows // self.block_rows):
            yield block(self.key, self.base, i, self.weight, self.noise, rows=self.block_rows)

    def load_into(self, index) -> None:
        """Bulk-load through the index's own ``add_batch_device``; each block
        is scattered at once (the scatter donates the index arrays, so the
        transient is one block and one compiled shape)."""
        for i, rows in enumerate(self.blocks()):
            lo = KEY0 + i * self.block_rows
            index.add_batch_device(range(lo, lo + self.block_rows), rows)
            index._flush()


def knn_factory(embedder, reserved_space: int, archive: Archive | None, built: list):
    """The stock ``BruteForceKnnFactory`` whose node loads the archive as it
    builds its ``VectorBackend``; the backend is appended to ``built`` so the
    harness can warm the search shapes and free the index after the run."""
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
    from pathway_tpu.stdlib.indexing.nearest_neighbors import BruteForceKnn

    class ArchiveKnn(BruteForceKnn):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            stock = self.backend_factory

            def factory():
                backend = stock()
                if archive is not None:
                    archive.load_into(backend.index)
                built.append(backend)
                return backend

            self.backend_factory = factory

    return BruteForceKnnFactory(
        embedder=embedder, reserved_space=reserved_space, _index_cls=ArchiveKnn
    )
