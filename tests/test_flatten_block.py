"""``FlattenNode`` derives a block's keys in one call; the scalar formula it
replaced is written out here as the reference, per item:
``_mix2(key, splitmix64(j))``. Keys, diffs, values, the other columns, row
order (row-major, item order) and the lineage edge must come out bit for bit
the same, for every kind of input the node takes."""

from __future__ import annotations

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine import operators as ops
from pathway_tpu.engine.blocks import DeltaBatch
from pathway_tpu.internals.keys import _mix2, splitmix64


def _obj(values: list) -> np.ndarray:
    arr = np.empty(len(values), dtype=object)
    for i, v in enumerate(values):
        arr[i] = v
    return arr


def _batch(seqs: list, diffs: list[int] | None = None, key0: int = 0x9E3779B97F4A7C15):
    n = len(seqs)
    keys = splitmix64(np.arange(n, dtype=np.uint64) + np.uint64(key0))
    return DeltaBatch(
        keys,
        np.asarray(diffs if diffs is not None else [1] * n, dtype=np.int64),
        {
            "seq": _obj(seqs),
            "tag": _obj([f"row{i}" for i in range(n)]),
            "num": np.arange(n, dtype=np.int64) * 3,
        },
        7,
    )


def _reference(batch: DeltaBatch):
    """The per-item walk: one key per (row key, position in the row)."""
    keys, diffs, vals, src = [], [], [], []
    for i in range(len(batch)):
        seq = batch.data["seq"][i]
        if seq is None:
            continue
        items = list(seq.value) if isinstance(seq, pw.Json) else list(seq)
        for j, item in enumerate(items):
            keys.append(
                int(
                    _mix2(
                        np.asarray([batch.keys[i]], dtype=np.uint64),
                        splitmix64(np.asarray([j], dtype=np.uint64)),
                    )[0]
                )
            )
            diffs.append(int(batch.diffs[i]))
            vals.append(item)
            src.append(i)
    return keys, diffs, vals, src


def _same_value(a, b) -> bool:
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        return type(a) is type(b) and a.dtype == b.dtype and np.array_equal(a, b)
    return type(a) is type(b) and a == b


_RNG = np.random.default_rng(29)
_RAGGED = [[int(x) for x in _RNG.integers(0, 99, n)] for n in _RNG.integers(0, 6, 40)]
_BIG = [[f"c{i}.{j}" for j in range(i % 4)] for i in range(12_000)]

CASES = {
    "ragged_lists": (_RAGGED, None),
    "tuples": ([(1, "a"), (), (2.5, None, (3, 4))], None),
    "store_parser_chunks": ([[("some text", {})], [("other", {"page": 2})]], None),
    "str": (["abc", "", "de"], None),
    "bytes": ([b"\x00\x01", b"", b"xyz"], None),
    "ndarray_1d": ([np.arange(3), np.arange(0), np.asarray([1.5, -0.0])], None),
    "ndarray_2d": ([np.arange(6).reshape(2, 3), np.arange(3).reshape(1, 3)], None),
    "json_arrays": ([pw.Json([1, "two", {"k": 3}]), pw.Json([]), pw.Json([[4]])], None),
    "none_rows": ([None, [1, 2], None, [3], None], None),
    "empty_sequences": ([[], [7], (), "", [8, 9]], None),
    "all_empty_block": ([[], None, (), ""], None),
    "one_row": ([["only", "row"]], None),
    "one_row_one_item": ([[("text", {})]], None),
    "no_rows": ([], None),
    "retractions": ([[1, 2], [3], [4, 5, 6], [7]], [-1, 1, -1, 2]),
    "mixed_kinds": ([[1], (2, 3), "ab", b"c", np.arange(2), pw.Json([5]), None, []], None),
    "block_of_12000_rows": (_BIG, [1 if i % 5 else -1 for i in range(len(_BIG))]),
}


@pytest.mark.parametrize("case", list(CASES))
def test_block_flatten_matches_scalar_formula(case, monkeypatch):
    seqs, diffs = CASES[case]
    batch = _batch(seqs, diffs)
    edges = []

    class _Lineage:
        def record_edge(self, node, out_keys, in_keys):
            edges.append((node, np.array(out_keys), np.array(in_keys)))

    monkeypatch.setattr(ops._lineage, "current", lambda: _Lineage())
    node = ops.FlattenNode("seq", ["tag", "num"])
    (out,) = node.process([batch], 7)

    keys, ref_diffs, vals, src = _reference(batch)
    assert out.keys.dtype == np.uint64 and out.diffs.dtype == np.int64
    assert out.keys.tolist() == keys
    assert out.diffs.tolist() == ref_diffs
    assert out.time == 7 and list(out.data) == ["seq", "tag", "num"]
    assert out.data["seq"].dtype == object and len(out.data["seq"]) == len(vals)
    assert all(_same_value(a, b) for a, b in zip(out.data["seq"], vals))
    assert out.data["tag"].tolist() == [f"row{i}" for i in src]
    assert out.data["num"].dtype == np.int64
    assert out.data["num"].tolist() == [3 * i for i in src]
    if keys:
        ((edge_node, edge_out, edge_in),) = edges
        assert edge_node is node
        assert edge_out.tolist() == keys
        assert edge_in.tolist() == [int(batch.keys[i]) for i in src]
    else:
        assert edges == []


def test_flatten_no_batch_gives_nothing():
    assert ops.FlattenNode("seq", []).process([None], 0) == []
