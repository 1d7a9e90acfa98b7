"""The reduction from a trace to numbers, on a small recorded trace: the head
of a traced window of ``minilm-l6.retrieve-short`` taken on the v5e
(``tests/data/trace_head.json``, written by ``reduce_trace.head``), and on a
hand-made list whose answer is known by construction."""

import json
import os

import pytest

from chipbench import reduce_trace as rt

D, H = "/device:TPU:0", "/host:CPU"
NAMES = {"knn.search": ["jit__search_kernel"], "encoder.encode_ids": ["jit__encode_ids_jit"]}
MS = 1_000_000

HAND = [
    (H, "python3", "chipbench.marker", 5 * MS, 1 * MS),
    (D, rt.MODULES, "jit__encode_ids_jit(123)", 10 * MS, 2 * MS),
    (D, rt.OPS, "%fusion.1 = f32[8,16] fusion(...)", 10 * MS, 1 * MS),
    (D, rt.OPS, "%fusion.2 = f32[8,16] fusion(...)", 11 * MS, 1 * MS),
    (D, rt.MODULES, "jit__search_kernel(456)", 20 * MS, 30 * MS),
    (D, rt.OPS, "%sort.5 = (f32[3,1048576]) sort(...)", 20 * MS, 20 * MS),
    (D, rt.OPS, "%fusion.2 = f32[3,4194304] fusion(...)", 35 * MS, 15 * MS),  # overlaps the sort by 5 ms
    (D, "Async XLA Ops", "%copy-start", 0, 100 * MS),  # not an operation that keeps the device busy
    (D, rt.MODULES, "jit__search_kernel(456)", 70 * MS, 30 * MS),  # cut by the window's end at 80
    (D, rt.OPS, "%sort.5 = (f32[3,1048576]) sort(...)", 70 * MS, 30 * MS),
]


def test_hand_made_trace():
    r = rt.reduce(HAND, window=(0, 80 * MS), names=NAMES)
    assert r["window_s"] == pytest.approx(0.080)
    assert r["busy_s"] == pytest.approx(0.002 + 0.030 + 0.010)
    assert r["launches"] == {"encoder.encode_ids": 1, "knn.search": 2}
    assert r["seconds"]["knn.search"] == pytest.approx(0.040)
    assert r["ops"][0][0] == "%sort.5"
    assert [(s // MS, e // MS) for s, e in r["gaps"]] == [(0, 10), (12, 20), (50, 70)]
    assert rt.find_marker(HAND, "chipbench.marker") == 5 * MS


def test_gaps_are_named_by_what_the_host_was_doing():
    gaps = [(0, 10 * MS), (12 * MS, 20 * MS), (50 * MS, 70 * MS)]
    named = dict((n, s) for n, s in rt.name_gaps(gaps, [(8 * MS, 60 * MS)], "host", "waiting") if ":" not in n)
    assert named["host"] == pytest.approx(0.002 + 0.008 + 0.010)
    assert named["waiting"] == pytest.approx(0.008 + 0.010)
    assert len(rt.name_gaps(gaps * 5, [], "host", "waiting")) <= 10


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        rt.reduce([e for e in HAND if e[0] == H], names=NAMES)


def test_labels_come_from_the_kernel_files():
    names = rt.kernel_names()
    assert rt.label_of("jit__search_kernel(8123)", names) == "knn.search"
    assert rt.label_of("jit__score_jit(1)", names) == "reranker.score"
    assert rt.label_of("jit_something_else(1)", names) == "jit_something_else"


def test_recorded_head_of_a_traced_window():
    path = os.path.join(os.path.dirname(__file__), "data", "trace_head.json")
    with open(path, encoding="utf-8") as f:
        rec = json.load(f)
    events = [tuple(e) for e in rec["events"]]
    w0 = rec["window"][0]
    r = rt.reduce(events, window=(w0, w0 + 250 * MS))
    want = rec["expected"]
    assert r["launches"] == want["launches"]
    # "expected" was worked out apart from reduce_trace: a boolean time line at 100 ns
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=2e-3)
    assert r["seconds"]["knn.search"] == pytest.approx(want["knn_search_s"], rel=1e-9)
    assert 0 < r["busy_s"] < r["window_s"] == pytest.approx(0.25)
    assert rt.find_marker(events, "chipbench.marker") is not None
