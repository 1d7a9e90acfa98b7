"""The decode loop's and the collector's span readers (PR 36) against a recorded ring and gap list of their
own (``data/spans_decode.json``), every expected value computed by hand. ``test_spans.py`` and ``data/spans.json``
stay as they were; ``conftest.py`` hands the expectations of the readers listed in ``BENCHMARK.json`` to its guard."""

import types

import pytest

from chipbench import run, spanlib

MS = 1_000_000
DECODE = run.load_json(run.HERE, "tests", "data", "spans_decode.json")
EXPECTED = {
    # decoder.step hand-overs in the window: 1.5, 2, 1.5, 1, 1 ms (the step before the window is left out)
    "step_launch_ms.answer": ("query", 1.5),
    # fetch end -> next hand-over end where rows stayed live: 114-111, 125-121, 146-133, 214-211, 222-219
    # (the row's last step left none: no gap to the next question's prefill)
    "step_gap_ms.answer": ("query", 26.0 / 5),
    # of those 26 ms, the 13 that hold the end of the first tick (140 ms)
    "yield_gap_share.answer": ("query", 50.0),
    # idle 7 + 7 + 13 + 11 + 12 = 50 ms; under a pass: 125-127 (engine) and 160-164 (another thread)
    "idle_in_gc.ingest": ("ingest", 12.0),
}


def decode_ctx(kind: str, monkeypatch, tmp_path, dropped: int = 0, records=None):
    recs = DECODE["records"] if records is None else records
    monkeypatch.setattr(spanlib, "ring", lambda: ([tuple(r) for r in recs], dropped))
    monkeypatch.setattr(spanlib, "OUT", str(tmp_path))
    window = dict(DECODE["window"], **({"latency_ms": [300.0], "attempted": 1} if kind == "query" else {"documents": 4}))
    return types.SimpleNamespace(window=window, trace=dict(DECODE["trace"]))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_decode_reader_reads_the_hand_computed_value(name, monkeypatch, tmp_path):
    kind, value = EXPECTED[name]
    assert run.load_metric(name).read(decode_ctx(kind, monkeypatch, tmp_path)) == pytest.approx(value)
    other = "ingest" if kind == "query" else "query"
    assert run.load_metric(name).read(decode_ctx(other, monkeypatch, tmp_path)) is None  # the other cell's window


@pytest.mark.parametrize("name", sorted(EXPECTED))
@pytest.mark.parametrize("fault", ["dropped", "empty", "no_ring"])
def test_decode_reader_returns_nothing_from_part_of_a_window(name, fault, monkeypatch, tmp_path):
    ctx = decode_ctx(EXPECTED[name][0], monkeypatch, tmp_path, dropped=int(fault == "dropped"),
                     records=[] if fault == "empty" else None)
    if fault == "no_ring":
        monkeypatch.setattr(spanlib, "ring", lambda: None)
    assert run.load_metric(name).read(ctx) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_decode_reader_returns_nothing_from_a_program_without_the_stamps(name, monkeypatch, tmp_path):
    """A parent's ring: no ``device/launch``, no ``gc/collect``, no ``pathway.live``, no tick's gen-0 sums."""
    new_keys = ("pathway.live", "pathway.gc0_passes", "pathway.gc0_ns")
    old = [[n, i, p, t0, t1, {k: v for k, v in (a or {}).items() if k not in new_keys}, tr, th]
           for n, i, p, t0, t1, a, tr, th in DECODE["records"] if n not in ("device/launch", "gc/collect", "generate/yield")]
    ctx = decode_ctx(EXPECTED[name][0], monkeypatch, tmp_path, records=old)
    assert run.load_metric(name).read(ctx) is None


def test_the_live_rows_order_the_decoder_launches(monkeypatch, tmp_path):
    from chipbench import steplib

    rep = spanlib.report(decode_ctx("query", monkeypatch, tmp_path))
    got = [(x["name"], x["launched"] // MS, x["fetched"] // MS, x["live"]) for x in steplib.launches(rep)]
    assert got == [("generate/prefill", 105, 111, 1), ("generate/step", 114, 121, 1), ("generate/step", 125, 133, 1),
                   ("generate/step", 146, 154, 0), ("generate/prefill", 206, 211, 1), ("generate/step", 214, 219, 1),
                   ("generate/step", 222, 227, 0)]
