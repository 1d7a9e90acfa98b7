"""The span readers against a small recorded ring and gap list
(``data/spans.json``), every expected value computed by hand."""

import json
import os
import types

import pytest

from chipbench import run, spanlib

MS = 1_000_000
DATA = run.load_json(run.HERE, "tests", "data", "spans.json")
NEW = [m["name"] for m in run.load_json(run.ROOT, "BENCHMARK.json")["per_layer"] if m["source"] == "program_span"]


def ctx_for(kind: str, monkeypatch, tmp_path, dropped: int = 0, records=None):
    recs = DATA["records"] if records is None else records
    monkeypatch.setattr(spanlib, "ring", lambda: ([tuple(r) for r in recs], dropped))
    monkeypatch.setattr(spanlib, "OUT", str(tmp_path))
    window = dict(DATA["window"], **({"latency_ms": [87.0, 82.0], "attempted": 2} if kind == "query" else {"documents": 4}))
    return types.SimpleNamespace(window=window, trace=dict(DATA["trace"]))


def test_self_times_sum_to_the_tick():
    spans = spanlib.spans_of(DATA["records"])
    own = dict(zip([(s["name"], s["t0"]) for s in spans], spanlib.self_time(spans)))
    assert own[("tick", 100 * MS)] == 9 * MS
    assert own[("device/dispatch", 114 * MS)] == 8 * MS  # 34 - tokenize 5 - put 1 - fetch 20; the event has no thread
    assert own[("index/search", 156 * MS)] == 8 * MS
    tree = [s for s in spans if s["trace"] is None and s["thread"] is not None and 100 * MS <= s["t0"] and s["t1"] <= 180 * MS]
    assert sum(spanlib.self_time(tree)) == 80 * MS


def test_gap_cover_names_a_wait_a_nodes_self_time_and_nothing():
    spans = spanlib.spans_of(DATA["records"])
    off = DATA["trace"]["offset_ns"]
    cover = spanlib.gap_cover(spans, [(a - off, b - off) for a, b in DATA["trace"]["gaps"]])
    assert {k: round(v * 1e3, 6) for k, v in cover.items()} == {
        "tick/wait": 10.0, "sweep/select_microbatch": 4.0, "": 1.0, "device/dispatch": 2.0,
        "microbatch/launch": 1.0, "frontier/select_microbatch": 1.0, "tick": 2.0,
        "sweep/external_index": 1.0, "embed/tokenize": 2.0, "index/add": 1.0,
    }


EXPECTED = {
    "coalesce_wait_p50_ms": ("query", 6.0), "microbatch_wait_p50_ms": ("query", 21.0),
    "tick_host_ms.query": ("query", 53.0), "fetches_per_query": ("query", 1.0),
    "queries_per_search": ("query", 2.0), "idle_in_wait.query": ("query", 40.0),
    "idle_unexplained.ingest": ("ingest", 12.0), "idle_in_tokenize.ingest": ("ingest", 8.0),
    "idle_in_microbatch_host.ingest": ("ingest", 24.0), "idle_in_index_add.ingest": ("ingest", 4.0),
    "h2d_bytes_per_doc.ingest": ("ingest", 56.0), "d2h_bytes_per_doc.ingest": ("ingest", 780.0),
}


def test_every_new_metric_has_an_expected_value():
    assert sorted(NEW) == sorted(EXPECTED)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_the_hand_computed_value(name, monkeypatch, tmp_path):
    kind, value = EXPECTED[name]
    assert run.load_metric(name).read(ctx_for(kind, monkeypatch, tmp_path)) == pytest.approx(value)
    other = "ingest" if kind == "query" else "query"
    assert run.load_metric(name).read(ctx_for(other, monkeypatch, tmp_path)) is None  # the other cell's window


@pytest.mark.parametrize("name", sorted(EXPECTED))
@pytest.mark.parametrize("fault", ["dropped", "empty", "no_ring"])
def test_reader_returns_nothing_from_part_of_a_window(name, fault, monkeypatch, tmp_path):
    ctx = ctx_for(EXPECTED[name][0], monkeypatch, tmp_path, dropped=int(fault == "dropped"),
                  records=[] if fault == "empty" else None)
    if fault == "no_ring":
        monkeypatch.setattr(spanlib, "ring", lambda: None)
    assert run.load_metric(name).read(ctx) is None


def test_report_writes_the_table_and_the_requests(monkeypatch, tmp_path, capsys):
    rep = spanlib.report(ctx_for("query", monkeypatch, tmp_path))
    assert len(rep["spans"]) == len(DATA["records"]) - 1  # the tick after the window is left out
    with open(os.path.join(tmp_path, "last_spans.json"), encoding="utf-8") as f:
        doc = json.load(f)
    assert doc["names"]["device/fetch"] == {"count": 2, "total_s": 0.027, "self_s": 0.027, "idle_gap_s": 0.0}
    assert doc["names"]["tick/wait"]["idle_gap_s"] == pytest.approx(0.010)
    assert doc["requests_ms"][0] == {"status": "ok", "arrival": -10.0, "admitted": -8.0, "first_tick": 0.0,
                                     "respond": 76.0, "done": 77.0}
    # idle while the server held a request: the gaps up to 177 ms, 14 of the 25 ms, none of it under no span
    assert doc["idle_in_flight_s"] == pytest.approx(0.014) and doc["idle_in_flight_under_no_span_s"] == 0.0
    assert "largest by self time" in capsys.readouterr().err


def test_the_ring_of_a_real_run_reads_back(monkeypatch, tmp_path):
    """The seam: a tracer's ring as the program keeps it, through ``ring``."""
    from pathway_tpu import observability as obs
    from pathway_tpu.observability.spans import SpanBuffer, Tracer

    tracer = Tracer(trace_id="ab" * 16, buffer=SpanBuffer(max_spans=16))
    tick = tracer.begin_tick(0)
    tok = tracer.begin("sweep/x")
    tracer.end(tok, {"pathway.rows_in": 1})
    tracer.end_tick(0, tick)
    monkeypatch.setattr(obs, "_tracer", tracer)
    records, dropped = spanlib.ring()
    spans = spanlib.spans_of(records)
    assert dropped == 0 and [s["name"] for s in spans] == ["sweep/x", "tick"]
    assert spans[0]["parent"] == spans[1]["id"] and spanlib.attr(spans[0], "rows_in") == 1
