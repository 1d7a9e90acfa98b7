"""Row lineage (ISSUE 8 tentpole, pillar 4): provenance rings at
key-deriving operator edges, the ``/explain?sink=&key=`` backward walk
(contributing input rows → operator chain → trace span ids), the
``pathway_tpu explain`` CLI plumbing, and the embed→KNN→rerank demo-pipeline
acceptance."""

from __future__ import annotations

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.internals.run import current_runtime
from pathway_tpu.observability import lineage as lineage_mod


def _explain_live(port, sink, key):
    url = f"http://127.0.0.1:{port}/explain?sink={sink}&key={key}"
    return json.loads(urllib.request.urlopen(url, timeout=2).read())


def _run_and_explain():
    """Run the registered pipeline, then explain one live sink row offline
    (the store and graph survive the run, like the device plane's stats)."""
    pw.run(monitoring_level="none")
    store = lineage_mod.current()
    assert store is not None
    store.fold()  # hot path only parks; reads fold (as /explain does)
    rt = current_runtime()
    sink = sorted(store.sinks)[0]
    key = next(iter(store.sinks[sink].data))
    return store.explain(rt.scheduler, sink, key), store, sink


def test_explain_groupby_pipeline_reports_inputs_and_path():
    G.clear()
    t = pw.debug.table_from_rows(
        pw.schema_from_types(x=int),
        [(i, i // 8, 1) for i in range(32)],
        is_stream=True,
    )
    t = t.with_columns(m=t.x % 3)
    g = t.groupby(t.m).reduce(s=pw.reducers.sum(t.x))
    pw.io.subscribe(g, on_change=lambda **k: None)
    doc, store, sink = _run_and_explain()
    assert doc["ok"] and doc["sink"] == sink
    ops = [p["operator"] for p in doc["path"]]
    assert "groupby" in ops and "subscribe" in ops
    gb = next(p for p in doc["path"] if p["operator"] == "groupby")
    assert gb["derives_keys"]  # the group key maps back to input row keys
    assert doc["output"] is not None and "s" in doc["output"]["row"]
    # the walk bottomed out at the input connector with actual row values
    assert doc["inputs"], doc
    for i in doc["inputs"]:
        assert "x" in i["row"] and i["tick"] is not None


def test_explain_join_pipeline_reaches_both_sides():
    G.clear()
    left = pw.debug.table_from_rows(
        pw.schema_from_types(k=int, v=int),
        [(1, 10, 0, 1), (2, 20, 0, 1)],
        is_stream=True,
    )
    right = pw.debug.table_from_rows(
        pw.schema_from_types(k=int, name=str),
        [(1, "a", 0, 1), (2, "b", 0, 1)],
        is_stream=True,
    )
    j = left.join(right, left.k == right.k).select(v=left.v, name=right.name)
    pw.io.subscribe(j, on_change=lambda **k: None)
    doc, _store, _sink = _run_and_explain()
    assert doc["ok"]
    ops = [p["operator"] for p in doc["path"]]
    assert any(op.startswith("join") for op in ops), ops
    jn = next(p for p in doc["path"] if p["operator"].startswith("join"))
    assert jn["derives_keys"]
    # contributing rows from BOTH input connectors
    cols = set()
    for i in doc["inputs"]:
        cols.update(i["row"].keys())
    assert {"v", "name"} <= cols, doc["inputs"]


def test_explain_live_endpoint_with_span_ids(monkeypatch):
    monkeypatch.setenv("PATHWAY_TRACE", "on")
    monkeypatch.setenv("PATHWAY_MONITORING_HTTP_PORT", "20731")

    probed = threading.Event()

    class Subj(pw.io.python.ConnectorSubject):
        def run(self):
            for i in range(120):
                self.next(x=i)
                if i % 10 == 9:
                    time.sleep(0.04)
            # the run (and its monitoring server) ends with this source: hold
            # it open until the probe has asked its question of the live run
            probed.wait(timeout=15)

    G.clear()
    t = pw.io.python.read(Subj(), schema=pw.schema_from_types(x=int))
    t = t.with_columns(m=t.x % 3)
    g = t.groupby(t.m).reduce(s=pw.reducers.sum(t.x))
    pw.io.subscribe(g, on_change=lambda **k: None)
    got = {}

    def probe():
        try:
            _probe()
        finally:
            probed.set()

    def _probe():
        try:
            # discover live sinks via the error payload, then explain a row.
            # The server is up before the run installs its lineage store, so
            # an early listing can still show the PREVIOUS run's sinks: keep
            # asking until a listed sink has rows in the store of this run.
            from pathway_tpu.observability import lineage as lm

            base = "http://127.0.0.1:20731/explain"
            deadline = time.monotonic() + 12.0  # inside the source's 15 s hold
            while time.monotonic() < deadline and "doc" not in got:
                try:
                    got["listing"] = json.loads(urllib.request.urlopen(base, timeout=2).read())
                except OSError:
                    time.sleep(0.05)
                    continue
                store = lm.current()
                for sink in got["listing"].get("sinks") or []:
                    if store is None:
                        break
                    store.fold()
                    ring = store.sinks.get(sink)
                    if ring and ring.data:
                        doc = _explain_live(20731, sink, next(iter(ring.data)))
                        if doc.get("ok"):  # not ok: that store was the stale one
                            got["doc"] = doc
                            break
                time.sleep(0.05)
            assert "listing" in got, "monitoring server never came up"
        except Exception as e:  # pragma: no cover - surfaced by asserts
            got["error"] = repr(e)

    th = threading.Thread(target=probe)
    th.start()
    pw.run(with_http_server=True, monitoring_level="none")
    th.join()
    assert "error" not in got, got
    assert got["listing"]["ok"] is False  # missing sink= lists the sinks
    assert got["listing"]["sinks"]
    doc = got.get("doc")
    assert doc is not None and doc["ok"], got
    # with tracing on, ingested rows carry the originating tick span id
    spans = [i["span_id"] for i in doc["inputs"]]
    assert spans and any(s is not None for s in spans), doc["inputs"]


def test_explain_chain_embed_knn_rerank_demo(monkeypatch):
    """ISSUE 8 acceptance: /explain returns the full provenance path for a
    live row of the embed→KNN→rerank demo pipeline."""
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.mocks import FakeEmbedder
    from pathway_tpu.xpacks.llm.rerankers import EncoderReranker

    monkeypatch.setenv("PATHWAY_TRACE", "on")
    G.clear()
    emb = FakeEmbedder(dimension=12)
    docs = [f"document number {i} about topic {i % 3}" for i in range(12)]
    doc_t = pw.debug.table_from_rows(
        pw.schema_from_types(text=str), [(d,) for d in docs]
    )
    index = BruteForceKnnFactory(embedder=emb).build_index(doc_t.text, doc_t)
    q_t = pw.debug.table_from_rows(
        pw.schema_from_types(qi=int, q=str),
        [(i, docs[i], i // 4, 1) for i in range(8)],
        is_stream=True,
    )
    picked = index.query_as_of_now(q_t.q, number_of_matches=1).select(
        qi=pw.left.qi,
        q=pw.left.q,
        top=pw.apply(lambda ts: ts[0] if ts else "", pw.right.text),
    )
    rr = EncoderReranker(emb)
    scored = picked.select(picked.qi, picked.top, score=rr(picked.top, picked.q))
    seen = {}
    pw.io.subscribe(
        scored, on_change=lambda key, row, time, is_addition: seen.update({key: row})
    )
    pw.run(monitoring_level="none")
    assert seen, "demo pipeline produced no output"
    store = lineage_mod.current()
    store.fold()
    rt = current_runtime()
    sink = sorted(store.sinks)[0]
    # explain a LIVE output row (one the subscriber actually delivered)
    key = next(k for k in seen if k in store.sinks[sink].data)
    doc = store.explain(rt.scheduler, sink, key)
    assert doc["ok"]
    assert doc["output"] is not None and "score" in doc["output"]["row"]
    ops = [p["operator"] for p in doc["path"]]
    assert "subscribe" in ops
    assert len(ops) >= 3, ops  # a real operator chain, not a stub
    # provenance bottoms out at the query input with the actual query row
    assert doc["inputs"], doc
    assert any("q" in i["row"] for i in doc["inputs"]), doc["inputs"]
    # originating trace span ids ride along (PATHWAY_TRACE=on)
    assert any(i["span_id"] for i in doc["inputs"]) or doc["output"]["span_id"]


def test_lineage_ring_bounded_eviction():
    ring = lineage_mod._Ring(cap=128)
    for i in range(1000):
        ring.add(i, i + 1)
    assert len(ring.data) <= 128
    assert 999 in ring.data  # newest survive
    # contributor lists are capped
    ring2 = lineage_mod._Ring(cap=4)
    for i in range(50):
        ring2.add(7, i)
    assert len(ring2.data[7]) <= lineage_mod._MAX_CONTRIB


def test_lineage_disabled_with_zero_cap(monkeypatch):
    monkeypatch.setenv("PATHWAY_LINEAGE_KEYS", "0")
    G.clear()
    t = pw.debug.table_from_rows(pw.schema_from_types(x=int), [(1,), (2,)])
    pw.io.subscribe(t, on_change=lambda **k: None)
    pw.run(monitoring_level="none")
    assert lineage_mod.current() is None
    # the audit monitors stay live even with lineage off
    from pathway_tpu.observability import audit as audit_mod

    assert audit_mod.current() is not None


def test_explain_payload_errors():
    from pathway_tpu.internals.monitoring import _explain_payload

    G.clear()
    t = pw.debug.table_from_rows(pw.schema_from_types(x=int), [(1,)])
    pw.io.subscribe(t, on_change=lambda **k: None)
    pw.run(monitoring_level="none")
    rt = current_runtime()
    doc = json.loads(_explain_payload(rt, "sink=nope&key=1"))
    assert doc["ok"] is False and "unknown sink" in doc["error"]
    doc = json.loads(_explain_payload(rt, "sink=subscribe"))
    assert doc["ok"] is False and "key" in doc["error"]
