"""Request-scoped tracing (ISSUE 13): end-to-end query flight paths with
tail-based sampling and latency decomposition.

Covers the tentpole surface:

- tail sampling catches what head sampling misses: with ``PATHWAY_TRACE_SAMPLE``
  at 1%, an injected stage delay on exactly one of 500 served requests
  produces a kept trace whose decomposition attributes >=80% of that
  request's latency to the injected stage — on the thread runtime here and
  on a 2-process cluster in the subprocess test;
- cross-process stitching: a 2-proc cluster query whose KNN index shard
  lives on the peer yields ONE trace id whose stage spans come from both
  processes, byte-identical answers with tracing on vs off, and
  ``PATHWAY_REQUEST_TRACE=off`` installs no plane at all (hot path pays one
  is-None test);
- the serving surface: ``X-Pathway-Request-Id`` response header,
  ``/request?id=`` endpoint, ``/status`` slowest-request exemplars,
  ``/metrics`` ``pathway_request_stage_seconds{stage}`` histograms, and the
  ``pathway_tpu trace`` CLI;
- flight-recorder dumps naming the requests that died mid-flight.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import textwrap
import threading
import time
import urllib.error
import urllib.request

import pytest

import pathway_tpu as pw
from pathway_tpu.observability import requests as req_mod
from conftest import free_port_base

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class QuerySchema(pw.Schema):
    query: str


def _free_port() -> int:
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_ready(port: int, timeout: float = 20.0) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port), timeout=0.5).close()
            return
        except OSError:
            time.sleep(0.02)
    raise AssertionError(f"server on port {port} never came up")


def _post(port: int, payload: dict, route: str = "/", timeout: float = 60.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{route}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    resp = urllib.request.urlopen(req, timeout=timeout)
    return json.loads(resp.read()), dict(resp.headers)


def _stop_run() -> None:
    rt = pw.internals.run.current_runtime()
    if rt is not None:
        rt.request_stop()


# ------------------------------------------------------------------- off mode


def test_off_mode_installs_no_plane(monkeypatch):
    """PATHWAY_REQUEST_TRACE=off: no plane object exists at all — engine hot
    loops guard on a single is-None read and zero rings are allocated."""
    monkeypatch.setenv("PATHWAY_REQUEST_TRACE", "off")
    assert req_mod.install_from_env() is None
    assert req_mod.current() is None
    # a tick under off mode keeps the scheduler's per-tick plane slot None
    from pathway_tpu.engine.graph import EngineGraph, Scheduler

    sched = Scheduler(EngineGraph())
    sched.run_tick(0)
    assert sched._rp is None


def test_knob_defaults(monkeypatch):
    for k in (
        "PATHWAY_REQUEST_TRACE",
        "PATHWAY_REQUEST_TRACE_SLOW_MS",
        "PATHWAY_REQUEST_TRACE_KEEP",
        "PATHWAY_REQUEST_TRACE_KEPT",
    ):
        monkeypatch.delenv(k, raising=False)
    from pathway_tpu.internals.config import get_pathway_config

    cfg = get_pathway_config()
    assert cfg.request_trace == "on"
    assert cfg.request_trace_slow_ms == 250.0
    assert cfg.request_trace_keep == 0.01
    assert cfg.request_trace_kept == 256
    d = cfg.to_dict()
    assert "request_trace_slow_ms" in d and "request_trace_keep" in d
    monkeypatch.setenv("PATHWAY_REQUEST_TRACE", "maybe")
    with pytest.raises(ValueError):
        cfg.request_trace


# --------------------------------------------------- tail sampling (thread)


def test_tail_sampling_catches_injected_delay_thread(monkeypatch):
    """500 served requests, head sampling at 1%, one request delayed 0.4 s by
    an injected stage delay: the request plane keeps that trace regardless of
    the tick-hash head decision, and its latency decomposition attributes
    >=80% of the request's latency to the injected engine stage."""
    n_clients = 8
    per_client = 62
    needle = "needle-313"
    port = _free_port()
    monkeypatch.setenv("PATHWAY_TRACE", "on")
    monkeypatch.setenv("PATHWAY_TRACE_SAMPLE", "0.01")
    monkeypatch.setenv("PATHWAY_REQUEST_TRACE", "on")
    monkeypatch.setenv("PATHWAY_REQUEST_TRACE_SLOW_MS", "150")
    monkeypatch.setenv("PATHWAY_REQUEST_TRACE_KEEP", "0.002")
    monkeypatch.setenv("PATHWAY_SERVE_COALESCE_MS", "2")

    from pathway_tpu.internals.parse_graph import G

    G.clear()
    queries, respond = pw.io.http.rest_connector(
        host="127.0.0.1", port=port, schema=QuerySchema
    )

    def work(q: str) -> str:
        if q == needle:
            time.sleep(0.4)  # the injected stage delay
        return q.upper()

    respond(queries.select(result=pw.apply(work, queries.query)))

    out: dict = {}

    def orchestrate() -> None:
        _wait_ready(port)
        ids: dict[str, str] = {}
        lock = threading.Lock()

        def client(ci: int) -> None:
            for j in range(per_client):
                q = needle if (ci == 3 and j == per_client // 2) else f"q-{ci}-{j}"
                body, headers = _post(port, {"query": q})
                assert body == q.upper()
                with lock:
                    ids[q] = headers.get("X-Pathway-Request-Id")

        threads = [
            threading.Thread(target=client, args=(ci,)) for ci in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        plane = req_mod.current()
        out["ids"] = ids
        out["kept_ids"] = plane.kept_ids()
        out["summary"] = plane.status_summary()
        out["needle_trace"] = plane.get_trace(ids[needle])
        out["slowest"] = plane.slowest_exemplars()
        # r8 stitching: kept spans land in the live span buffer under the
        # per-request trace id, next to the (1%-sampled) tick spans
        from pathway_tpu import observability as _obs

        spans, _ = _obs.current().buffer.since(0, limit=100000)
        out["request_span_tids"] = {
            s["traceId"] for s in spans if s["name"] == "serve/request"
        }
        _stop_run()

    th = threading.Thread(target=orchestrate)
    th.start()
    pw.run(monitoring_level="none")
    th.join()
    G.clear()

    total = out["summary"]["completed_total"]
    assert total == n_clients * per_client
    needle_id = out["ids"][needle]
    assert needle_id in out["kept_ids"], (
        f"delayed request not kept: {out['summary']}"
    )
    # tail sampling must not have kept everything (most requests were fast)
    assert out["summary"]["kept_total"] < total * 0.2
    doc = out["needle_trace"]
    assert doc["ok"] and doc["kept"] and doc["status"] == "ok"
    assert doc["duration_ms"] >= 380
    decomp = doc["decomposition_ms"]
    engine_stages = {k: v for k, v in decomp.items() if k.startswith("sweep/")}
    assert engine_stages, f"no engine stage in decomposition: {decomp}"
    top_stage, top_ms = max(engine_stages.items(), key=lambda kv: kv[1])
    assert top_ms >= 0.8 * doc["duration_ms"], (
        f"injected stage under-attributed: {top_stage}={top_ms}ms of "
        f"{doc['duration_ms']}ms total ({decomp})"
    )
    # the slowest-request exemplars surface the delay cohort: requests that
    # coalesced into (or queued behind) the needle's tick share its stall, so
    # the needle itself may legitimately rank below the top-8 — but the top
    # exemplar must carry the stall's duration and be decomposed
    slowest = out["slowest"]
    assert slowest and slowest == sorted(
        slowest, key=lambda e: -e["duration_ms"]
    )
    assert slowest[0]["duration_ms"] >= 380
    assert slowest[0]["decomposition_ms"]
    # kept request spans carry per-request trace ids derived from the ids
    assert req_mod.derive_request_trace_id(needle_id) in out["request_span_tids"]


# -------------------------------------------- serving surface + CLI + metrics


def test_request_endpoint_status_metrics_and_cli(monkeypatch):
    port = _free_port()
    mon_port = _free_port()
    monkeypatch.setenv("PATHWAY_MONITORING_HTTP_PORT", str(mon_port))
    monkeypatch.setenv("PATHWAY_REQUEST_TRACE_SLOW_MS", "0")  # keep everything
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    queries, respond = pw.io.http.rest_connector(
        host="127.0.0.1", port=port, schema=QuerySchema
    )
    respond(queries.select(result=pw.apply(lambda q: q[::-1], queries.query)))

    out: dict = {}

    def orchestrate() -> None:
        _wait_ready(port)
        body, headers = _post(port, {"query": "hello"})
        assert body == "olleh"
        rid = headers["X-Pathway-Request-Id"]
        listing = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{mon_port}/request", timeout=10
            ).read()
        )
        trace_doc = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{mon_port}/request?id={rid}", timeout=10
            ).read()
        )
        status = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{mon_port}/status", timeout=10
            ).read()
        )
        metrics = (
            urllib.request.urlopen(
                f"http://127.0.0.1:{mon_port}/metrics", timeout=10
            )
            .read()
            .decode()
        )
        from click.testing import CliRunner

        from pathway_tpu.cli import cli as cli_group

        cli_res = CliRunner().invoke(
            cli_group, ["trace", rid, "--port", str(mon_port)]
        )
        out.update(
            rid=rid,
            listing=listing,
            trace_doc=trace_doc,
            status=status,
            metrics=metrics,
            cli_exit=cli_res.exit_code,
            cli_out=cli_res.output,
        )
        _stop_run()

    th = threading.Thread(target=orchestrate)
    th.start()
    pw.run(monitoring_level="none", with_http_server=True)
    th.join()
    G.clear()

    assert out["rid"] in out["listing"]["kept_ids"]
    doc = out["trace_doc"]
    assert doc["ok"] and doc["kept"]
    assert doc["trace_id"] == req_mod.derive_request_trace_id(out["rid"])
    names = [s["name"] for s in doc["spans"]]
    assert "request" in names and "serve/admission" in names
    assert any(n.startswith("sweep/") for n in names)
    # every child span parents to the request root under one trace id
    root = [s for s in doc["spans"] if s["name"] == "request"][0]
    for s in doc["spans"]:
        assert s["traceId"] == doc["trace_id"]
        if s is not root:
            assert s["parentSpanId"] == root["spanId"]
    # /status: plane summary + slowest exemplars in the serving section
    assert out["status"]["request_trace"]["completed_total"] >= 1
    slowest = out["status"]["serving"]["slowest"]
    assert slowest and slowest[0]["decomposition_ms"]
    # /metrics: per-stage histogram exposition
    assert "pathway_request_stage_seconds_bucket" in out["metrics"]
    assert 'stage="serve/admission"' in out["metrics"]
    assert "pathway_request_traces_kept_total" in out["metrics"]
    # CLI round-trip
    assert out["cli_exit"] == 0, out["cli_out"]
    assert out["rid"] in out["cli_out"]


def test_timeout_trace_kept(monkeypatch):
    """A request the engine never answers is exactly what tail sampling is
    for: the 504 keeps its flight path with status=timeout."""
    import pathway_tpu.io.http._server as server_mod

    monkeypatch.setattr(server_mod, "_REQUEST_TIMEOUT_S", 1.0)
    monkeypatch.setenv("PATHWAY_REQUEST_TRACE_SLOW_MS", "100000")
    monkeypatch.setenv("PATHWAY_REQUEST_TRACE_KEEP", "0")
    port = _free_port()
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    queries, respond = pw.io.http.rest_connector(
        host="127.0.0.1", port=port, schema=QuerySchema
    )
    # answer only non-timeout queries: the filtered-out request never resolves
    respond(
        queries.filter(queries.query != "blackhole").select(
            result=pw.apply(str.upper, queries.query)
        )
    )
    out: dict = {}

    def orchestrate() -> None:
        _wait_ready(port)
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/",
            data=json.dumps({"query": "blackhole"}).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            urllib.request.urlopen(req, timeout=30)
            out["status"] = 200
        except urllib.error.HTTPError as e:
            out["status"] = e.code
            out["rid"] = e.headers.get("X-Pathway-Request-Id")
        plane = req_mod.current()
        out["trace"] = plane.get_trace(out["rid"]) if out.get("rid") else None
        _stop_run()

    th = threading.Thread(target=orchestrate)
    th.start()
    pw.run(monitoring_level="none")
    th.join()
    G.clear()
    assert out["status"] == 504
    assert out["trace"] is not None and out["trace"]["ok"]
    assert out["trace"]["kept"] and out["trace"]["status"] == "timeout"


# ----------------------------------------------------------- flight recorder


def test_flight_dump_names_inflight_requests(tmp_path, monkeypatch):
    """Satellite: a crash post-mortem dump includes the in-flight request
    table (request_id, route, stage reached, elapsed)."""
    monkeypatch.setenv("PATHWAY_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("PATHWAY_REQUEST_TRACE", "on")
    plane = req_mod.install_from_env()
    try:
        import time as _t

        key = 12345
        plane.begin(key, "/v1/retrieve", _t.monotonic_ns())
        plane.note_tick(7)
        w = _t.monotonic_ns()
        plane.note_stage(7, "index/search", w, w + 1000, rows=1)
        from pathway_tpu.observability import device as device_mod

        path = device_mod.flight_dump("test_crash")
        assert path is not None
        doc = json.loads(open(path).read())
        assert "requests" in doc and len(doc["requests"]) == 1
        row = doc["requests"][0]
        assert row["request_id"] == f"{key:016x}"
        assert row["route"] == "/v1/retrieve"
        assert row["stage"] == "index/search"
        assert row["elapsed_ms"] >= 0
    finally:
        req_mod.shutdown()


# -------------------------------------------------------- 2-process cluster

_CLUSTER_DELAY_SCRIPT = textwrap.dedent(
    """
    import json, os, socket, sys, threading, time, urllib.request

    import pathway_tpu as pw
    from pathway_tpu.observability import requests as req_mod

    port = int(sys.argv[1])
    N_CLIENTS = 16
    PER_CLIENT = 31  # 496 background requests
    NEEDLE = "needle-313"

    class QuerySchema(pw.Schema):
        query: str

    queries, respond = pw.io.http.rest_connector(
        host="127.0.0.1", port=port, schema=QuerySchema
    )

    def work(q):
        if q == NEEDLE:
            time.sleep(0.4)
        return q.upper()

    respond(queries.select(result=pw.apply(work, queries.query)))

    pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
    if pid == 0:
        def post(q):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/",
                data=json.dumps({"query": q}).encode(),
                headers={"Content-Type": "application/json"},
            )
            r = urllib.request.urlopen(req, timeout=60)
            return json.loads(r.read()), r.headers.get("X-Pathway-Request-Id")

        def orchestrate():
            deadline = time.monotonic() + 45
            while time.monotonic() < deadline:
                try:
                    socket.create_connection(("127.0.0.1", port), timeout=0.5).close()
                    break
                except OSError:
                    time.sleep(0.05)
            ids = {}
            lock = threading.Lock()

            def client(ci):
                for j in range(PER_CLIENT):
                    q = f"q-{ci}-{j}"
                    body, rid = post(q)
                    assert body == q.upper(), (q, body)
                    with lock:
                        ids[q] = rid

            threads = [
                threading.Thread(target=client, args=(ci,))
                for ci in range(N_CLIENTS)
            ]
            for t in threads:
                t.start()
            # the needle flies amid the concurrent background load
            body, needle_id = post(NEEDLE)
            assert body == NEEDLE.upper()
            for t in threads:
                t.join()
            # quiesce so the needle's ticket fully settles
            time.sleep(0.3)
            plane = req_mod.current()
            doc = plane.get_trace(needle_id)
            total = len(ids) + 1
            print("RESULT:" + json.dumps({
                "total": total,
                "summary": plane.status_summary(),
                "needle": doc,
            }), flush=True)
            rt = pw.internals.run.current_runtime()
            if rt is not None:
                rt.request_stop()

        threading.Thread(target=orchestrate, daemon=True).start()

    pw.run(monitoring_level="none")
    print("DONE", flush=True)
    """
)


def _run_cluster(script_text: str, argv: list[str], extra_env: dict, timeout=240):
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "cluster_script.py")
        with open(script, "w") as fh:
            fh.write(script_text)
        env = dict(os.environ)
        env.update(
            PATHWAY_PROCESSES="2",
            PATHWAY_THREADS="1",
            PATHWAY_BARRIER_TIMEOUT="60",
            PATHWAY_FIRST_PORT=str(free_port_base(3)),
            JAX_PLATFORMS="cpu",
            PYTHONPATH=REPO,
        )
        env.update(extra_env)
        procs = []
        for pid in range(2):
            penv = dict(env, PATHWAY_PROCESS_ID=str(pid))
            procs.append(
                subprocess.Popen(
                    [sys.executable, script] + argv,
                    env=penv,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT,
                    text=True,
                )
            )
        outputs = []
        for p in procs:
            try:
                stdout, _ = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                texts = []
                for q in procs:
                    q.kill()
                    o, _ = q.communicate()
                    texts.append(o or "")
                raise AssertionError(
                    "cluster process hung; output:\n" + "\n---\n".join(texts)
                )
            outputs.append(stdout)
        if any(p.returncode != 0 for p in procs):
            joined = "\n=== next process ===\n".join(outputs)
            codes = [p.returncode for p in procs]
            raise AssertionError(f"cluster processes exited {codes}:\n{joined}")
        return outputs


def test_tail_sampling_catches_injected_delay_cluster():
    """The acceptance criterion's cluster half: 500 requests through a
    2-process cluster, one with an injected 0.4 s stage delay, head sampling
    at 1% — the kept trace attributes >=80% of the needle's latency to the
    injected engine stage."""
    http_port = _free_port()
    outputs = _run_cluster(
        _CLUSTER_DELAY_SCRIPT,
        [str(http_port)],
        {
            "PATHWAY_TRACE": "on",
            "PATHWAY_TRACE_SAMPLE": "0.01",
            "PATHWAY_REQUEST_TRACE": "on",
            "PATHWAY_REQUEST_TRACE_SLOW_MS": "150",
            "PATHWAY_REQUEST_TRACE_KEEP": "0.002",
            "PATHWAY_SERVE_COALESCE_MS": "5",
        },
        timeout=420,
    )
    line = [l for l in outputs[0].splitlines() if l.startswith("RESULT:")]
    assert line, outputs[0]
    res = json.loads(line[0][len("RESULT:") :])
    assert res["total"] == 497
    doc = res["needle"]
    assert doc["ok"] and doc["kept"] and doc["status"] == "ok"
    assert doc["duration_ms"] >= 380
    decomp = doc["decomposition_ms"]
    engine = {k: v for k, v in decomp.items() if k.startswith("sweep/")}
    assert engine, decomp
    top_stage, top_ms = max(engine.items(), key=lambda kv: kv[1])
    assert top_ms >= 0.8 * doc["duration_ms"], (top_stage, top_ms, doc)
    # tail sampling kept the anomaly without keeping the fleet
    assert res["summary"]["kept_total"] < res["total"] * 0.25


_CLUSTER_STITCH_SCRIPT = textwrap.dedent(
    """
    import json, os, socket, sys, threading, time, urllib.request

    import numpy as np
    import pathway_tpu as pw
    from pathway_tpu.observability import requests as req_mod
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.mocks import FakeEmbedder

    port = int(sys.argv[1])

    class QuerySchema(pw.Schema):
        query: str

    emb = FakeEmbedder(dimension=12, deterministic=True)
    docs = [f"document number {i} about topic {i % 5}" for i in range(16)]
    doc_t = pw.debug.table_from_rows(
        pw.schema_from_types(text=str), [(d,) for d in docs]
    )
    index = BruteForceKnnFactory(embedder=emb, reserved_space=64).build_index(
        doc_t.text, doc_t
    )

    queries, respond = pw.io.http.rest_connector(
        host="127.0.0.1", port=port, schema=QuerySchema, route="/v1/retrieve"
    )
    picked = index.query_as_of_now(queries.query, number_of_matches=2).select(
        q=pw.left.query,
        top=pw.apply(lambda ts: list(ts) if ts else [], pw.right.text),
    )
    respond(picked.select(result=pw.apply(lambda t: {"docs": t}, picked.top)))

    pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
    if pid == 0:
        def orchestrate():
            deadline = time.monotonic() + 45
            while time.monotonic() < deadline:
                try:
                    socket.create_connection(("127.0.0.1", port), timeout=0.5).close()
                    break
                except OSError:
                    time.sleep(0.05)
            def post(q):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/v1/retrieve",
                    data=json.dumps({"query": q}).encode(),
                    headers={"Content-Type": "application/json"},
                )
                r = urllib.request.urlopen(req, timeout=60)
                return json.loads(r.read()), r.headers.get("X-Pathway-Request-Id")

            # settle: as-of-now answers reflect index state at arrival, so
            # wait until the corpus is fully indexed (k=2 answered twice
            # identically) before the measured, byte-compared queries fly
            prev = None
            for _ in range(100):
                body, _rid = post("warmup probe")
                if len(body["docs"]) == 2 and body == prev:
                    break
                prev = body
                time.sleep(0.1)
            answers = {}
            rids = {}
            for i in range(6):
                q = f"topic {i % 5} please"
                body, rid = post(q)
                answers[f"{q}#{i}"] = body
                rids[f"{q}#{i}"] = rid
            print("ANSWERS:" + json.dumps(answers, sort_keys=True), flush=True)
            plane = req_mod.current()
            rt = pw.internals.run.current_runtime()
            if plane is None:
                # PATHWAY_REQUEST_TRACE=off: no plane, no rings, no ids — the
                # engine's per-tick plane slot stayed None (one is-None test)
                assert all(v is None for v in rids.values()), rids
                assert getattr(rt, "_rp", "missing") is None
                print("OFF_OK", flush=True)
            else:
                time.sleep(0.3)
                traces = [plane.get_trace(rid) for rid in rids.values()]
                print("TRACES:" + json.dumps(traces), flush=True)
            if rt is not None:
                rt.request_stop()

        threading.Thread(target=orchestrate, daemon=True).start()

    pw.run(monitoring_level="none")
    print("DONE", flush=True)
    """
)


def test_cluster_cross_process_stitching_and_off_mode():
    """Satellite: a 2-proc cluster /v1/retrieve whose KNN index shards live
    partly on the peer yields ONE trace id per request with stage spans from
    BOTH processes; with PATHWAY_REQUEST_TRACE=off the answers are
    byte-identical and no plane (hence no rings) exists anywhere."""
    port_on = _free_port()
    on_out = _run_cluster(
        _CLUSTER_STITCH_SCRIPT,
        [str(port_on)],
        {
            "PATHWAY_REQUEST_TRACE": "on",
            "PATHWAY_REQUEST_TRACE_SLOW_MS": "0",  # keep every trace
        },
        timeout=300,
    )
    port_off = _free_port()
    off_out = _run_cluster(
        _CLUSTER_STITCH_SCRIPT,
        [str(port_off)],
        {"PATHWAY_REQUEST_TRACE": "off"},
        timeout=300,
    )

    def _grab(lines, tag):
        hits = [l for l in lines.splitlines() if l.startswith(tag)]
        assert hits, lines
        return hits[0][len(tag) :]

    answers_on = json.loads(_grab(on_out[0], "ANSWERS:"))
    answers_off = json.loads(_grab(off_out[0], "ANSWERS:"))
    assert answers_on == answers_off, "tracing changed the served answers"
    assert "OFF_OK" in off_out[0]
    traces = json.loads(_grab(on_out[0], "TRACES:"))
    assert traces and all(t["ok"] and t["kept"] for t in traces)
    stitched = 0
    for t in traces:
        tids = {s["traceId"] for s in t["spans"]}
        assert tids == {t["trace_id"]}, "spans split across trace ids"
        procs = set()
        for s in t["spans"]:
            for a in s["attributes"]:
                if a["key"] == "pathway.process_id":
                    procs.add(int(a["value"]["intValue"]))
        if procs == {0, 1}:
            stitched += 1
    assert stitched >= 1, (
        "no trace carried stage spans from both processes: "
        + json.dumps(traces)[:2000]
    )


# ----------------------------------------------- review regressions (serving)


def test_multi_route_request_ids_unique(monkeypatch):
    """Two routes on one webserver mint from a process-wide key sequence: a
    route-local counter would hand the Nth request of each route the SAME
    engine key, cross-wiring their request ids, live-table records, and
    derived trace ids."""
    monkeypatch.setenv("PATHWAY_REQUEST_TRACE_SLOW_MS", "0")  # keep everything
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    port = _free_port()
    ws = pw.io.http.PathwayWebserver(host="127.0.0.1", port=port)
    q_a, respond_a = pw.io.http.rest_connector(
        webserver=ws, route="/a", schema=QuerySchema
    )
    q_b, respond_b = pw.io.http.rest_connector(
        webserver=ws, route="/b", schema=QuerySchema
    )
    respond_a(q_a.select(result=pw.apply(str.upper, q_a.query)))
    respond_b(q_b.select(result=pw.apply(str.lower, q_b.query)))

    out: dict = {}

    def orchestrate() -> None:
        try:
            _wait_ready(port)
            ids = []
            for i in range(4):
                body_a, h_a = _post(port, {"query": f"Xy-{i}"}, route="/a")
                body_b, h_b = _post(port, {"query": f"Xy-{i}"}, route="/b")
                assert body_a == f"XY-{i}" and body_b == f"xy-{i}"
                ids.append(h_a["X-Pathway-Request-Id"])
                ids.append(h_b["X-Pathway-Request-Id"])
            out["ids"] = ids
            plane = req_mod.current()
            out["kept"] = plane.kept_ids()
            out["summary"] = plane.status_summary()
        except Exception as e:  # pragma: no cover - surfaced below
            out["error"] = repr(e)
        finally:
            _stop_run()

    th = threading.Thread(target=orchestrate)
    th.start()
    pw.run(monitoring_level="none")
    th.join()
    G.clear()
    assert "error" not in out, out.get("error")
    ids = out["ids"]
    assert len(set(ids)) == len(ids), f"request ids collided across routes: {ids}"
    # every flight completed under its own id (slow_ms=0 keeps all 8)
    assert out["summary"]["completed_total"] == 8
    assert set(ids) <= set(out["kept"])


def test_client_disconnect_completes_cancelled_flight(monkeypatch):
    """A client that disconnects mid-flight cancels its handler (aiohttp
    handler_cancellation): the in-flight record must complete as 'cancelled'
    (kept by tail sampling) instead of leaking in the live table and pinning
    plane.hot until the 120 s timeout."""
    monkeypatch.setenv("PATHWAY_REQUEST_TRACE_SLOW_MS", "100000")
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    port = _free_port()
    queries, respond = pw.io.http.rest_connector(
        host="127.0.0.1", port=port, schema=QuerySchema
    )
    # blackhole pipeline: a filtered-out query never resolves its future
    answered = queries.filter(queries.query != "blackhole")
    respond(answered.select(result=pw.apply(str.upper, answered.query)))

    out: dict = {}

    def orchestrate() -> None:
        try:
            _wait_ready(port)
            # raw socket POST, then hang up before any response can arrive
            body = json.dumps({"query": "blackhole"}).encode()
            s = socket.create_connection(("127.0.0.1", port), timeout=5)
            s.sendall(
                b"POST / HTTP/1.1\r\nHost: x\r\nContent-Type: application/json\r\n"
                + f"Content-Length: {len(body)}\r\n\r\n".encode()
                + body
            )
            time.sleep(0.5)  # let the handler register + push the row
            plane = req_mod.current()
            out["inflight_before"] = plane.status_summary()["in_flight"]
            s.close()
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                summary = plane.status_summary()
                if summary["in_flight"] == 0 and summary["by_status"].get(
                    "cancelled"
                ):
                    break
                time.sleep(0.05)
            out["summary"] = plane.status_summary()
            # a normal request afterwards still serves fine
            body2, _h = _post(port, {"query": "alive"})
            assert body2 == "ALIVE"
        except Exception as e:  # pragma: no cover - surfaced below
            out["error"] = repr(e)
        finally:
            _stop_run()

    th = threading.Thread(target=orchestrate)
    th.start()
    pw.run(monitoring_level="none")
    th.join()
    G.clear()
    assert "error" not in out, out.get("error")
    assert out["inflight_before"] == 1, out
    summary = out["summary"]
    assert summary["in_flight"] == 0, f"cancelled request leaked: {summary}"
    assert summary["by_status"].get("cancelled") == 1, summary
