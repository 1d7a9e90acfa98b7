"""Serving-plane tests (ISSUE 10): concurrent REST front door.

Covers the r14 tentpole surface — N parallel clients coalescing into few
engine ticks with byte-correct answers, the 429 shed path with exact counts,
arrival-driven single-request latency beating the fixed poll, webserver
lifecycle (back-to-back port reuse + 503 flush on shutdown), query-row
retraction (``delete_completed_queries``/``keep_queries``), OpenAPI at
``/_schema``, the ``/status``+``/metrics`` serving section, the
DocumentStore→TieredKnnFactory default, and a 2-process cluster run with the
route live on the coordinator.
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
from pathway_tpu.internals.parse_graph import G
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


def _wait_ready(port: int, timeout: float = 15.0) -> None:
    """TCP-connect readiness probe (no HTTP request, so request counters stay
    exact)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port), timeout=0.5).close()
            return
        except OSError:
            time.sleep(0.02)
    raise AssertionError(f"server on port {port} never came up")


def _post(port: int, payload: dict, route: str = "/", timeout: float = 30.0):
    """POST returning (status, parsed body, headers)."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{route}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        resp = urllib.request.urlopen(req, timeout=timeout)
        return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        body = e.read()
        try:
            parsed = json.loads(body)
        except ValueError:
            parsed = body.decode(errors="replace")
        return e.code, parsed, dict(e.headers)


def _stop_current_run() -> None:
    rt = pw.internals.run.current_runtime()
    if rt is not None:
        rt.request_stop()


# ------------------------------------------------------------------ coalescing


def test_concurrent_clients_coalesce_byte_correct(monkeypatch):
    """16 parallel clients against one route: every request answered
    byte-correctly, the requests coalesce into a few engine ticks (not one
    tick per request), and the serving section shows up on /status+/metrics."""
    n_clients = 16
    port = _free_port()
    mon_port = _free_port()
    # wide coalesce window so simultaneous clients provably share ticks
    monkeypatch.setenv("PATHWAY_SERVE_COALESCE_MS", "100")
    monkeypatch.setenv("PATHWAY_MONITORING_HTTP_PORT", str(mon_port))

    queries, respond = pw.io.http.rest_connector(
        host="127.0.0.1", port=port, schema=QuerySchema
    )
    respond(queries.select(result=pw.apply(lambda q: q.upper(), queries.query)))

    results: dict[int, tuple] = {}
    status_doc: dict = {}
    metrics_text: list[str] = []

    def client(i: int, barrier: threading.Barrier) -> None:
        barrier.wait()
        results[i] = _post(port, {"query": f"hello-{i}"})

    def orchestrate() -> None:
        _wait_ready(port)
        barrier = threading.Barrier(n_clients)
        threads = [
            threading.Thread(target=client, args=(i, barrier))
            for i in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        status_doc.update(
            json.loads(
                urllib.request.urlopen(
                    f"http://127.0.0.1:{mon_port}/status", timeout=10
                ).read()
            )
        )
        metrics_text.append(
            urllib.request.urlopen(
                f"http://127.0.0.1:{mon_port}/metrics", timeout=10
            )
            .read()
            .decode()
        )
        _stop_current_run()

    th = threading.Thread(target=orchestrate)
    th.start()
    pw.run(monitoring_level="none", with_http_server=True)
    th.join()

    assert len(results) == n_clients
    for i, (status, body, _hdr) in results.items():
        assert status == 200, (i, body)
        assert body == f"HELLO-{i}"

    from pathway_tpu.io.http._server import serving_status

    rt = pw.internals.run.current_runtime()
    serving = serving_status(rt)
    assert serving is not None
    [route] = serving["routes"]
    assert route["requests_total"] == n_clients
    assert route["responses_total"] == n_clients
    assert route["shed_total"] == 0
    # the coalescing claim: 16 simultaneous requests must NOT take 16
    # response ticks (the 100 ms window gathers them into a handful)
    assert 1 <= route["batches_total"] <= 5, route
    assert route["mean_batch"] >= n_clients / 5

    # /status carried the serving section while live; /metrics the counters
    live = status_doc["serving"]["routes"][0]
    assert live["requests_total"] == n_clients
    assert "pathway_serve_requests_total" in metrics_text[0]
    assert 'pathway_serve_responses_total{route="/"}' in metrics_text[0]


# ------------------------------------------------------------------- shed path


def test_shed_returns_429_with_exact_counts(monkeypatch):
    """A tiny in-flight budget + a slow pipeline: overflow clients get a fast
    429 with Retry-After, and the route counters account for every request."""
    n_clients = 8
    port = _free_port()
    monkeypatch.setenv("PATHWAY_SERVE_MAX_INFLIGHT", "2")

    queries, respond = pw.io.http.rest_connector(
        host="127.0.0.1", port=port, schema=QuerySchema
    )

    def slow_upper(q: str) -> str:
        time.sleep(0.25)
        return q.upper()

    respond(queries.select(result=pw.apply(slow_upper, queries.query)))

    results: dict[int, tuple] = {}

    def client(i: int, barrier: threading.Barrier) -> None:
        barrier.wait()
        results[i] = _post(port, {"query": f"q{i}"})

    def orchestrate() -> None:
        _wait_ready(port)
        barrier = threading.Barrier(n_clients)
        threads = [
            threading.Thread(target=client, args=(i, barrier))
            for i in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        _stop_current_run()

    th = threading.Thread(target=orchestrate)
    th.start()
    pw.run(monitoring_level="none")
    th.join()

    ok = {i: r for i, r in results.items() if r[0] == 200}
    shed = {i: r for i, r in results.items() if r[0] == 429}
    assert len(ok) + len(shed) == n_clients, results
    # budget is 2 and resolution needs an engine tick that takes >= 0.25 s,
    # while all 8 arrive within milliseconds: most must shed
    assert len(shed) >= 4, results
    for i, (_s, body, hdr) in shed.items():
        assert hdr.get("Retry-After"), (i, hdr)
        assert body["error"] == "overloaded"
    for i, (_s, body, _h) in ok.items():
        assert body == f"Q{i}".upper()

    from pathway_tpu.io.http._server import serving_status

    serving = serving_status(pw.internals.run.current_runtime())
    [route] = serving["routes"]
    assert route["shed_total"] == len(shed)
    assert route["responses_total"] == len(ok)
    assert route["requests_total"] == n_clients


# ------------------------------------------------- arrival-driven query ticks


def test_arrival_tick_beats_fixed_poll_latency():
    """With a 400 ms autocommit the pre-r14 connector answered no faster than
    the poll period; the arrival-driven wakeup must answer well under it."""
    port = _free_port()
    queries, respond = pw.io.http.rest_connector(
        host="127.0.0.1", port=port, schema=QuerySchema
    )
    respond(queries.select(result=pw.apply(lambda q: q.upper(), queries.query)))

    timings: list[float] = []
    answers: list = []

    def orchestrate() -> None:
        _wait_ready(port)
        # warm one request (first tick may pay jit/compile costs), then time
        _post(port, {"query": "warm"})
        for i in range(3):
            t0 = time.perf_counter()
            status, body, _ = _post(port, {"query": f"fast-{i}"})
            timings.append(time.perf_counter() - t0)
            answers.append((status, body))
        _stop_current_run()

    th = threading.Thread(target=orchestrate)
    th.start()
    pw.run(monitoring_level="none", autocommit_duration_ms=400)
    th.join()

    assert all(s == 200 for s, _ in answers), answers
    # fixed-poll would floor every request at ~the 400 ms period; the arrival
    # path's bound is the coalesce window (2 ms) + one tick
    assert min(timings) < 0.35, timings


# ------------------------------------------------------------------- lifecycle


def test_webserver_lifecycle_port_reuse_and_shutdown_flush():
    """Run 1 leaves a request pending (its query produces no response row) —
    engine shutdown must flush it with a fast 503. Run 2 binds the SAME port
    immediately after: stop() released it (cleanup awaited, thread joined)."""
    port = _free_port()

    queries, respond = pw.io.http.rest_connector(
        host="127.0.0.1", port=port, schema=QuerySchema
    )
    answered = queries.filter(queries.query != "blackhole")
    respond(answered.select(result=pw.apply(lambda q: q.upper(), answered.query)))

    pending_result: dict = {}

    def orchestrate() -> None:
        _wait_ready(port)

        def pending_client() -> None:
            t0 = time.perf_counter()
            status, body, _ = _post(port, {"query": "blackhole"})
            pending_result.update(
                status=status, body=body, elapsed=time.perf_counter() - t0
            )

        t = threading.Thread(target=pending_client)
        t.start()
        time.sleep(0.5)  # let the request register + drain into the engine
        _stop_current_run()
        t.join(timeout=30)
        assert not t.is_alive(), "pending client still blocked after stop"

    th = threading.Thread(target=orchestrate)
    th.start()
    pw.run(monitoring_level="none")
    th.join()

    assert pending_result["status"] == 503, pending_result
    # flushed at shutdown, NOT after the 120 s request timeout
    assert pending_result["elapsed"] < 30, pending_result

    # ---- run 2: fresh pipeline on the same port ----
    G.clear()
    queries2, respond2 = pw.io.http.rest_connector(
        host="127.0.0.1", port=port, schema=QuerySchema
    )
    respond2(queries2.select(result=pw.apply(lambda q: q.upper(), queries2.query)))

    result2: dict = {}

    def orchestrate2() -> None:
        _wait_ready(port)
        status, body, _ = _post(port, {"query": "again"})
        result2.update(status=status, body=body)
        _stop_current_run()

    th2 = threading.Thread(target=orchestrate2)
    th2.start()
    pw.run(monitoring_level="none")
    th2.join()
    assert result2 == {"status": 200, "body": "AGAIN"}


# ------------------------------------------------- keep/delete served queries


def _run_query_row_lifecycle(keep_queries: bool) -> list[bool]:
    """One served request; returns the queries-table additions/retractions
    observed by an independent subscriber."""
    port = _free_port()
    queries, respond = pw.io.http.rest_connector(
        host="127.0.0.1", port=port, schema=QuerySchema, keep_queries=keep_queries
    )
    respond(queries.select(result=pw.apply(lambda q: q.upper(), queries.query)))

    events: list[bool] = []
    pw.io.subscribe(
        queries,
        lambda key, row, time, is_addition: events.append(is_addition),
        service_class="bulk",
    )

    def orchestrate() -> None:
        _wait_ready(port)
        status, body, _ = _post(port, {"query": "x"})
        assert (status, body) == (200, "X")
        time.sleep(0.3)  # let the post-serve retraction tick land
        _stop_current_run()

    th = threading.Thread(target=orchestrate)
    th.start()
    pw.run(monitoring_level="none")
    th.join()
    return events


def test_delete_completed_queries_retracts_served_row():
    assert _run_query_row_lifecycle(keep_queries=False) == [True, False]


def test_keep_queries_retains_served_row():
    G.clear()
    assert _run_query_row_lifecycle(keep_queries=True) == [True]


# --------------------------------------------------------------------- OpenAPI


def test_openapi_schema_endpoint():
    port = _free_port()

    class RetrieveSchema(pw.Schema):
        query: str
        k: int = pw.column_definition(default_value=3)

    queries, respond = pw.io.http.rest_connector(
        host="127.0.0.1",
        port=port,
        route="/v1/retrieve",
        schema=RetrieveSchema,
        methods=("GET", "POST"),
        documentation=pw.io.http.EndpointDocumentation(
            summary="Retrieve top-k chunks", tags=["rag"]
        ),
    )
    respond(queries.select(result=pw.apply(lambda q, k: q * k, queries.query, queries.k)))

    spec: dict = {}

    def orchestrate() -> None:
        _wait_ready(port)
        spec.update(
            json.loads(
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/_schema", timeout=10
                ).read()
            )
        )
        # GET path with query-param coercion (k arrives as a string)
        status, body, _ = _post(port, {"query": "ab", "k": 2}, route="/v1/retrieve")
        assert (status, body) == (200, "abab")
        _stop_current_run()

    th = threading.Thread(target=orchestrate)
    th.start()
    pw.run(monitoring_level="none")
    th.join()

    assert spec["openapi"].startswith("3.")
    item = spec["paths"]["/v1/retrieve"]
    assert set(item) == {"get", "post"}
    post_op = item["post"]
    assert post_op["summary"] == "Retrieve top-k chunks"
    assert post_op["tags"] == ["rag"]
    body_schema = post_op["requestBody"]["content"]["application/json"]["schema"]
    assert body_schema["properties"]["query"] == {"type": "string"}
    assert body_schema["properties"]["k"] == {"type": "integer", "default": 3}
    assert body_schema["required"] == ["query"]
    get_params = {p["name"]: p for p in item["get"]["parameters"]}
    assert get_params["query"]["required"] is True
    assert get_params["k"]["required"] is False


# ------------------------------------------ DocumentStore tiered default (r13)


def test_document_store_defaults_to_tiered_and_matches_bruteforce(monkeypatch):
    """DocumentStore without a retriever_factory builds the tiered index; a
    corpus 4x the hot bound answers byte-identically to BruteForce."""
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
    from pathway_tpu.stdlib.indexing.retrievers import TieredKnnFactory
    from pathway_tpu.xpacks.llm import DocumentStore
    from pathway_tpu.xpacks.llm.mocks import FakeEmbedder

    from utils import rows_of

    monkeypatch.setenv("PATHWAY_INDEX_HOT_ROWS", "32")
    n_docs, dim, k = 128, 16, 8
    texts = [f"document number {i} about topic {i % 13}" for i in range(n_docs)]
    probes = [f"document number {i * 17 % n_docs} about topic 0" for i in range(6)]

    def retrieve_all(factory=None, embedder=None):
        G.clear()
        docs = pw.debug.table_from_rows(
            pw.schema_from_types(data=str), [(t,) for t in texts]
        )
        store = DocumentStore(docs, retriever_factory=factory, embedder=embedder)
        q = pw.debug.table_from_rows(
            DocumentStore.RetrieveQuerySchema, [(p, k, None, None) for p in probes]
        )
        rows = [
            r[0].value if hasattr(r[0], "value") else r[0]
            for r in rows_of(store.retrieve_query(q))
        ]
        return store, sorted(rows, key=lambda hits: json.dumps(hits))

    emb = FakeEmbedder(dimension=dim)
    tiered_store, tiered_rows = retrieve_all(embedder=emb)
    assert isinstance(tiered_store.retriever_factory, TieredKnnFactory)
    brute_store, brute_rows = retrieve_all(
        factory=BruteForceKnnFactory(embedder=FakeEmbedder(dimension=dim))
    )
    assert tiered_rows == brute_rows
    # release the tiered backend NOW: the device plane's tier-stats registry
    # is weak, but CPython collects the graph's reference cycles lazily — a
    # later test asserting on live tier stats must not see this corpus
    import gc

    del tiered_store, brute_store
    G.clear()
    gc.collect()


def test_push_admitted_refuses_without_blocking_when_credit_exhausted(monkeypatch):
    """With the flow plane on, the REST push takes ingest credit atomically
    and NON-blockingly: a saturated gate refuses (the handler sheds 429) —
    it neither silently drops a row whose future is registered nor stalls
    the event loop on the blocking credit path."""
    from pathway_tpu import flow
    from pathway_tpu.engine import operators as ops
    from pathway_tpu.io.http._server import _RouteServing

    monkeypatch.setenv("PATHWAY_FLOW", "on")
    monkeypatch.setenv("PATHWAY_INPUT_QUEUE_ROWS", "2")
    plane = flow.install_from_env()
    assert plane is not None
    try:
        node = ops.StreamInputNode(["query"])
        node.input_name = "rest:/"
        rs = _RouteServing("/", ("POST",), None)
        rs.node = node
        assert rs.push_admitted(1, ("a",))
        assert rs.push_admitted(2, ("b",))
        t0 = time.perf_counter()
        assert not rs.push_admitted(3, ("c",))  # full: refused immediately
        assert time.perf_counter() - t0 < 0.1, "refusal must not block"
        gate = node.flow_gate
        assert gate.queued == 2 and gate.admitted_rows == 2
        assert len(node._pending) == 2  # the refused row never appended
    finally:
        flow.shutdown()


# ------------------------------------------- DocumentStore over the front door


def test_document_store_server_retrieve_over_rest():
    """The full RAG serving path: DocumentStoreServer's /v1/retrieve answers a
    live HTTP query with the real top-k — NOT the provisional padded row.
    (Pre-r14 the as-of-now join padded over the whole query universe, so the
    response future resolved with [] whenever the reply landed a tick after
    the query — which the microbatch embed path makes the common case.)"""
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm import DocumentStore
    from pathway_tpu.xpacks.llm.mocks import FakeEmbedder
    from pathway_tpu.xpacks.llm.servers import DocumentStoreServer

    docs = pw.debug.table_from_rows(
        pw.schema_from_types(data=str),
        [("kafka topics stream rows",), ("tpu matmul systolic array",),
         ("bananas are yellow",)],
    )
    # brute-force factory: pw.run's last-runtime handle keeps this graph (and
    # so its index backend) alive until the next run — a tiered backend here
    # would leak into later tests' live tier-stats assertions
    store = DocumentStore(
        docs, retriever_factory=BruteForceKnnFactory(embedder=FakeEmbedder(dimension=16))
    )
    port = _free_port()
    DocumentStoreServer("127.0.0.1", port, store)
    out: dict = {}

    def drive() -> None:
        _wait_ready(port)
        status, body, _ = _post(
            port, {"query": "kafka topics stream rows", "k": 1},
            route="/v1/retrieve",
        )
        out["status"], out["body"] = status, body
        _stop_current_run()

    th = threading.Thread(target=drive)
    th.start()
    pw.run(monitoring_level="none")
    th.join()
    assert out["status"] == 200
    assert out["body"], "retrieve returned the provisional padded reply"
    assert out["body"][0]["text"] == "kafka topics stream rows", out


# --------------------------------------------- serving-tier embedding memo


def test_embedder_memo_identical_deduped_and_bounded():
    """The opt-in embedding memo (serving tier): values identical to the
    uncached path, duplicates within a batch (microbatch pad replicas) encode
    once, repeats are hits, and the LRU stays bounded."""
    import numpy as np

    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    plain = SentenceTransformerEmbedder("tiny", seed=0)
    memo = SentenceTransformerEmbedder("tiny", seed=0, memoize=8)
    texts = [f"alpha beta gamma {i}" for i in range(6)]  # uniform lengths
    want = plain.func(list(texts))
    got = memo.func(list(texts))
    assert all(np.array_equal(a, b) for a, b in zip(want, got))
    assert memo.memo_misses == 6 and memo.memo_hits == 0
    # pad-replica pattern: 8 copies of one text = exactly one encoded miss
    memo.func([texts[0]] * 8)
    assert memo.memo_misses == 6 and memo.memo_hits == 8
    again = memo.func(list(texts))
    assert all(np.array_equal(a, b) for a, b in zip(want, again))
    assert memo.memo_misses == 6  # all hits
    # bound holds under churn
    memo.func([f"delta {i} epsilon zeta" for i in range(20)])
    assert len(memo._memo) <= 8


# ----------------------------------------------------------- 2-process cluster


_CLUSTER_SCRIPT = textwrap.dedent(
    """
    import json
    import os
    import socket
    import sys
    import threading
    import time
    import urllib.request

    import pathway_tpu as pw

    port = int(sys.argv[1])

    class QuerySchema(pw.Schema):
        query: str

    queries, respond = pw.io.http.rest_connector(
        host="127.0.0.1", port=port, schema=QuerySchema
    )
    respond(queries.select(result=pw.apply(lambda q: q.upper(), queries.query)))

    pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
    if pid == 0:
        def client():
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                try:
                    socket.create_connection(("127.0.0.1", port), timeout=0.5).close()
                    break
                except OSError:
                    time.sleep(0.05)
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/",
                data=json.dumps({"query": "pod"}).encode(),
                headers={"Content-Type": "application/json"},
            )
            body = json.loads(urllib.request.urlopen(req, timeout=30).read())
            print("ANSWER:" + body, flush=True)
            rt = pw.internals.run.current_runtime()
            if rt is not None:
                rt.request_stop()

        threading.Thread(target=client, daemon=True).start()

    pw.run(monitoring_level="none")
    print("DONE", flush=True)
    """
)


def test_cluster_route_live_on_coordinator(tmp_path):
    """2-process cluster with the REST route served by the coordinator: the
    query flows through the pod (barriers, heartbeats) and comes back upper-
    cased; the stop propagates to the peer."""
    script = tmp_path / "serve_cluster.py"
    script.write_text(_CLUSTER_SCRIPT)
    http_port = _free_port()
    env = dict(os.environ)
    env.update(
        PATHWAY_PROCESSES="2",
        PATHWAY_THREADS="1",
        PATHWAY_BARRIER_TIMEOUT="45",
        PATHWAY_FIRST_PORT=str(free_port_base(3)),
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO,
    )
    procs = []
    for pid in range(2):
        penv = dict(env, PATHWAY_PROCESS_ID=str(pid))
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script), str(http_port)],
                env=penv,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outputs = []
    for p in procs:
        try:
            stdout, _ = p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            texts = []
            for q in procs:
                q.kill()
                out, _ = q.communicate()
                texts.append(out or "")
            raise AssertionError(
                "cluster process hung; output:\n" + "\n---\n".join(texts)
            )
        outputs.append(stdout)
    for p, txt in zip(procs, outputs):
        assert p.returncode == 0, f"process exited {p.returncode}:\n{txt}"
    assert "ANSWER:POD" in outputs[0], outputs[0]
    assert all("DONE" in o for o in outputs), outputs
