"""Distributed serving fabric (r18): every process is a front door.

Covers the fabric plane end to end: the transport's RPC/cast contract, the
token-bucket + API-key door protection with EXACT counters under a mixed
authorized/unauthorized flood, pid-salted request-key minting, the replica
store's changelog/lag semantics, single-process ``serve_table``, a 3-process
cluster whose embed→KNN→rerank answers are byte-identical from every door
(and to a single-process run) with the r16 trace stitching ingress and owner
spans under one trace id, a 2-process replica answering within the
configured staleness bound under churn, and (slow) SIGKILL of a peer front
door under a Supervisor — the fabric re-forms and serves again.
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
from conftest import free_port_base

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_ready(port: int, timeout: float = 40.0) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port), timeout=0.5).close()
            return
        except OSError:
            time.sleep(0.05)
    raise TimeoutError(f"port {port} never came up")


def _get(url: str, timeout: float = 30.0):
    """(status, body, headers) without raising on HTTP errors."""
    try:
        r = urllib.request.urlopen(url, timeout=timeout)
        return r.status, r.read().decode(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(), dict(e.headers)


def _post(url: str, payload: dict, headers: dict | None = None, timeout: float = 60.0):
    req = urllib.request.Request(
        url,
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        r = urllib.request.urlopen(req, timeout=timeout)
        return r.status, r.read().decode(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(), dict(e.headers)


# ---------------------------------------------------------------------- units


def test_token_bucket_refill_and_retry_after():
    from pathway_tpu.fabric.limits import TokenBucket, retry_after_header

    t = [0.0]
    b = TokenBucket(rate=2.0, burst=3, clock=lambda: t[0])
    assert [b.try_take() for _ in range(3)] == [0.0, 0.0, 0.0]  # burst
    wait = b.try_take()
    assert wait == pytest.approx(0.5)  # one token at 2/s
    assert retry_after_header(wait) == "1"  # rounded UP, never early
    t[0] += 0.5
    assert b.try_take() == 0.0
    assert b.try_take() == pytest.approx(0.5)
    t[0] += 100.0  # refill clamps at burst
    assert b.available() == pytest.approx(3.0)
    # default burst = ceil(rate)
    b2 = TokenBucket(rate=2.5, clock=lambda: t[0])
    assert b2.burst == 3


def test_api_key_guard_and_header_extraction():
    from pathway_tpu.fabric.limits import (
        FORBIDDEN,
        UNAUTHORIZED,
        ApiKeyGuard,
        extract_api_key,
    )

    g = ApiKeyGuard(("secret-1", "secret-2"))
    assert g.check(None) == UNAUTHORIZED
    assert g.check("") == UNAUTHORIZED
    assert g.check("wrong") == FORBIDDEN
    assert g.check("secret-2") is None
    assert ApiKeyGuard(()).check(None) is None  # auth off
    assert extract_api_key({"X-API-Key": "k"}) == "k"
    assert extract_api_key({"Authorization": "Bearer tok"}) == "tok"
    # X-API-Key wins over Authorization; Basic auth is not an API key
    assert extract_api_key({"X-API-Key": "a", "Authorization": "Bearer b"}) == "a"
    assert extract_api_key({"Authorization": "Basic xyz"}) is None
    assert extract_api_key({}) is None


def test_mint_request_key_is_pid_salted(monkeypatch):
    """Two processes' Nth requests must never mint the same engine key: the
    request id (and the derived trace id) IS the key."""
    from pathway_tpu.io.http import _server as S

    monkeypatch.delenv("PATHWAY_PROCESS_ID", raising=False)
    seq = S._KEY_SEQ
    # pin the sequence so both mints hash the same counter value
    S._KEY_SEQ = iter([7, 7])
    try:
        k0 = S.mint_request_key()
        monkeypatch.setenv("PATHWAY_PROCESS_ID", "2")
        k2 = S.mint_request_key()
    finally:
        S._KEY_SEQ = seq
    assert k0 != k2


def test_replica_store_apply_lag_and_snapshot():
    from pathway_tpu.fabric.replica import ReplicaStore

    store = ReplicaStore("/t", "name")
    assert store.lag_s() is None  # never synced: maximally stale
    store.apply([("a", {"name": "a", "v": 1}, 1), ("b", {"name": "b", "v": 2}, 1)], 1, 100.0)
    assert store.lookup("a") == {"name": "a", "v": 1} and len(store) == 2
    # upsert = retract + insert in emission order; delete removes
    store.apply(
        [("a", {"name": "a", "v": 1}, -1), ("a", {"name": "a", "v": 9}, 1), ("b", {"name": "b", "v": 2}, -1)],
        2,
        101.0,
    )
    assert store.lookup("a") == {"name": "a", "v": 9}
    assert store.lookup("b") is None
    assert store.seq == 2
    # frontier advances freshness without data
    store.frontier(2, 105.0)
    assert store.synced_unix == 105.0
    assert store.lag_s(now_unix=106.5) == pytest.approx(1.5)
    # snapshot overlapping already-applied deltas converges (last write wins)
    store.install_snapshot({"a": {"name": "a", "v": 9}, "c": {"name": "c", "v": 3}}, 3, 107.0)
    assert store.lookup("c") == {"name": "c", "v": 3} and store.seq == 3
    # an OLDER snapshot never rolls the store back
    store.install_snapshot({"zz": {}}, 1, 90.0)
    assert store.lookup("c") is not None and store.seq == 3
    store.is_owner = True
    assert store.lag_s() == 0.0


def test_fabric_transport_rpc_and_cast():
    from pathway_tpu.fabric.transport import FabricNode, FabricUnavailable

    first_port = free_port_base(7)
    n0 = FabricNode(0, 2, first_port)
    n1 = FabricNode(1, 2, first_port)
    got_casts: list = []
    try:
        n0.req_handlers["echo"] = lambda payload, reply: reply({"got": payload})

        def deferred(payload, reply):
            threading.Thread(target=lambda: reply(payload * 2), daemon=True).start()

        n0.req_handlers["deferred"] = deferred

        def boom(payload, reply):
            raise ValueError("kaboom")

        n0.req_handlers["boom"] = boom
        n1.cast_handlers["note"] = got_casts.append

        assert n1.call(0, "echo", {"x": 1}, timeout=10) == {"got": {"x": 1}}
        assert n1.call(0, "deferred", 21, timeout=10) == 42
        with pytest.raises(FabricUnavailable, match="kaboom"):
            n1.call(0, "boom", None, timeout=10)
        with pytest.raises(FabricUnavailable, match="no fabric handler"):
            n1.call(0, "nope", None, timeout=10)
        assert n0.cast(1, "note", {"seq": 1})
        deadline = time.monotonic() + 5
        while not got_casts and time.monotonic() < deadline:
            time.sleep(0.01)
        assert got_casts == [{"seq": 1}]
    finally:
        n0.close()
        n1.close()
    # a closed endpoint is unavailable, not a hang
    with pytest.raises(FabricUnavailable):
        n1.call(0, "echo", 1, timeout=0.5)


# ------------------------------------------- single-process door protection


def test_rate_limit_and_auth_exact_counters_under_mixed_flood():
    """One route with auth + a token bucket, flooded by a mix of authorized,
    key-less and wrong-key clients: every client-observed 401/403/429/200
    matches the route's exact counters, and admitted+rejected == sent."""
    import pathway_tpu as pw
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.io.http._server import serving_status

    G.clear()
    port = _free_port()
    queries, respond = pw.io.http.rest_connector(
        host="127.0.0.1",
        port=port,
        schema=pw.schema_from_types(query=str),
        rate_limit=5.0,
        api_keys=("good-key",),
    )
    respond(queries.select(result=pw.apply(lambda q: q.upper(), queries.query)))

    N = 40
    results: dict[str, list[int]] = {"auth": [], "nokey": [], "badkey": []}

    def client():
        _wait_ready(port)
        url = f"http://127.0.0.1:{port}/"
        for i in range(N):
            status, _b, _h = _post(url, {"query": f"q{i}"}, headers={"X-API-Key": "good-key"})
            results["auth"].append(status)
            status, _b, _h = _post(url, {"query": f"n{i}"})
            results["nokey"].append(status)
            status, _b, hdrs = _post(url, {"query": f"b{i}"}, headers={"Authorization": "Bearer wrong"})
            results["badkey"].append(status)
        rt = pw.internals.run.current_runtime()
        if rt is not None:
            rt.request_stop()

    th = threading.Thread(target=client)
    th.start()
    pw.run(monitoring_level="none", autocommit_duration_ms=20)
    th.join()

    assert set(results["nokey"]) == {401}
    assert set(results["badkey"]) == {403}
    ok = sum(1 for s in results["auth"] if s == 200)
    limited = sum(1 for s in results["auth"] if s == 429)
    assert ok + limited == N and ok > 0
    assert limited > 0, "the 5 req/s bucket never engaged — flood too slow?"

    serving = serving_status(pw.internals.run.current_runtime())
    route = serving["routes"][0]
    assert route["unauthorized_total"] == N
    assert route["forbidden_total"] == N
    assert route["limited_total"] == limited
    assert route["responses_total"] == ok
    assert route["requests_total"] == 3 * N
    assert route["rate_limit"] == 5.0 and route["auth"] is True


def test_rate_limited_response_carries_retry_after():
    from pathway_tpu.fabric.limits import TokenBucket
    from pathway_tpu.io.http import _server as S

    state = S._RouteServing("/r", ("POST",), None)
    state.limiter = TokenBucket(rate=1.0, burst=1)
    assert S.gate_check(state, {}) is None  # burst token
    status, body, hdrs = S.gate_check(state, {})
    assert status == 429 and body["error"] == "rate limited"
    assert int(hdrs["Retry-After"]) >= 1
    assert state.limited_total == 1


def test_serve_table_single_process_lookup_and_schema():
    import pathway_tpu as pw
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    port = _free_port()
    prices = pw.debug.table_from_rows(
        pw.schema_from_types(name=str, price=int), [("apple", 3), ("pear", 5)]
    )
    troute = pw.io.http.serve_table(
        prices, route="/v1/prices", key_column="name", host="127.0.0.1", port=port
    )
    out: dict = {}

    def client():
        _wait_ready(port)
        time.sleep(0.4)  # one tick: the static table lands in the store
        out["hit"] = _get(f"http://127.0.0.1:{port}/v1/prices?name=pear")
        out["miss"] = _get(f"http://127.0.0.1:{port}/v1/prices?name=zzz")
        out["noparam"] = _get(f"http://127.0.0.1:{port}/v1/prices")
        out["schema"] = _get(f"http://127.0.0.1:{port}/_schema")
        rt = pw.internals.run.current_runtime()
        if rt is not None:
            rt.request_stop()

    th = threading.Thread(target=client)
    th.start()
    pw.run(monitoring_level="none")
    th.join()

    status, body, hdrs = out["hit"]
    assert status == 200 and json.loads(body) == {"name": "pear", "price": 5}
    assert hdrs["X-Pathway-Fabric"] == "owner"
    assert float(hdrs["X-Pathway-Replica-Lag-Ms"]) == 0.0  # authoritative
    status, body, _ = out["miss"]
    assert status == 404 and json.loads(body)["error"] == "unknown key"
    assert out["noparam"][0] == 400
    spec = json.loads(out["schema"][1])
    assert "/v1/prices" in spec["paths"]
    assert "name" in [p["name"] for p in spec["paths"]["/v1/prices"]["get"]["parameters"]]
    assert troute.store.is_owner and len(troute.store) == 2
    assert troute.local_answers == 3  # hit + miss + (400 short-circuits first)


# ----------------------------------------------------- 3-process byte identity

_RETRIEVE_SCRIPT = textwrap.dedent(
    """
    import json, os, socket, sys, threading, time, urllib.request
    import pathway_tpu as pw
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
    from pathway_tpu.xpacks.llm.rerankers import EncoderReranker

    port = int(sys.argv[1])

    emb = SentenceTransformerEmbedder("tiny", seed=0)
    rr = EncoderReranker(emb)
    docs = [f"alpha beta doc{i} gamma delta" for i in range(24)]
    doc_t = pw.debug.table_from_rows(
        pw.schema_from_types(text=str), [(d,) for d in docs]
    )
    index = BruteForceKnnFactory(embedder=emb, reserved_space=64).build_index(
        doc_t.text, doc_t
    )
    ws = pw.io.http.PathwayWebserver(host="127.0.0.1", port=port)
    queries, respond = pw.io.http.rest_connector(
        webserver=ws, route="/v1/retrieve", schema=pw.schema_from_types(query=str)
    )
    picked = index.query_as_of_now(queries.query, number_of_matches=2).select(
        q=pw.left.query,
        top=pw.apply(lambda ts: ts[0] if ts else "", pw.right.text),
    )
    scored = picked.select(picked.top, score=rr(picked.top, picked.q))
    reply = scored.select(
        result=pw.apply(
            lambda t, s: {"top": t, "score": round(float(s), 6)},
            scored.top,
            scored.score,
        )
    )
    respond(reply)

    pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
    n_proc = int(os.environ.get("PATHWAY_PROCESSES", "1"))
    stride = int(os.environ.get("PATHWAY_FABRIC_PORT_STRIDE", "1"))
    fabric_on = os.environ.get("PATHWAY_FABRIC") == "on"
    mon_base = int(os.environ.get("PATHWAY_MONITORING_HTTP_PORT", "0"))

    def wait_ready(p, timeout=60.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                socket.create_connection(("127.0.0.1", p), timeout=0.5).close()
                return
            except OSError:
                time.sleep(0.05)
        raise TimeoutError(p)

    if pid == 0:
        def client():
            doors = [port + i * stride for i in range(n_proc)] if fabric_on else [port]
            for p in doors:
                wait_ready(p)
            time.sleep(1.0)
            out = {"answers": {}, "rids": {}}
            qs = ["alpha beta doc3 gamma", "doc7 delta", "gamma doc11 alpha"]
            for p in doors:
                bodies, rids = [], []
                for q in qs:
                    req = urllib.request.Request(
                        f"http://127.0.0.1:{p}/v1/retrieve",
                        data=json.dumps({"query": q}).encode(),
                        headers={"Content-Type": "application/json"},
                    )
                    r = urllib.request.urlopen(req, timeout=90)
                    bodies.append(r.read().decode())
                    rids.append(r.headers.get("X-Pathway-Request-Id"))
                out["answers"][str(p)] = bodies
                out["rids"][str(p)] = rids
            out["schemas"] = [
                urllib.request.urlopen(
                    f"http://127.0.0.1:{p}/_schema", timeout=30
                ).read().decode()
                for p in doors
            ]
            if fabric_on and mon_base:
                # the last door is a PEER: its kept trace (ingress spans) and
                # the coordinator's (owner spans) must share one trace id
                rid = out["rids"][str(doors[-1])][0]
                peer_mon = mon_base + (n_proc - 1)
                out["peer_trace"] = json.loads(urllib.request.urlopen(
                    f"http://127.0.0.1:{peer_mon}/request?id={rid}", timeout=30
                ).read())
                out["owner_trace"] = json.loads(urllib.request.urlopen(
                    f"http://127.0.0.1:{mon_base}/request?id={rid}", timeout=30
                ).read())
                time.sleep(1.6)  # two heartbeat intervals: serving rollup lands
                out["status"] = json.loads(urllib.request.urlopen(
                    f"http://127.0.0.1:{mon_base}/status", timeout=30
                ).read())
            print("RESULT:" + json.dumps(out), flush=True)
            rt = pw.internals.run.current_runtime()
            if rt is not None:
                rt.request_stop()

        threading.Thread(target=client, daemon=True).start()

    pw.run(monitoring_level="none", with_http_server=bool(mon_base))
    print("DONE", flush=True)
    """
)


def _run_cluster(script_path, http_port, n_proc, extra_env, timeout=180, first_port=None):
    env = dict(os.environ)
    env.update(
        PATHWAY_PROCESSES=str(n_proc),
        PATHWAY_THREADS="1",
        PATHWAY_BARRIER_TIMEOUT="60",
        PATHWAY_FIRST_PORT=str(
            first_port if first_port is not None else free_port_base(2 * n_proc + 2)
        ),
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO,
    )
    env.update(extra_env)
    procs = [
        subprocess.Popen(
            [sys.executable, str(script_path), str(http_port)],
            env=dict(env, PATHWAY_PROCESS_ID=str(pid)),
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for pid in range(n_proc)
    ]
    outputs = []
    for p in procs:
        try:
            stdout, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            texts = []
            for q in procs:
                q.kill()
                out, _ = q.communicate()
                texts.append(out or "")
            raise AssertionError(
                "cluster process hung; output:\\n" + "\\n---\\n".join(texts)
            )
        outputs.append(stdout)
    for p, txt in zip(procs, outputs):
        assert p.returncode == 0, f"process exited {p.returncode}:\n{txt}"
    result = None
    for line in outputs[0].splitlines():
        if line.startswith("RESULT:"):
            result = json.loads(line[len("RESULT:") :])
    assert result is not None, outputs[0]
    return result


def test_fabric_three_process_byte_identity_and_trace_stitch(tmp_path):
    """The acceptance surface: a 3-process embed→KNN→rerank cluster with the
    fabric on answers byte-identically from all three doors AND matches a
    single-process run of the same pipeline; /_schema is served from every
    door; one forwarded request's kept r16 traces stitch ingress-process and
    owner-process spans under one derived trace id; the coordinator's
    serving rollup counts every door's traffic."""
    script = tmp_path / "retrieve.py"
    script.write_text(_RETRIEVE_SCRIPT)
    # one contiguous block: monitoring ports first, cluster bands after —
    # two independent scans would find the SAME free range and collide
    block = free_port_base(4 + 9)
    mon_base = block
    http_port = _free_port()
    fabric = _run_cluster(
        script,
        http_port,
        3,
        {
            "PATHWAY_FABRIC": "on",
            "PATHWAY_REQUEST_TRACE_KEEP": "1.0",  # keep every trace: both sides
            "PATHWAY_MONITORING_HTTP_PORT": str(mon_base),
        },
        first_port=block + 4,
    )
    single = _run_cluster(
        script, _free_port(), 1, {"PATHWAY_FABRIC": "off", "PATHWAY_MONITORING_HTTP_PORT": "0"}
    )

    # byte identity: every fabric door agrees, and agrees with single-process
    doors = sorted(fabric["answers"], key=int)
    assert len(doors) == 3
    reference = single["answers"][str(list(single["answers"])[0])]
    for door in doors:
        assert fabric["answers"][door] == reference, (
            f"door {door} diverged from the single-process answers"
        )
    # every door serves the same OpenAPI document
    assert len(set(fabric["schemas"])) == 1
    # request ids are unique pod-wide (pid-salted mint)
    all_rids = [r for rids in fabric["rids"].values() for r in rids]
    assert len(set(all_rids)) == len(all_rids)

    # trace stitch: peer ingress + coordinator owner, one trace id
    peer_doc, owner_doc = fabric["peer_trace"], fabric["owner_trace"]
    assert peer_doc["ok"] and peer_doc["kept"], peer_doc
    assert owner_doc["ok"] and owner_doc["kept"], owner_doc
    assert peer_doc["trace_id"] == owner_doc["trace_id"]
    peer_stages = [s["name"] for s in peer_doc["spans"]]
    assert "fabric/forward" in peer_stages and "serve/admission" in peer_stages
    assert "serve/respond" in [s["name"] for s in owner_doc["spans"]]
    # the owner side decomposed real engine stages of the flight
    assert any(k.startswith("sweep/") for k in owner_doc["decomposition_ms"])

    # pod-wide serving rollup: the coordinator's cluster block counts all
    # nine requests (3 doors x 3 queries), exactly
    cluster = fabric["status"]["serving"]["cluster"]
    assert cluster["n_reporting"] == 3
    route = cluster["routes"]["/v1/retrieve"]
    assert route["requests"] == 9
    assert route["responses"] == 9
    assert route["forwarded_out"] == 6  # two peer doors x 3 queries
    assert route["forwarded_in"] == 6  # all arrived at the owner
    # the fabric section names this process's doors
    assert fabric["status"]["fabric"]["enabled"] is True


# ------------------------------------------------ 2-process replica staleness

_REPLICA_SCRIPT = textwrap.dedent(
    """
    import json, os, socket, sys, threading, time, urllib.request, urllib.error
    import pathway_tpu as pw
    from pathway_tpu.io.python import ConnectorSubject

    port = int(sys.argv[1])
    KEYS = 8

    class Churn(ConnectorSubject):
        def __init__(self):
            super().__init__()
            self._stop = False
        def run(self):
            i = 0
            while not self._stop and i < 400:
                self.next_batch([{"name": f"k{i % KEYS}", "price": i}])
                i += 1
                time.sleep(0.005)
        def on_stop(self):
            self._stop = True

    feed = pw.io.python.read(
        Churn(), schema=pw.schema_from_types(name=str, price=int), name="churn"
    )
    latest = feed.groupby(feed.name).reduce(
        name=feed.name, price=pw.reducers.max(feed.price)
    )
    ws = pw.io.http.PathwayWebserver(host="127.0.0.1", port=port)
    pw.io.http.serve_table(latest, route="/v1/latest", key_column="name", webserver=ws)

    pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
    stride = int(os.environ.get("PATHWAY_FABRIC_PORT_STRIDE", "1"))
    bound_ms = float(os.environ.get("PATHWAY_FABRIC_MAX_STALENESS_MS", "2000"))
    mon_base = int(os.environ.get("PATHWAY_MONITORING_HTTP_PORT", "0"))

    def wait_ready(p, timeout=60.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                socket.create_connection(("127.0.0.1", p), timeout=0.5).close()
                return
            except OSError:
                time.sleep(0.05)
        raise TimeoutError(p)

    def get(url):
        try:
            r = urllib.request.urlopen(url, timeout=30)
            return r.status, r.read().decode(), dict(r.headers)
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode(), dict(e.headers)

    if pid == 0:
        def client():
            owner, peer = port, port + stride
            wait_ready(owner); wait_ready(peer)
            time.sleep(1.0)
            out = {"during": [], "settled": [], "lags": []}
            # mid-churn: the peer must answer locally within the bound
            for i in range(30):
                status, body, hdrs = get(f"http://127.0.0.1:{peer}/v1/latest?name=k{i % KEYS}")
                src = hdrs.get("X-Pathway-Fabric", "")
                lag = hdrs.get("X-Pathway-Replica-Lag-Ms")
                out["during"].append([status, src])
                if lag is not None:
                    out["lags"].append(float(lag))
                time.sleep(0.02)
            time.sleep(3.0)  # churn ends (400 rows); both stores settle
            for k in range(KEYS):
                so, bo, _ = get(f"http://127.0.0.1:{owner}/v1/latest?name=k{k}")
                sp, bp, hp = get(f"http://127.0.0.1:{peer}/v1/latest?name=k{k}")
                out["settled"].append([so, bo, sp, bp, hp.get("X-Pathway-Fabric")])
            out["peer_metrics"] = urllib.request.urlopen(
                f"http://127.0.0.1:{mon_base + 1}/metrics", timeout=30
            ).read().decode()
            out["peer_status"] = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{mon_base + 1}/status", timeout=30
            ).read())
            print("RESULT:" + json.dumps(out), flush=True)
            rt = pw.internals.run.current_runtime()
            if rt is not None:
                rt.request_stop()

        threading.Thread(target=client, daemon=True).start()

    pw.run(monitoring_level="none", with_http_server=bool(mon_base), autocommit_duration_ms=20)
    print("DONE", flush=True)
    """
)


def test_fabric_replica_staleness_bound_under_churn(tmp_path):
    """A churning served table on a 2-process fabric: the peer's replica
    answers locally with measured lag within the configured bound, settles
    byte-identical to the owner once churn ends, and exposes
    pathway_fabric_replica_lag_seconds on its own /metrics."""
    script = tmp_path / "replica.py"
    script.write_text(_REPLICA_SCRIPT)
    block = free_port_base(3 + 7)  # monitoring ports + cluster bands, disjoint
    mon_base = block
    result = _run_cluster(
        script,
        _free_port(),
        2,
        {
            "PATHWAY_FABRIC": "on",
            "PATHWAY_FABRIC_MAX_STALENESS_MS": "2000",
            "PATHWAY_MONITORING_HTTP_PORT": str(mon_base),
        },
        first_port=block + 3,
    )
    # mid-churn answers come from the local replica (or an honest fallback —
    # never a silent stale answer); at least most must be local
    srcs = [src for _s, src in result["during"]]
    local = sum(1 for s in srcs if s.startswith("replica:"))
    assert local >= len(srcs) * 0.8, srcs
    assert result["lags"], "no measured lag was reported"
    assert max(result["lags"]) <= 2000.0, result["lags"]
    # settled: every key byte-identical owner vs peer, answered locally
    for so, bo, sp, bp, src in result["settled"]:
        assert so == sp == 200
        assert bo == bp
        assert src.startswith("replica:")
    assert "pathway_fabric_replica_lag_seconds" in result["peer_metrics"]
    rep = result["peer_status"]["fabric"]["replica"]["/v1/latest"]
    assert rep["rows"] == 8 and rep["is_owner"] is False
    assert rep["local_answers"] >= local


# ------------------------------------------------------- SIGKILL + Supervisor

_SUPERVISED_SCRIPT = textwrap.dedent(
    """
    import json, os, sys, threading, time
    import pathway_tpu as pw

    port = int(sys.argv[1])
    stop_file = sys.argv[2]
    pid_dir = sys.argv[3]
    me = os.environ.get("PATHWAY_PROCESS_ID", "0")
    with open(os.path.join(pid_dir, f"pid.{me}"), "w") as fh:
        fh.write(str(os.getpid()))

    queries, respond = pw.io.http.rest_connector(
        host="127.0.0.1", port=port, schema=pw.schema_from_types(query=str)
    )
    respond(queries.select(result=pw.apply(lambda q: q.upper(), queries.query)))

    def watch_stop():
        while not os.path.exists(stop_file):
            time.sleep(0.1)
        rt = pw.internals.run.current_runtime()
        if rt is not None:
            rt.request_stop()

    threading.Thread(target=watch_stop, daemon=True).start()
    pw.run(monitoring_level="none")
    """
)


@pytest.mark.slow
def test_fabric_front_door_sigkill_supervisor_reforms(tmp_path):
    """SIGKILL the PEER front-door process mid-serve: the Supervisor
    relaunches the cluster, the fabric re-forms, and the peer door serves
    again — the fabric survives the failure mode it exists for."""
    from pathway_tpu.resilience.supervisor import Supervisor

    script = tmp_path / "sup_serve.py"
    script.write_text(_SUPERVISED_SCRIPT)
    stop_file = tmp_path / "stop"
    http_port = _free_port()
    first_port = free_port_base(6)
    env = dict(os.environ)
    env.update(
        PATHWAY_FABRIC="on",
        PATHWAY_BARRIER_TIMEOUT="45",
        PATHWAY_HEARTBEAT_INTERVAL="0.2",
        PATHWAY_HEARTBEAT_TIMEOUT="3",
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO,
    )
    peer_port = http_port + 1
    phases: dict = {}

    def drive():
        try:
            _wait_ready(peer_port, timeout=60)
            status, body, hdrs = _post(
                f"http://127.0.0.1:{peer_port}/", {"query": "before"}, timeout=60
            )
            phases["before"] = (status, body, hdrs.get("X-Pathway-Fabric"))
            # SIGKILL the peer (the process serving the door we just used)
            import signal

            peer_os_pid = int((tmp_path / "pid.1").read_text())
            os.kill(peer_os_pid, signal.SIGKILL)
            # the supervisor tears down and relaunches; the door comes back
            time.sleep(1.0)
            _wait_ready(peer_port, timeout=90)
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                status, body, hdrs = _post(
                    f"http://127.0.0.1:{peer_port}/", {"query": "after"}, timeout=60
                )
                if status == 200:
                    break
                time.sleep(0.5)
            phases["after"] = (status, body, hdrs.get("X-Pathway-Fabric"))
        finally:
            stop_file.write_text("stop")

    sup = Supervisor(
        [sys.executable, str(script), str(http_port), str(stop_file), str(tmp_path)],
        processes=2,
        threads=1,
        first_port=first_port,
        max_restarts=2,
        backoff_s=0.2,
        env=env,
        log_dir=str(tmp_path / "logs"),
    )
    th = threading.Thread(target=drive)
    th.start()
    result = sup.run()
    th.join()
    assert phases["before"][0] == 200 and phases["before"][1] == '"BEFORE"'
    assert phases["before"][2] == "forwarded:p0"
    assert phases["after"][0] == 200 and phases["after"][1] == '"AFTER"'
    assert phases["after"][2] == "forwarded:p0"
    assert result.restarts >= 1
