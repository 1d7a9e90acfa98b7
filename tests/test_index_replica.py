"""Replica-served retrieval (r20): KNN answered at every front door.

Covers the index-replica plane end to end: the :class:`ReplicaIndex`
changelog/gap/resync/lag semantics, the :class:`IndexRoute` outbox's
sequence discipline, ``local_retrieve_response``'s exact reproduction of the
owner's reply bytes (shape, order, filter-error semantics, fallback
sentinels), the recall@10 >= 0.95 gate for a lagging replica, the pod-wide
query-embedding memo share (hit/evict counters, no echo loops), the
heartbeat ride-along with the retired-peer drop, a 3-process DocumentStore
cluster whose ``/v1/retrieve`` answers byte-identically from every door
once churn settles (with ``pathway_replica_*`` metrics and the /status
fabric.index + coordinator rollup), and (slow) SIGKILL of a replica door
under a Supervisor — snapshot resync brings it back to serving locally.
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

import numpy as np
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


def _post(url: str, payload: dict, timeout: float = 60.0):
    req = urllib.request.Request(
        url,
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        r = urllib.request.urlopen(req, timeout=timeout)
        return r.status, r.read().decode(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(), dict(e.headers)


def _vec_backend():
    from pathway_tpu.stdlib.indexing._engine import VectorBackend

    return VectorBackend(dimension=16)


def _embed(texts: list[str]) -> list[np.ndarray]:
    from pathway_tpu.xpacks.llm.mocks import FakeEmbedder

    return FakeEmbedder(dimension=16).func(list(texts))


# ------------------------------------------------------- ReplicaIndex units


def test_replica_index_apply_search_and_last_write_wins():
    from pathway_tpu.fabric import ReplicaIndex

    rep = ReplicaIndex(_vec_backend)
    vecs = _embed([f"doc {i}" for i in range(6)])
    ops = [("a", i, vecs[i], {"i": i}, f"doc {i}") for i in range(4)]
    rep.apply_ops(1, ops, seq=1, ts_unix=100.0)
    rep.apply_ops(2, [("a", i, vecs[i], {"i": i}, f"doc {i}") for i in (4, 5)],
                  seq=1, ts_unix=100.0)
    assert len(rep) == 6 and rep.applied_total == 6
    hits = rep.search_one(vecs[3], 2, lambda md: True)
    assert hits and hits[0][0] == 3 and hits[0][1] == pytest.approx(1.0)
    assert hits[0][2][2] == "doc 3"  # payload text joined back
    # last write wins: re-adding a live key replaces it (snapshot overlap)
    rep.apply_ops(1, [("a", 3, vecs[0], {"i": 30}, "doc 3 v2")], seq=2, ts_unix=101.0)
    assert len(rep) == 6
    assert rep.rows[3][2] == "doc 3 v2"
    # removal drops the row from backend and shadow alike
    rep.apply_ops(1, [("r", 3)], seq=3, ts_unix=102.0)
    assert len(rep) == 5 and 3 not in rep.rows
    assert rep.search_one(vecs[3], 6, lambda md: True)
    assert all(k != 3 for k, _s, _r in rep.search_one(vecs[3], 6, lambda md: True))


def test_replica_index_gap_reset_frontier_and_lag():
    from pathway_tpu.fabric import ReplicaIndex

    rep = ReplicaIndex(_vec_backend)
    rep.self_src = 0
    now = time.time()
    # never synced: remote slices unknown -> maximally stale
    assert rep.lag_from(0) == 0.0  # self slice is always fresh
    assert rep.lag_from(1) is None
    assert rep.remote_lag_s(3) is None
    vec = _embed(["x"])[0]
    rep.apply_ops(1, [("a", 1, vec, None, "x")], seq=1, ts_unix=now)
    # a cast whose prev_seq jumps past our held position is a gap; one that
    # connects (prev <= held seq) is not
    assert rep.src_gap(1, 5)
    assert not rep.src_gap(1, 1)
    assert not rep.src_gap(1, 0)
    # frontier stamps advance freshness without data
    rep.frontier_from(2, 0, now)
    assert rep.lag_from(2) is not None
    lag = rep.remote_lag_s(3)
    assert lag is not None and lag < 10.0
    # a restarted source resets its epoch
    rep.reset_src(1)
    assert rep.src_seq[1] == 0
    # poisoning makes the slice read as never-synced until a snapshot lands
    rep.poison(1)
    assert rep.lag_from(1) is None
    assert rep.remote_lag_s(3) is None
    rep.install_slice(1, {1: (vec, None, "x")}, seq=0, ts_unix=time.time())
    assert rep.lag_from(1) is not None
    assert rep.resyncs_total == 0  # the counter belongs to the plane's pull


def test_replica_index_self_slice_and_install_slice():
    from pathway_tpu.fabric import ReplicaIndex

    rep = ReplicaIndex(_vec_backend)
    rep.self_src = 0
    vecs = _embed(["a", "b", "c"])
    rep.apply_ops(0, [("a", 1, vecs[0], None, "a")], seq=None, ts_unix=1.0)
    rep.apply_ops(1, [("a", 2, vecs[1], None, "b")], seq=1, ts_unix=1.0)
    rows, _seq, _ts = rep.self_slice()
    assert set(rows) == {1}  # only the authoritative slice, never peers'
    # install: rows the snapshot no longer carries are dropped for that src
    rep.install_slice(1, {3: (vecs[2], None, "c")}, seq=4, ts_unix=2.0)
    assert set(rep.rows) == {1, 3}
    assert rep.src_seq[1] == 4
    # sequence regressions are accepted (restarted source, fresh snapshot)
    rep.install_slice(1, {3: (vecs[2], None, "c")}, seq=1, ts_unix=3.0)
    assert rep.src_seq[1] == 1


def test_index_route_outbox_sequence_discipline():
    """The changelog sequence advances ONLY on non-empty drains, so idle
    frontier stamps can never read as missed data casts downstream."""
    import types

    from pathway_tpu.fabric.index_replica import IndexRoute

    ir = IndexRoute("/v1/retrieve", None, 0)
    ir.bind(types.SimpleNamespace(backend_factory=_vec_backend))
    assert ir.replica is not None
    vec = _embed(["d"])[0]
    assert not ir.outbox_pending()
    ops, prev, seq = ir.drain_ops()
    assert (ops, prev, seq) == ([], 0, 0)  # idle: seq stays put
    ir.note_ops([("a", 7, vec, None, "d")])
    assert ir.outbox_pending()
    assert len(ir.replica) == 1  # self slice applies immediately (zero lag)
    ops, prev, seq = ir.drain_ops()
    assert len(ops) == 1 and (prev, seq) == (0, 1)
    ops, prev, seq = ir.drain_ops()
    assert (ops, prev, seq) == ([], 1, 1)
    # a second InnerIndex binding marks the route composite (always forward)
    ir.bind(types.SimpleNamespace(backend_factory=_vec_backend))
    assert ir.composite


# ------------------------------------------------ local answer byte contract


def _armed_route(texts: list[str], embedder=None):
    import types

    from pathway_tpu.fabric.index_replica import IndexRoute
    from pathway_tpu.xpacks.llm.mocks import FakeEmbedder

    ir = IndexRoute("/v1/retrieve", embedder or FakeEmbedder(dimension=16), 0)
    ir.bind(types.SimpleNamespace(backend_factory=_vec_backend))
    vecs = _embed(texts)
    ir.note_ops(
        [
            ("a", i, vecs[i], {"path": f"/d/{i}.md", "i": i}, texts[i])
            for i in range(len(texts))
        ]
    )
    return ir


def test_local_retrieve_response_shape_order_and_filters():
    from pathway_tpu.fabric.index_replica import local_retrieve_response

    texts = [f"doc number {i} alpha beta" for i in range(8)]
    ir = _armed_route(texts)
    res = local_retrieve_response(
        ir, {"query": texts[5], "k": 3, "metadata_filter": None,
             "filepath_globpattern": None}
    )
    assert res is not None
    body, spans = res
    out = json.loads(body)
    assert len(out) == 3
    assert out[0]["text"] == texts[5]
    assert out[0]["dist"] == pytest.approx(-1.0)
    assert [d["dist"] for d in out] == sorted(d["dist"] for d in out)
    assert out[0]["metadata"] == {"path": "/d/5.md", "i": 5}
    assert [s[0] for s in spans] == ["replica/embed", "replica/search"]
    assert spans[1][3] == {"rows": 3}
    # metadata filter + glob merge through the SAME combine_filters bytes
    res = local_retrieve_response(
        ir, {"query": texts[5], "k": 8, "metadata_filter": "i == 2",
             "filepath_globpattern": None}
    )
    out = json.loads(res[0])
    assert [d["text"] for d in out] == [texts[2]]
    res = local_retrieve_response(
        ir, {"query": texts[5], "k": 8, "metadata_filter": None,
             "filepath_globpattern": "/d/3.*"}
    )
    assert [d["text"] for d in json.loads(res[0])] == [texts[3]]
    # malformed filter reproduces the engine node's error semantics: the
    # EMPTY reply, never an exception and never a forward
    res = local_retrieve_response(
        ir, {"query": texts[5], "k": 3, "metadata_filter": "((",
             "filepath_globpattern": None}
    )
    assert res is not None and json.loads(res[0]) == []


def test_local_retrieve_response_fallback_sentinels():
    """Requests the replica cannot answer exactly return None — the door
    forwards instead of guessing."""
    import types

    from pathway_tpu.fabric.index_replica import (
        IndexRoute,
        local_retrieve_response,
    )

    texts = ["alpha", "beta"]
    ir = _armed_route(texts)
    # missing/bad query or k: the owner path owns the error behavior
    assert local_retrieve_response(ir, {"k": 3}) is None
    assert local_retrieve_response(ir, {"query": "alpha"}) is None
    assert local_retrieve_response(ir, {"query": "alpha", "k": "NaN"}) is None
    # an async embedder can't be reproduced on the door thread
    async def aembed(texts):
        return _embed(texts)

    ir_async = IndexRoute("/v1/retrieve", types.SimpleNamespace(func=aembed), 0)
    ir_async.bind(types.SimpleNamespace(backend_factory=_vec_backend))
    vec = _embed(["alpha"])[0]
    ir_async.note_ops([("a", 0, vec, None, "alpha")])
    assert local_retrieve_response(ir_async, {"query": "alpha", "k": 1}) is None
    # a hit whose payload text was never cast (restored source's slice)
    ir2 = _armed_route(["gamma"])
    ir2.replica.rows[0] = (ir2.replica.rows[0][0], None, None, 0)
    assert local_retrieve_response(ir2, {"query": "gamma", "k": 1}) is None
    # composite routes always forward
    ir3 = _armed_route(["delta"])
    ir3.composite = True
    assert local_retrieve_response(ir3, {"query": "delta", "k": 1}) is None


def test_lagging_replica_recall_at_10_gate():
    """The approximate-regime acceptance gate: a replica missing the tail of
    the changelog (lagging slices) still answers with recall@10 >= 0.95
    against the fully-caught-up index."""
    from pathway_tpu.fabric import ReplicaIndex

    n, missing, k = 160, 4, 10
    texts = [f"corpus doc {i} " + " ".join(f"w{(i * 7 + j) % 53}" for j in range(6))
             for i in range(n)]
    vecs = _embed(texts)
    full = ReplicaIndex(_vec_backend)
    full.apply_ops(0, [("a", i, vecs[i], None, texts[i]) for i in range(n)],
                   seq=1, ts_unix=1.0)
    lagging = ReplicaIndex(_vec_backend)
    lagging.apply_ops(
        0, [("a", i, vecs[i], None, texts[i]) for i in range(n - missing)],
        seq=1, ts_unix=1.0,
    )
    queries = _embed([f"query {q} w{q % 53} w{(q * 3) % 53}" for q in range(25)])
    recalls = []
    for qv in queries:
        want = {key for key, _s, _r in full.search_one(qv, k, lambda md: True)}
        got = {key for key, _s, _r in lagging.search_one(qv, k, lambda md: True)}
        recalls.append(len(want & got) / k)
    assert sum(recalls) / len(recalls) >= 0.95, recalls


# ------------------------------------------------------ memo share (pod tier)


def test_memo_share_drain_apply_counters_and_no_echo():
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    a = SentenceTransformerEmbedder("tiny", seed=123, memoize=8)
    b = SentenceTransformerEmbedder("tiny", seed=123, memoize=8)
    assert a.memo_fingerprint == b.memo_fingerprint
    texts = [f"shared query {i}" for i in range(3)]
    want = a.func(list(texts))
    entries = a.drain_shared_out()
    assert a.memo_shared_out == 3
    assert sorted(t for t, _v in entries) == sorted(texts)
    assert a.drain_shared_out() == []  # drained once, gone
    n = b.apply_shared(entries)
    assert n == 3 and b.memo_shared_in == 3
    got = b.func(list(texts))
    assert all(np.array_equal(w, g) for w, g in zip(want, got))
    assert b.memo_hits == 3 and b.memo_misses == 0  # all served by the share
    # no echo: peer-applied entries never re-enter b's share buffer
    assert b.drain_shared_out() == []
    # local entries win over a late peer copy of the same text
    local = b.func(["only mine"])
    b.apply_shared([("only mine", [0.0] * len(local[0]))])
    assert np.array_equal(b.func(["only mine"])[0], local[0])
    # eviction counter moves when the LRU bound trims
    a.func([f"churn {i} text" for i in range(12)])
    assert a.memo_evictions > 0 and len(a._memo) <= 8


def test_memo_module_api_stats_and_prometheus_lines():
    from pathway_tpu.xpacks.llm import embedders as emb_mod

    a = emb_mod.SentenceTransformerEmbedder("tiny", seed=321, memoize=16)
    b = emb_mod.SentenceTransformerEmbedder("tiny", seed=321, memoize=16)
    a.func(["module share alpha", "module share beta"])
    shared = emb_mod.drain_shared_memo()
    assert a.memo_fingerprint in shared
    ours = shared[a.memo_fingerprint]
    assert {t for t, _v in ours} >= {"module share alpha", "module share beta"}
    n = emb_mod.apply_shared_memo(a.memo_fingerprint, ours)
    assert n >= 2  # installed into b (a holds them locally already)
    assert b.memo_hits == 0
    b.func(["module share alpha"])
    assert b.memo_hits == 1 and b.memo_misses == 0
    stats = {s["fingerprint"]: s for s in emb_mod.memo_stats()}
    st = stats[b.memo_fingerprint]
    for key in ("capacity", "entries", "hits", "misses", "evictions",
                "shared_in", "shared_out", "hit_ratio"):
        assert key in st
    lines = emb_mod.memo_prometheus_lines()
    text = "\n".join(lines)
    for series in (
        "pathway_embedder_memo_hits_total",
        "pathway_embedder_memo_misses_total",
        "pathway_embedder_memo_evictions_total",
        "pathway_embedder_memo_shared_in_total",
        "pathway_embedder_memo_shared_out_total",
        "pathway_embedder_memo_entries",
        "pathway_embedder_memo_hit_ratio",
    ):
        assert f"# TYPE {series}" in text, series
        assert f"{series}{{embedder=" in text, series


# ------------------------------------------------- heartbeat ride-along


def test_heartbeat_peer_replica_index_and_retired_drop():
    """Replica health rides the existing heartbeat telemetry; a retired
    (drained) peer's stale lag disappears from the rollup instead of
    alarming forever."""
    from pathway_tpu.resilience.heartbeat import HeartbeatClient, HeartbeatMonitor

    monitor = HeartbeatMonitor(n_proc=2, port=0, timeout=30.0)
    block = {
        "/v1/retrieve": {"rows": 42, "lag_s": 0.5, "local": 7, "fallbacks": 1,
                         "gaps": 0, "resyncs": 0}
    }
    client = HeartbeatClient(pid=1, port=monitor.port, interval=0.05)
    client.summary_fn = lambda: {"replica_index": block}
    try:
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if monitor.peer_replica_index():
                break
            time.sleep(0.05)
        got = monitor.peer_replica_index()
        assert got == {1: block}
        # peers without the block simply don't appear
        assert 0 not in got
        monitor.retire_peer(1)
        assert monitor.peer_replica_index() == {}
        assert monitor.dead_peer() is None  # retirement is not death
    finally:
        client.goodbye()
        monitor.close()


# --------------------------------------- 3-process byte identity under churn

_RETRIEVE_CLUSTER_SCRIPT = textwrap.dedent(
    """
    import json, os, socket, sys, threading, time, urllib.request, urllib.error
    import pathway_tpu as pw
    from pathway_tpu.io.python import ConnectorSubject
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm import DocumentStore
    from pathway_tpu.xpacks.llm.mocks import FakeEmbedder
    from pathway_tpu.xpacks.llm.servers import DocumentStoreServer

    port = int(sys.argv[1])
    BASE, CHURN = 12, 48

    base = pw.debug.table_from_rows(
        pw.schema_from_types(data=str),
        [(f"seed doc {i:02d} topic{i % 5} alpha beta",) for i in range(BASE)],
    )

    class Churn(ConnectorSubject):
        def __init__(self):
            super().__init__()
            self._stop = False
        def run(self):
            for i in range(CHURN):
                if self._stop:
                    return
                self.next_batch([
                    {"data": f"churn doc {i:02d} topic{i % 5} gamma delta"}
                ])
                time.sleep(0.02)
        def on_stop(self):
            self._stop = True

    feed = pw.io.python.read(
        Churn(), schema=pw.schema_from_types(data=str), name="churn_docs"
    )
    store = DocumentStore(
        base.concat_reindex(feed),
        retriever_factory=BruteForceKnnFactory(embedder=FakeEmbedder(dimension=16)),
    )
    DocumentStoreServer("127.0.0.1", port, store)

    pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
    n_proc = int(os.environ.get("PATHWAY_PROCESSES", "1"))
    stride = int(os.environ.get("PATHWAY_FABRIC_PORT_STRIDE", "1"))
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

    def retrieve(p, q, k=3):
        req = urllib.request.Request(
            f"http://127.0.0.1:{p}/v1/retrieve",
            data=json.dumps({"query": q, "k": k}).encode(),
            headers={"Content-Type": "application/json"},
        )
        r = urllib.request.urlopen(req, timeout=90)
        return r.status, r.read().decode(), dict(r.headers)

    if pid == 0:
        def client():
            doors = [port + i * stride for i in range(n_proc)]
            for p in doors:
                wait_ready(p)
            time.sleep(1.0)
            qs = ["topic2 alpha beta", "churn doc 07", "seed doc 03 topic3"]
            out = {"during": [], "lags": []}
            # mid-churn: every door answers (locally or via an HONEST
            # forward), never an error, lag bounded when reported
            for i in range(18):
                p = doors[i % n_proc]
                status, _body, hdrs = retrieve(p, qs[i % len(qs)])
                out["during"].append([status, hdrs.get("X-Pathway-Fabric", "")])
                lag = hdrs.get("X-Pathway-Replica-Lag-Ms")
                if lag is not None:
                    out["lags"].append(float(lag))
                time.sleep(0.05)
            # settle: churn ends, replicas converge -> byte identity
            deadline = time.monotonic() + 45
            settled = None
            while time.monotonic() < deadline:
                rounds = []
                for q in qs:
                    row = [retrieve(p, q) for p in doors]
                    rounds.append(row)
                bodies_equal = all(
                    len({body for _s, body, _h in row}) == 1 for row in rounds
                )
                peers_local = all(
                    h.get("X-Pathway-Fabric", "").startswith("replica:")
                    for row in rounds
                    for _s, _b, h in row[1:]
                )
                nonempty = all(json.loads(row[0][1]) for row in rounds)
                if bodies_equal and peers_local and nonempty:
                    settled = rounds
                    break
                time.sleep(0.5)
            out["settled_ok"] = settled is not None
            if settled is not None:
                out["settled_rows"] = [
                    len(json.loads(row[0][1])) for row in settled
                ]
                out["settled_fabric"] = [
                    [h.get("X-Pathway-Fabric", "") for _s, _b, h in row]
                    for row in settled
                ]
            time.sleep(1.6)  # two heartbeats: the coordinator rollup lands
            out["status"] = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{mon_base}/status", timeout=30
            ).read())
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

    pw.run(monitoring_level="none", with_http_server=bool(mon_base),
           autocommit_duration_ms=50)
    print("DONE", flush=True)
    """
)


def _run_cluster(script_path, http_port, n_proc, extra_env, timeout=240, first_port=None):
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
                "cluster process hung; output:\n" + "\n---\n".join(texts)
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


def test_replica_three_door_byte_identity_under_churn(tmp_path):
    """The tentpole acceptance surface: a 3-process DocumentStore cluster
    with live churn answers /v1/retrieve from every door; once churn
    settles, peer doors answer LOCALLY (replica:p*) with bytes identical to
    the owner's engine answer, the coordinator's /status rolls replica
    health up pod-wide, and peer /metrics exposes pathway_replica_*."""
    script = tmp_path / "retrieve_cluster.py"
    script.write_text(_RETRIEVE_CLUSTER_SCRIPT)
    # one contiguous block: monitoring ports first, cluster bands after —
    # two independent scans would find the SAME free range and collide
    block = free_port_base(4 + 9)
    mon_base = block
    result = _run_cluster(
        script,
        _free_port(),
        3,
        {
            "PATHWAY_FABRIC": "on",
            "PATHWAY_REPLICA_MAX_STALENESS_MS": "2000",
            "PATHWAY_MONITORING_HTTP_PORT": str(mon_base),
        },
        first_port=block + 4,
    )
    # mid-churn: every request succeeded; honest sources only (replica or
    # forwarded, never empty)
    assert all(status == 200 for status, _src in result["during"]), result["during"]
    for lag in result["lags"]:
        assert lag <= 2000.0, result["lags"]
    # settled: byte identity across all three doors, peers serving locally
    assert result["settled_ok"], result
    assert all(n > 0 for n in result["settled_rows"])
    for row in result["settled_fabric"]:
        for src in row[1:]:
            assert src.startswith("replica:p"), row
    # /status: the fabric.index section on a peer door
    peer_index = result["peer_status"]["fabric"]["index"]["/v1/retrieve"]
    assert peer_index["armed"] is True
    assert peer_index["rows"] == 60  # 12 seed + 48 churn docs, full corpus
    assert peer_index["local_answers"] >= 1
    assert peer_index["lag_s"] is not None and peer_index["lag_s"] <= 2.0
    # coordinator rollup: every door reports, totals merged per route
    rollup = result["status"]["cluster"]["replica_index"]["/v1/retrieve"]
    assert rollup["doors"] == 3
    assert rollup["rows_min"] == 60
    assert rollup["local"] >= 1
    # peer /metrics: the replica series with route labels
    metrics = result["peer_metrics"]
    for series in (
        "pathway_replica_lag_seconds",
        "pathway_replica_index_rows",
        "pathway_replica_local_answers_total",
        "pathway_replica_fallback_total",
        "pathway_replica_gaps_total",
        "pathway_replica_resyncs_total",
    ):
        assert f'{series}{{route="/v1/retrieve"}}' in metrics, series


# ------------------------------------------------- SIGKILL + Supervisor

_SUPERVISED_REPLICA_SCRIPT = textwrap.dedent(
    """
    import json, os, sys, threading, time
    import pathway_tpu as pw
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm import DocumentStore
    from pathway_tpu.xpacks.llm.mocks import FakeEmbedder
    from pathway_tpu.xpacks.llm.servers import DocumentStoreServer

    port = int(sys.argv[1])
    stop_file = sys.argv[2]
    pid_dir = sys.argv[3]
    me = os.environ.get("PATHWAY_PROCESS_ID", "0")
    with open(os.path.join(pid_dir, f"pid.{me}"), "w") as fh:
        fh.write(str(os.getpid()))

    docs = pw.debug.table_from_rows(
        pw.schema_from_types(data=str),
        [(f"stable doc {i:02d} omega",) for i in range(10)],
    )
    store = DocumentStore(
        docs,
        retriever_factory=BruteForceKnnFactory(embedder=FakeEmbedder(dimension=16)),
    )
    DocumentStoreServer("127.0.0.1", port, store)

    def watch_stop():
        while not os.path.exists(stop_file):
            time.sleep(0.1)
        rt = pw.internals.run.current_runtime()
        if rt is not None:
            rt.request_stop()

    threading.Thread(target=watch_stop, daemon=True).start()
    pw.run(monitoring_level="none", autocommit_duration_ms=50)
    """
)


@pytest.mark.slow
def test_replica_door_sigkill_supervisor_resyncs_and_reserves(tmp_path):
    """SIGKILL the replica door mid-serve: the Supervisor relaunches the
    cluster, the fresh process resyncs (casts + snapshot RPC) and the door
    serves /v1/retrieve LOCALLY again with the same bytes as before."""
    from pathway_tpu.resilience.supervisor import Supervisor

    script = tmp_path / "sup_replica.py"
    script.write_text(_SUPERVISED_REPLICA_SCRIPT)
    stop_file = tmp_path / "stop"
    http_port = _free_port()
    first_port = free_port_base(6)
    env = dict(os.environ)
    env.update(
        PATHWAY_FABRIC="on",
        PATHWAY_REPLICA_MAX_STALENESS_MS="3000",
        PATHWAY_BARRIER_TIMEOUT="45",
        PATHWAY_HEARTBEAT_INTERVAL="0.2",
        PATHWAY_HEARTBEAT_TIMEOUT="3",
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO,
    )
    peer_port = http_port + 1
    phases: dict = {}

    def ask(timeout=60.0):
        """Poll the peer door until it answers LOCALLY (replica:p1) with the
        converged answer — staleness is bounded per slice, so an early local
        answer can legitimately predate the full corpus landing."""
        deadline = time.monotonic() + timeout
        last = None
        while time.monotonic() < deadline:
            try:
                status, body, hdrs = _post(
                    f"http://127.0.0.1:{peer_port}/v1/retrieve",
                    {"query": "stable doc 03 omega", "k": 1},
                    timeout=60,
                )
            except (urllib.error.URLError, OSError):
                time.sleep(0.5)
                continue
            last = (status, body, hdrs.get("X-Pathway-Fabric", ""))
            if (
                status == 200
                and last[2].startswith("replica:")
                and "stable doc 03 omega" in body
            ):
                return last
            time.sleep(0.5)
        return last

    def drive():
        try:
            _wait_ready(peer_port, timeout=90)
            phases["before"] = ask()
            import signal

            peer_os_pid = int((tmp_path / "pid.1").read_text())
            os.kill(peer_os_pid, signal.SIGKILL)
            time.sleep(1.0)
            _wait_ready(peer_port, timeout=120)
            phases["after"] = ask(timeout=90.0)
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
    assert phases.get("before") is not None and phases["before"][0] == 200
    assert phases["before"][2].startswith("replica:p1"), phases["before"]
    assert phases.get("after") is not None and phases["after"][0] == 200
    assert phases["after"][2].startswith("replica:p1"), phases["after"]
    assert phases["before"][1] == phases["after"][1]  # same bytes after resync
    assert result.restarts >= 1
