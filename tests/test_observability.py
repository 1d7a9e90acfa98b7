"""Live observability plane (ISSUE 3 tentpole): streaming spans with head
sampling, watermarks, end-to-end latency histograms, backlog gauges, the
``/trace`` endpoint, Prometheus escaping, clean shutdown, and cluster-wide
aggregation on the coordinator's ``/status``."""

from __future__ import annotations

import io
import json
import os
import socket
import subprocess
import sys
import textwrap
import threading
import time
import urllib.request

import pytest

import pathway_tpu as pw
from pathway_tpu import observability as obs
from pathway_tpu.internals.monitoring import (
    MonitoringHttpServer,
    escape_label_value,
    prometheus_text,
    run_stats,
)
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.observability.metrics import BUCKET_BOUNDS_S, Histogram
from pathway_tpu.observability.spans import (
    RotatingTraceSink,
    SpanBuffer,
    Tracer,
    derive_trace_id,
    tick_hash_sampled,
)
from conftest import free_port_base

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class S(pw.Schema):
    x: int


class TS(pw.Schema):
    x: int
    ts: float


def _slow_stream(n=60, pause_every=20, pause=0.02):
    class Subj(pw.io.python.ConnectorSubject):
        def run(self):
            for i in range(n):
                self.next(x=i)
                if i % pause_every == pause_every - 1:
                    time.sleep(pause)

    return Subj()


def _pipeline(subject=None, schema=S, **read_kwargs):
    G.clear()
    t = pw.io.python.read(subject or _slow_stream(), schema=schema, **read_kwargs)
    t = t.with_columns(m=t.x % 5)
    g = t.groupby(t.m).reduce(s=pw.reducers.sum(t.x))
    pw.io.subscribe(g, on_change=lambda **k: None)


# ------------------------------------------------------------------ sampling


def test_tick_hash_sampling_deterministic_and_proportional():
    assert all(tick_hash_sampled(t, 1.0) for t in range(100))
    assert not any(tick_hash_sampled(t, 0.0) for t in range(100))
    picked = [t for t in range(10_000) if tick_hash_sampled(t, 0.1)]
    # deterministic: same decision on every call (and thus every process)
    assert picked == [t for t in range(10_000) if tick_hash_sampled(t, 0.1)]
    assert 500 < len(picked) < 1500  # ~10%


def test_trace_id_derivation_is_stable():
    a, b = derive_trace_id("run-1"), derive_trace_id("run-1")
    assert a == b and len(a) == 32
    assert derive_trace_id("run-2") != a


# ------------------------------------------------------------ span plumbing


def test_span_buffer_since_cursor():
    buf = SpanBuffer(max_spans=4)
    for i in range(6):
        buf.append({"name": f"s{i}"})
    spans, seq = buf.since(0)
    assert [s["name"] for s in spans] == ["s2", "s3", "s4", "s5"]  # ring of 4
    assert seq == 6
    spans2, seq2 = buf.since(seq)
    assert spans2 == [] and seq2 == 6
    buf.append({"name": "s6"})
    spans3, _ = buf.since(seq)
    assert [s["name"] for s in spans3] == ["s6"]


def test_span_buffer_since_truncation_resumes_not_skips():
    """A slow /trace poller hitting the limit must get a cursor pointing at
    the last RETURNED span, so the backlog drains over successive polls."""
    buf = SpanBuffer(max_spans=10_000)
    for i in range(5000):
        buf.append({"name": f"s{i}"})
    first, cur = buf.since(0, limit=4096)
    assert len(first) == 4096 and cur == 4096
    rest, cur2 = buf.since(cur, limit=4096)
    assert [s["name"] for s in rest] == [f"s{i}" for i in range(4096, 5000)]
    assert cur2 == 5000


def test_rotating_sink_rotates(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    sink = RotatingTraceSink(path, rotate_bytes=2000)
    for i in range(40):
        sink.write([{"name": "x" * 50, "spanId": str(i)}])
    sink.close()
    assert os.path.exists(path + ".1")  # rotated at least once
    # both generations hold valid OTLP/JSON documents
    for p in (path, path + ".1"):
        with open(p) as fh:
            for line in fh:
                doc = json.loads(line)
                assert doc["resourceSpans"][0]["scopeSpans"][0]["spans"]


def test_fast_serializer_matches_materializer():
    """The file sink's direct string serializer must produce byte-equivalent
    OTLP spans to the generic materializer the /trace endpoint uses."""
    tr = Tracer(trace_id="ab" * 16, sample=1.0, buffer=SpanBuffer(max_spans=64))
    tr.begin_tick(3)
    tr.span(
        'weird "name"\\x',
        10,
        20,
        {"pathway.rows_in": 7, "ratio": 0.5, "flag": True, "s": 'a"b\\c'},
    )
    tr.span("bare", 30, 40)
    tok = tr.begin_tick(4)  # noqa: F841 — rotates the tick span id
    batch = list(tr.buffer._ring)
    line = tr._serialize_batch(batch)
    doc = json.loads(line)
    spans = doc["resourceSpans"][0]["scopeSpans"][0]["spans"]
    materialized = [tr._materialize(q, r) for q, r in batch]
    assert spans == materialized
    assert doc["resourceSpans"][0]["resource"]["attributes"][0]["value"][
        "stringValue"
    ] == "pathway_tpu"


def test_tracer_off_by_default():
    _pipeline()
    pw.run(monitoring_level="none")
    assert obs.current() is None
    rt = pw.internals.run.current_runtime()
    assert rt.scheduler.tracer is None  # hot loop pays one is-None test


def test_live_trace_spans_and_file(tmp_path, monkeypatch):
    path = str(tmp_path / "live.jsonl")
    monkeypatch.setenv("PATHWAY_TRACE", "on")
    monkeypatch.setenv("PATHWAY_TRACE_LIVE_FILE", path)
    _pipeline()
    pw.run(monitoring_level="none")
    spans = []
    with open(path) as fh:
        for line in fh:
            spans.extend(json.loads(line)["resourceSpans"][0]["scopeSpans"][0]["spans"])
    roots = [s for s in spans if s["name"] == "pathway.run"]
    assert len(roots) == 1
    ticks = [s for s in spans if s["name"] == "tick"]
    assert ticks and all(s["parentSpanId"] == roots[0]["spanId"] for s in ticks)
    sweeps = [s for s in spans if s["name"].startswith("sweep/")]
    tick_ids = {s["spanId"] for s in ticks}
    assert sweeps and all(s["parentSpanId"] in tick_ids for s in sweeps)
    names = {s["name"] for s in sweeps}
    # sources emit via poll (no pending input), so sweeps cover the
    # downstream operators — either as their own spans or inside a fused
    # chain span (r15: chains are the unit of dispatch, spans are
    # ``sweep/chain{a+b+...}`` naming every member)
    for op in ("groupby", "subscribe"):
        assert any(
            n == f"sweep/{op}" or (n.startswith("sweep/chain{") and op in n)
            for n in names
        ), f"no sweep span covers {op}: {names}"
    assert all(s["traceId"] == roots[0]["traceId"] for s in spans)
    # sweep spans carry row counts
    gb = next(s for s in sweeps if "groupby" in s["name"])
    keys = {a["key"] for a in gb["attributes"]}
    assert "pathway.rows_in" in keys


def test_head_sampling_drops_ticks(monkeypatch):
    monkeypatch.setenv("PATHWAY_TRACE", "on")
    monkeypatch.setenv("PATHWAY_TRACE_SAMPLE", "0.05")
    _pipeline()
    pw.run(monitoring_level="none")
    # tracer shut down at run end; sampled mode must record far fewer spans
    # than full-rate tracing of the same ~10-tick run would
    monkeypatch.setenv("PATHWAY_TRACE_SAMPLE", "1.0")
    tr_full = obs.install_from_env()
    assert tr_full is not None and tr_full.sample == 1.0
    obs.shutdown()


def test_trace_endpoint_serves_live_spans(monkeypatch):
    monkeypatch.setenv("PATHWAY_TRACE", "on")
    monkeypatch.setenv("PATHWAY_MONITORING_HTTP_PORT", "20611")
    # the stream outlasts the probe's two fetches on a loaded host too (the
    # server goes down with the run: a fetch after that is a reset connection)
    _pipeline(_slow_stream(n=80, pause_every=10, pause=0.1))
    got = {}

    def probe():
        time.sleep(0.1)
        try:
            one = json.loads(
                urllib.request.urlopen(
                    "http://127.0.0.1:20611/trace?since=0", timeout=2
                ).read()
            )
            time.sleep(0.05)
            two = json.loads(
                urllib.request.urlopen(
                    f"http://127.0.0.1:20611/trace?since={one['next']}", timeout=2
                ).read()
            )
            got["one"], got["two"] = one, two
        except Exception as e:  # pragma: no cover - surfaced by assert below
            got["error"] = repr(e)

    th = threading.Thread(target=probe)
    th.start()
    pw.run(with_http_server=True, monitoring_level="none")
    th.join()
    assert "error" not in got, got
    assert got["one"]["enabled"] and got["one"]["spans"]
    names = {s["name"] for s in got["one"]["spans"]}
    assert "tick" in names
    # the cursor advances and only newer spans return
    assert got["two"]["next"] >= got["one"]["next"]
    first_ids = {s["spanId"] for s in got["one"]["spans"]}
    assert not first_ids & {s["spanId"] for s in got["two"]["spans"]}


def test_microbatch_launch_and_device_dispatch_spans(monkeypatch):
    from pathway_tpu.internals.udfs import UDF

    class BatchedUdf(UDF):
        is_batched = True

        def __init__(self):
            super().__init__(_fn=lambda xs: [x * 2 for x in xs], return_type=int)

    monkeypatch.setenv("PATHWAY_TRACE", "on")
    monkeypatch.setenv("PATHWAY_MICROBATCH", "auto")
    G.clear()

    class KS(pw.Schema):
        k: int = pw.column_definition(primary_key=True)
        x: int

    t = pw.debug.table_from_rows(
        KS, [(i, 10 + i, i // 8, 1) for i in range(32)], is_stream=True
    )
    u = BatchedUdf()
    s = t.select(t.k, y=u(t.x))
    pw.io.subscribe(s, on_change=lambda **k: None)
    spans = {}

    real_shutdown = obs.shutdown

    def capture_then_shutdown():
        tr = obs.current()
        if tr is not None:
            spans["all"], _ = tr.buffer.since(0)
        real_shutdown()

    monkeypatch.setattr(obs, "shutdown", capture_then_shutdown)
    pw.run(monitoring_level="none")
    names = [s["name"] for s in spans["all"]]
    assert "microbatch/launch" in names
    assert "device/dispatch" in names
    disp = next(s for s in spans["all"] if s["name"] == "device/dispatch")
    attrs = {a["key"]: a["value"] for a in disp["attributes"]}
    assert "pathway.bucket" in attrs and "pathway.cold_shape" in attrs


# ------------------------------------------------- watermarks & histograms


def test_event_time_watermark_and_processing_time_fallback():
    class Subj(pw.io.python.ConnectorSubject):
        def run(self):
            for i in range(30):
                self.next(x=i, ts=5000.0 + i * 10)

    _pipeline(Subj(), schema=TS, event_time_column="ts", name="clicks")
    t0 = time.time()
    pw.run(monitoring_level="none")
    stats = run_stats(pw.internals.run.current_runtime())
    (wm,) = stats["watermarks"]
    assert wm["input"].startswith("clicks:")
    assert wm["watermark"] == 5290.0  # event-time high-water mark
    assert wm["rows_ingested"] == 30
    # processing-time fallback: watermark ≈ ingest wall clock
    _pipeline(name="raw")
    pw.run(monitoring_level="none")
    (wm2,) = run_stats(pw.internals.run.current_runtime())["watermarks"]
    assert wm2["input"].startswith("raw:")
    assert t0 - 60 < wm2["watermark"] <= time.time()
    assert wm2["lag_s"] is not None and wm2["lag_s"] >= 0


def test_sink_latency_histogram_populates_and_renders():
    _pipeline()
    pw.run(monitoring_level="none")
    rt = pw.internals.run.current_runtime()
    stats = run_stats(rt)
    assert stats["sink_latency"], stats
    (label, summary), = stats["sink_latency"].items()
    assert label.startswith("subscribe:")
    assert summary["count"] > 0 and summary["p50_s"] is not None
    text = prometheus_text(rt)
    assert "pathway_sink_latency_seconds_bucket" in text
    assert 'le="+Inf"' in text
    assert "pathway_sink_latency_seconds_count" in text
    assert "pathway_input_watermark_unix_seconds" in text
    assert "pathway_backlog_rows" in text
    # histogram invariant: +Inf cumulative count equals _count
    inf_line = next(
        l for l in text.splitlines()
        if l.startswith("pathway_sink_latency_seconds_bucket") and '+Inf' in l
    )
    count_line = next(
        l for l in text.splitlines()
        if l.startswith("pathway_sink_latency_seconds_count")
    )
    assert inf_line.rsplit(" ", 1)[1] == count_line.rsplit(" ", 1)[1]


def test_histogram_merge_and_quantile():
    h1, h2 = Histogram(), Histogram()
    for v in (0.001, 0.002, 0.004):
        h1.observe(v)
    for v in (0.5, 1.0, 100.0):
        h2.observe(v)
    merged = Histogram.merge([h1.snapshot(), h2.snapshot()])
    assert merged["count"] == 6
    assert merged["sum_s"] == pytest.approx(101.507)
    assert Histogram.quantile(merged, 0.5) <= 0.5
    assert Histogram.quantile(merged, 0.99) == float("inf")  # 100s > top bucket
    assert Histogram.quantile({"counts": [0] * (len(BUCKET_BOUNDS_S) + 1), "sum_s": 0, "count": 0}, 0.5) is None


def test_histogram_merge_edge_cases_empty_and_disjoint():
    """ISSUE 5 satellite: merge/quantile over empty and disjoint-bucket
    snapshots (a cluster peer on another build generation may ship a counts
    list of a different length, or nothing at all)."""
    empty = Histogram().snapshot()
    assert Histogram.merge([]) == {
        "counts": [0] * (len(BUCKET_BOUNDS_S) + 1),
        "sum_s": 0.0,
        "count": 0,
    }
    assert Histogram.merge([empty, empty])["count"] == 0
    assert Histogram.quantile(Histogram.merge([]), 0.5) is None
    # disjoint buckets: one peer only hit the lowest bucket, the other only
    # the overflow tail — the merge keeps both ends
    low = Histogram()
    low.observe(1e-6)
    high = Histogram()
    high.observe(1e9)
    merged = Histogram.merge([low.snapshot(), high.snapshot()])
    assert merged["count"] == 2
    assert merged["counts"][0] == 1 and merged["counts"][-1] == 1
    assert Histogram.quantile(merged, 0.25) == BUCKET_BOUNDS_S[0]
    assert Histogram.quantile(merged, 0.99) == float("inf")
    # short / missing counts lists degrade instead of crashing
    ragged = Histogram.merge([{"counts": [3], "sum_s": 0.1, "count": 3}, empty])
    assert ragged["counts"][0] == 3 and ragged["count"] == 3
    assert Histogram.merge([{"sum_s": 0.0, "count": 0}])["count"] == 0
    # over-long counts extend the result rather than dropping the tail
    long = Histogram.merge(
        [{"counts": [0] * (len(BUCKET_BOUNDS_S) + 2) + [7], "sum_s": 1.0, "count": 7}]
    )
    assert long["counts"][-1] == 7


def test_histogram_merge_associative_and_order_independent():
    """ISSUE 5 satellite property test: merge is associative and
    order-independent over randomized snapshots."""
    import itertools
    import random

    rng = random.Random(1234)
    snaps = []
    for _ in range(4):
        h = Histogram()
        for _ in range(rng.randrange(0, 40)):
            h.observe(rng.uniform(0, 64) ** 2 / 64.0)
        snaps.append(h.snapshot())
    baseline = Histogram.merge(snaps)
    for perm in itertools.permutations(snaps):
        m = Histogram.merge(list(perm))
        assert m["counts"] == baseline["counts"]
        assert m["count"] == baseline["count"]
        assert m["sum_s"] == pytest.approx(baseline["sum_s"])
    # associativity: merge(merge(a,b), merge(c,d)) == merge(a,b,c,d), and any
    # other parenthesization
    left = Histogram.merge(
        [Histogram.merge(snaps[:2]), Histogram.merge(snaps[2:])]
    )
    right = Histogram.merge(
        [snaps[0], Histogram.merge([snaps[1], Histogram.merge(snaps[2:])])]
    )
    assert left["counts"] == baseline["counts"] == right["counts"]
    assert left["count"] == baseline["count"] == right["count"]
    assert left["sum_s"] == pytest.approx(baseline["sum_s"])


def test_backlog_gauge_sees_queued_rows():
    from pathway_tpu.engine.operators import StreamInputNode

    node = StreamInputNode(["x"])
    node.node_index = 7
    for i in range(5):
        node.push(i, (i,))

    class FakeGraph:
        nodes = [node]

    class FakeSched:
        graph = FakeGraph()

    gauges = obs.backlog_gauges(FakeSched())
    assert gauges == [{"queue": "input:7", "rows": 5}]
    (wm,) = obs.input_watermarks(FakeSched())
    assert wm["backlog_rows"] == 5 and wm["rows_ingested"] == 5


# ---------------------------------------------------------- prometheus text


def test_prometheus_label_escaping():
    assert escape_label_value('plain') == 'plain'
    assert escape_label_value('a"b') == 'a\\"b'
    assert escape_label_value("a\\b") == "a\\\\b"
    assert escape_label_value("a\nb") == "a\\nb"

    class Node:
        node_index = 0
        name = 'weird"op\\name\nx'
        stats_rows_in = 3
        stats_rows_out = 2
        stats_time_ns = 1000
        stats_latency_ewma_ms = 0.5
        stats_last_time = 1

    class FakeGraph:
        nodes = [Node()]

    class FakeSched:
        graph = FakeGraph()
        current_time = 1

    class RT:
        scheduler = FakeSched()

    text = prometheus_text(RT())
    assert 'operator="weird\\"op\\\\name\\nx"' in text
    # no raw newline may survive inside a label value
    for line in text.splitlines():
        if line.startswith("pathway_operator_rows_in_total{"):
            assert line.count("{") == 1 and line.endswith(" 3")


# ------------------------------------------------------- http server extras


def test_monitoring_host_env(monkeypatch):
    monkeypatch.setenv("PATHWAY_MONITORING_HTTP_HOST", "0.0.0.0")

    class RT:
        scheduler = None

    srv = MonitoringHttpServer(RT(), port=0).start()
    try:
        assert srv.host == "0.0.0.0"
        status = json.loads(
            urllib.request.urlopen(f"http://127.0.0.1:{srv.port}/status", timeout=2).read()
        )
        assert status["alive"]
    finally:
        srv.stop()


def test_http_404_and_strict_paths():
    class RT:
        scheduler = None

    srv = MonitoringHttpServer(RT(), port=0).start()
    try:
        for bad in ("/nope", "/metricsfoo", "/status2"):
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(f"http://127.0.0.1:{srv.port}{bad}", timeout=2)
            assert exc.value.code == 404
    finally:
        srv.stop()


def test_run_stats_reports_monitoring_endpoint(monkeypatch):
    monkeypatch.setenv("PATHWAY_MONITORING_HTTP_PORT", "20633")
    _pipeline()
    got = {}

    def probe():
        time.sleep(0.05)
        try:
            got["status"] = json.loads(
                urllib.request.urlopen("http://127.0.0.1:20633/status", timeout=2).read()
            )
        except Exception as e:
            got["error"] = repr(e)

    th = threading.Thread(target=probe)
    th.start()
    pw.run(with_http_server=True, monitoring_level="none")
    th.join()
    assert "error" not in got, got
    assert got["status"]["monitoring"] == {"host": "127.0.0.1", "port": 20633}


# ------------------------------------------------------------ clean shutdown


def test_no_leaked_threads_or_ports_after_failing_runs(monkeypatch, tmp_path):
    """Two back-to-back FAILING runs with the http server + live tracing on:
    the server port must rebind, the dashboard/tracer threads must not
    accumulate, and the trace sink must be closed (ISSUE 3 satellite)."""
    monkeypatch.setenv("PATHWAY_MONITORING_HTTP_PORT", "20655")
    monkeypatch.setenv("PATHWAY_TRACE", "on")
    monkeypatch.setenv("PATHWAY_TRACE_LIVE_FILE", str(tmp_path / "t.jsonl"))

    class Exploding(pw.io.python.ConnectorSubject):
        def run(self):
            self.next(x=1)
            time.sleep(0.02)
            raise RuntimeError("boom")

    def failing_run():
        _pipeline(Exploding())
        with pytest.raises(RuntimeError, match="input connector failed"):
            pw.run(with_http_server=True, monitoring_level="none")

    baseline = threading.active_count()
    failing_run()
    failing_run()  # port 20655 must be free again — stop() ran despite the raise
    assert obs.current() is None  # tracer shut down despite the raise
    # give daemon threads a beat to unwind, then compare
    deadline = time.time() + 5
    while threading.active_count() > baseline and time.time() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= baseline + 1, [
        t.name for t in threading.enumerate()
    ]
    # the port is genuinely released
    s = socket.socket()
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.bind(("127.0.0.1", 20655))
    s.close()


# ------------------------------------------------------- cluster aggregation


_CLUSTER_PIPELINE = textwrap.dedent(
    """
    import time

    import pathway_tpu as pw

    class S(pw.Schema):
        x: int

    class Subj(pw.io.python.ConnectorSubject):
        def run(self):
            for i in range(40):
                self.next(x=i)
                time.sleep(0.06)

    t = pw.io.python.read(Subj(), schema=S, name="feed")
    t = t.with_columns(m=t.x % 3)
    g = t.groupby(t.m).reduce(s=pw.reducers.sum(t.x))
    pw.io.subscribe(g, on_change=lambda **k: None)
    pw.run(with_http_server=True, monitoring_level="none")
    """
)


def test_cluster_monitoring_and_trace_stitching(tmp_path):
    """2-process cluster, live: per-process /metrics on offset ports, the
    coordinator /status aggregates every peer's tick/watermark/backlog, and
    the exported per-process trace docs share one trace id (ISSUE 3
    acceptance)."""
    script = tmp_path / "pipeline.py"
    script.write_text(_CLUSTER_PIPELINE)
    # one contiguous free range: cluster plane at base..base+3 (coordinator,
    # peer links, heartbeats), monitoring HTTP at base+5/base+6
    base = free_port_base(7)
    first_port = base
    http_base = base + 5
    env = dict(os.environ)
    env.update(
        PATHWAY_PROCESSES="2",
        PATHWAY_THREADS="1",
        PATHWAY_FIRST_PORT=str(first_port),
        PATHWAY_BARRIER_TIMEOUT="45",
        PATHWAY_MONITORING_HTTP_PORT=str(http_base),
        PATHWAY_HEARTBEAT_INTERVAL="0.1",
        PATHWAY_TRACE="on",
        PATHWAY_RUN_ID="obs-test-run",
        PATHWAY_TRACE_FILE=str(tmp_path / "run.otlp.json"),
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO,
    )
    procs = [
        subprocess.Popen(
            [sys.executable, str(script)],
            env=dict(env, PATHWAY_PROCESS_ID=str(pid)),
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for pid in range(2)
    ]
    got: dict = {}
    try:
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                status0 = json.loads(
                    urllib.request.urlopen(
                        f"http://127.0.0.1:{http_base}/status", timeout=2
                    ).read()
                )
                cluster = status0.get("cluster")
                # wait on what the assertions below read, not on the head
                # count alone: a peer can report before the coordinator has
                # built its own graph, and then no input has a watermark yet
                if (
                    cluster
                    and cluster["n_reporting"] == 2
                    and status0.get("watermarks")
                    and cluster.get("watermark_min") is not None
                    and all(p.get("tick") is not None for p in cluster["processes"].values())
                ):
                    got["status0"] = status0
                    got["metrics1"] = (
                        urllib.request.urlopen(
                            f"http://127.0.0.1:{http_base + 1}/metrics", timeout=2
                        )
                        .read()
                        .decode()
                    )
                    got["trace0"] = json.loads(
                        urllib.request.urlopen(
                            f"http://127.0.0.1:{http_base}/trace?since=0", timeout=2
                        ).read()
                    )
                    break
            except (urllib.error.URLError, OSError):
                pass
            if any(p.poll() is not None for p in procs):
                break
            time.sleep(0.2)
        outs = []
        for p in procs:
            try:
                out, _ = p.communicate(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate()
            outs.append(out or "")
        assert all(p.returncode == 0 for p in procs), "\n---\n".join(outs)
        assert "status0" in got, "coordinator never reported 2 processes:\n" + "\n---\n".join(outs)
        cluster = got["status0"]["cluster"]
        # every process reports tick + backlog; the stream was live so the
        # coordinator saw watermarks from its own inputs
        assert set(cluster["processes"]) == {"0", "1"}
        for pid, summary in cluster["processes"].items():
            assert summary["tick"] is not None, (pid, summary)
            assert "backlog_rows" in summary and "rows_in" in summary
        assert cluster["tick_max"] is not None and cluster["tick_max"] >= 0
        assert got["status0"]["watermarks"], got["status0"]
        assert cluster["watermark_min"] is not None
        # peer's /metrics serves on the offset port while live
        assert "pathway_operator_rows_in_total" in got["metrics1"]
        # live /trace shares the run-id-derived trace id
        expected_trace = derive_trace_id("obs-test-run")
        assert got["trace0"]["traceId"] == expected_trace
        # offline per-process docs stitch under the SAME trace id
        for pid in (0, 1):
            with open(str(tmp_path / "run.otlp.json") + f".p{pid}") as fh:
                doc = json.load(fh)
            spans = doc["resourceSpans"][0]["scopeSpans"][0]["spans"]
            assert all(s["traceId"] == expected_trace for s in spans)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


# --------------------------------------------- fused-chain error attribution


def test_fused_chain_error_attributed_to_member_not_tail(monkeypatch):
    """ISSUE 13 satellite: a UDF raise inside a fused chain must attribute to
    the raising MEMBER on ``pathway_operator_errors_total{op}``, not to the
    chain tail (the chain executes as ONE sweep step; the per-member
    ``_tls.node`` pin inside the segment/unit walk is what keeps row-level
    error reports honest)."""
    import pathway_tpu as pw
    from pathway_tpu.internals import error_log
    from pathway_tpu.internals.monitoring import prometheus_text

    monkeypatch.setenv("PATHWAY_TERMINATE_ON_ERROR", "0")
    error_log.clear()

    class S(pw.Schema):
        x: int

    t = pw.debug.table_from_rows(S, [(i,) for i in range(8)])

    def boom(v):
        if v == 5:
            raise ValueError("mid-chain boom")
        return v * 10

    mid = t.select(y=pw.apply(boom, t.x))  # the raising MEMBER (a select)
    # the chain TAIL is a different operator kind, so a tail-attributed error
    # would be unmistakable ("filter:N" instead of "select:N")
    tail = mid.filter(mid.y >= 0)
    rows: list = []
    pw.io.subscribe(tail, lambda key, row, time, is_addition: rows.append(row))
    pw.run(monitoring_level="none", terminate_on_error=False)
    rt = pw.internals.run.current_runtime()
    counts = error_log.operator_error_counts()
    assert counts, "row-level failure was not logged at all"
    ((label, n),) = counts.items()
    assert n == 1
    # the label names the raising member's operator, never the chain tail
    assert label.startswith("select:"), f"error attributed to {label}"
    # /metrics carries the member-labelled counter
    text = prometheus_text(rt)
    assert f'pathway_operator_errors_total{{op="{label}"}} 1' in text
    error_log.clear()


def test_fused_chain_filter_error_attributed_to_filter(monkeypatch):
    """Same contract for a raising FILTER member mid-chain."""
    import pathway_tpu as pw
    from pathway_tpu.internals import error_log

    monkeypatch.setenv("PATHWAY_TERMINATE_ON_ERROR", "0")
    error_log.clear()

    class S(pw.Schema):
        x: int

    t = pw.debug.table_from_rows(S, [(i,) for i in range(6)])

    def keep(v):
        if v == 2:
            raise ValueError("filter boom")
        return True

    mid = t.filter(pw.apply(keep, t.x))
    tail = mid.select(z=mid.x + 1)
    pw.debug.table_to_pandas(tail)
    counts = error_log.operator_error_counts()
    assert counts and all(l.startswith("filter:") for l in counts), counts
    error_log.clear()
