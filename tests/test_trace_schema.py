"""OTLP-JSON line-schema validation (ISSUE 13 satellite).

Every span/event kind the repo emits — r8 tick + sweep spans, r10 device
dispatches, microbatch launches, serving events, r12 audit events, and the
new request-scoped spans — must round-trip through one checked schema, so a
sink-format drift fails tier-1 instead of breaking downstream collectors
(Perfetto / otel-desktop-viewer / the OTel file-exporter convention).

Validated on BOTH read paths: the materialized span dicts served by
``/trace?since=`` and the direct string-built ``ExportTraceServiceRequest``
lines the rotating file sink writes (they are separate serializers by
design — the fast path must not drift from the dict path).
"""

from __future__ import annotations

import json
import re
import socket
import threading
import time
import urllib.request

import pytest

import pathway_tpu as pw
from pathway_tpu import observability as obs
from pathway_tpu.observability import requests as req_mod

_HEX32 = re.compile(r"^[0-9a-f]{32}$")
_HEX16 = re.compile(r"^[0-9a-f]{16}$")
_DIGITS = re.compile(r"^[0-9]+$")

#: the exhaustive attribute-value box set this repo emits
_VALUE_KEYS = {"stringValue", "intValue", "doubleValue", "boolValue"}


def validate_span(span: dict) -> None:
    """One OTLP span object against the repo's emitted schema."""
    allowed = {
        "traceId",
        "spanId",
        "parentSpanId",
        "name",
        "kind",
        "startTimeUnixNano",
        "endTimeUnixNano",
        "attributes",
    }
    assert set(span) <= allowed, f"unknown span fields: {set(span) - allowed}"
    assert _HEX32.match(span["traceId"]), span
    assert _HEX16.match(span["spanId"]), span
    if "parentSpanId" in span:
        assert _HEX16.match(span["parentSpanId"]), span
    assert isinstance(span["name"], str) and span["name"]
    assert span["kind"] == 1
    assert _DIGITS.match(span["startTimeUnixNano"]), span
    assert _DIGITS.match(span["endTimeUnixNano"]), span
    assert int(span["endTimeUnixNano"]) >= int(span["startTimeUnixNano"]), span
    assert isinstance(span["attributes"], list)
    for attr in span["attributes"]:
        assert set(attr) == {"key", "value"}, attr
        assert isinstance(attr["key"], str) and attr["key"]
        v = attr["value"]
        assert isinstance(v, dict) and len(v) == 1, attr
        (vk, vv), = v.items()
        assert vk in _VALUE_KEYS, attr
        if vk == "intValue":
            assert isinstance(vv, str) and re.match(r"^-?[0-9]+$", vv), attr
        elif vk == "doubleValue":
            assert isinstance(vv, (int, float)), attr
        elif vk == "boolValue":
            assert isinstance(vv, bool), attr
        else:
            assert isinstance(vv, str), attr


def validate_export_line(line: str) -> list[dict]:
    """One file-sink line as a full ExportTraceServiceRequest document."""
    doc = json.loads(line)
    assert set(doc) == {"resourceSpans"}, doc.keys()
    spans_out = []
    for rs in doc["resourceSpans"]:
        assert set(rs) == {"resource", "scopeSpans"}
        for attr in rs["resource"]["attributes"]:
            assert set(attr) == {"key", "value"}
        for ss in rs["scopeSpans"]:
            assert set(ss) == {"scope", "spans"}
            assert ss["scope"]["name"] == "pathway_tpu.live"
            for span in ss["spans"]:
                validate_span(span)
                spans_out.append(span)
    return spans_out


def _free_port() -> int:
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_all_emitted_span_kinds_round_trip(tmp_path, monkeypatch):
    """Drive a real serving pipeline with every plane on and validate every
    span it emitted — through the ring-buffer dict path AND the file-sink
    line path — then assert the core span-kind coverage so a silently
    missing emitter can't pass as 'nothing to validate'."""
    trace_file = tmp_path / "live.otlpjson"
    monkeypatch.setenv("PATHWAY_TRACE", "on")
    monkeypatch.setenv("PATHWAY_TRACE_SAMPLE", "1.0")
    monkeypatch.setenv("PATHWAY_TRACE_LIVE_FILE", str(trace_file))
    monkeypatch.setenv("PATHWAY_REQUEST_TRACE", "on")
    monkeypatch.setenv("PATHWAY_REQUEST_TRACE_SLOW_MS", "0")  # keep all
    port = _free_port()

    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.mocks import FakeEmbedder

    G.clear()
    emb = FakeEmbedder(dimension=8, deterministic=True)
    doc_t = pw.debug.table_from_rows(
        pw.schema_from_types(text=str), [(f"doc {i}",) for i in range(8)]
    )
    index = BruteForceKnnFactory(embedder=emb, reserved_space=32).build_index(
        doc_t.text, doc_t
    )
    queries, respond = pw.io.http.rest_connector(
        host="127.0.0.1", port=port, schema=pw.schema_from_types(query=str)
    )
    picked = index.query_as_of_now(queries.query, number_of_matches=1).select(
        top=pw.apply(lambda ts: ts[0] if ts else "", pw.right.text)
    )
    respond(picked.select(result=picked.top))

    captured: dict = {}

    def orchestrate() -> None:
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            try:
                socket.create_connection(("127.0.0.1", port), timeout=0.5).close()
                break
            except OSError:
                time.sleep(0.02)
        for i in range(3):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/",
                data=json.dumps({"query": f"doc {i}"}).encode(),
                headers={"Content-Type": "application/json"},
            )
            urllib.request.urlopen(req, timeout=30).read()
        # audit/violation event shape: emitted through the same tracer.event
        # machinery the audit plane uses (provoking a real data-corruption
        # fault here would abort the run)
        tracer = obs.current()
        import gc

        gc.collect()  # a pass of generation 2 while the tracer records: a gc/collect span
        tracer.event(
            "audit/violation",
            {
                "pathway.audit.kind": "negative_multiplicity",
                "pathway.operator": "groupby:3",
                "pathway.key": "42",
                "pathway.tick": 7,
                "pathway.detail": "schema-coverage synthetic",
            },
        )
        spans, _ = tracer.buffer.since(0, limit=100000)
        captured["spans"] = spans
        rt = pw.internals.run.current_runtime()
        if rt is not None:
            rt.request_stop()

    th = threading.Thread(target=orchestrate)
    th.start()
    pw.run(monitoring_level="none")
    th.join()
    G.clear()

    spans = captured["spans"]
    assert spans, "no spans captured"
    for s in spans:
        validate_span(s)
    names = {s["name"] for s in spans}
    # coverage: every currently-emitted kind family must be present — a
    # removed/renamed emitter fails here, not in a downstream collector
    assert "tick" in names
    assert any(n.startswith("sweep/") for n in names), names
    assert any(n.startswith("sweep/chain{") for n in names), names
    assert any(n.startswith("microbatch/") or n == "device/dispatch" for n in names), names
    assert "serve/respond" in names, names
    assert "audit/violation" in names
    # PR 36: every kernel call's hand-over, and the collector's passes
    assert "device/launch" in names and "gc/collect" in names, names
    # request-plane spans (7-tuple records with per-request trace ids)
    assert "serve/request" in names and "serve/admission" in names, names
    req_spans = [s for s in spans if s["name"] == "serve/request"]
    tick_spans = [s for s in spans if s["name"] == "tick"]
    assert req_spans and tick_spans
    # request spans carry their own (per-request) trace ids, tick spans the
    # run trace id — distinct, both 32-hex (the stitching contract)
    assert {s["traceId"] for s in req_spans}.isdisjoint(
        {s["traceId"] for s in tick_spans}
    )

    # ---- the file sink's direct string serializer must agree ---------------
    assert trace_file.exists(), "live OTLP file sink never wrote"
    sink_spans: list[dict] = []
    with open(trace_file) as fh:
        for line in fh:
            if line.strip():
                sink_spans.extend(validate_export_line(line))
    assert sink_spans
    sink_names = {s["name"] for s in sink_spans}
    assert "tick" in sink_names
    assert "serve/request" in sink_names, "request spans missing from the file sink"


def test_decode_loop_and_collector_span_shapes():
    """Unit (PR 36): ``device/launch`` (a bool attribute), the decode loop's
    ``pathway.live`` and ``generate/yield`` event, a ``gc/collect`` span and
    a tick carrying its generation-0 sums, through both serializers."""
    from pathway_tpu.observability.spans import SpanBuffer, Tracer

    tr = Tracer(trace_id="ab" * 16, buffer=SpanBuffer(max_spans=64))
    tick = tr.begin_tick(1)
    step = tr.begin("generate/step")
    tr.end(tr.begin("device/launch"), {"pathway.label": "decoder.step", "pathway.cold": False})
    tr.end(tr.begin("device/fetch"), {"pathway.label": "decoder.tokens", "pathway.bytes": 16})
    tr.end(step, {"pathway.rows": 2, "pathway.bucket": 2, "pathway.live": 1})
    tr.event("generate/yield", {"pathway.operator.id": 3, "pathway.steps": 4, "pathway.reason": "period"})
    for gen in (0, 2):
        tr.on_gc("start", {"generation": gen})
        tr.on_gc("stop", {"generation": gen, "collected": 7, "uncollectable": 0})
    tr.end_tick(1, tick)
    batch = list(tr.buffer._ring)
    dict_spans = [tr._materialize(q, r) for q, r in batch]
    line_spans = validate_export_line(tr._serialize_batch(batch))
    assert [s["name"] for s in line_spans] == [s["name"] for s in dict_spans] == [
        "device/launch", "device/fetch", "generate/step", "generate/yield", "gc/collect", "tick"]
    for span in dict_spans:
        validate_span(span)
    attrs = {s["name"]: {a["key"]: a["value"] for a in s["attributes"]} for s in line_spans}
    assert attrs["device/launch"]["pathway.cold"] == {"boolValue": False}
    assert attrs["generate/step"]["pathway.live"] == {"intValue": "1"}
    assert attrs["generate/yield"]["pathway.reason"] == {"stringValue": "period"}
    assert attrs["gc/collect"]["pathway.generation"] == {"intValue": "2"}
    assert attrs["tick"]["pathway.gc0_passes"] == {"intValue": "1"}


def test_request_plane_span_record_shapes():
    """Unit: a synthetic request's kept spans validate without a pipeline
    (every event family: boundary, engine stage with attrs, respond)."""
    from pathway_tpu.internals.config import get_pathway_config

    plane = req_mod.RequestTracePlane(get_pathway_config())
    plane.slow_ms = 0.0  # keep unconditionally
    now = time.monotonic_ns()
    key = 7777
    plane.begin(key, "/v1/retrieve", now)
    plane.note_tick(3)
    plane.note_stage(3, "sweep/chain{select+subscribe}", now, now + 10_000, rows=4)
    plane.note_stage(
        3,
        "microbatch/embed",
        now,
        now + 5_000,
        rows=2,
        attrs={"udf": "embed", "bucket": 8, "pad": 6, "cold": True, "compile_ms": 1.5},
    )
    plane.note_stage(3, "index/search", now, now + 2_000, rows=1)
    doc = plane.complete(key, "ok", now + 20_000, now + 21_000)
    assert doc is not None
    assert doc["trace_id"] == req_mod.derive_request_trace_id(doc["request_id"])
    for span in doc["spans"]:
        validate_span(span)
    names = [s["name"] for s in doc["spans"]]
    assert names[0] == "request"
    assert "microbatch/embed" in names and "index/search" in names
    decomp = doc["decomposition_ms"]
    assert decomp["index/search"] == pytest.approx(0.002)
    assert decomp["serve/respond"] == pytest.approx(0.001)


def test_replica_span_record_shapes():
    """Unit (r20): a replica-served retrieval's spans — the door's local
    embed + search boundaries recorded via note_boundary — validate and
    decompose exactly like owner-side engine stages."""
    from pathway_tpu.internals.config import get_pathway_config

    plane = req_mod.RequestTracePlane(get_pathway_config())
    plane.slow_ms = 0.0  # keep unconditionally
    now = time.monotonic_ns()
    key = 8888
    plane.begin(key, "/v1/retrieve", now)
    plane.note_boundary(key, "replica/embed", now, now + 4_000, None)
    plane.note_boundary(key, "replica/search", now + 4_000, now + 9_000, {"rows": 3})
    doc = plane.complete(key, "ok", now + 10_000, now + 11_000)
    assert doc is not None
    for span in doc["spans"]:
        validate_span(span)
    names = [s["name"] for s in doc["spans"]]
    assert names[0] == "request"
    assert "replica/embed" in names and "replica/search" in names
    search = next(s for s in doc["spans"] if s["name"] == "replica/search")
    attrs = {a["key"]: a["value"] for a in search["attributes"]}
    assert attrs["rows"] == {"intValue": "3"}
    decomp = doc["decomposition_ms"]
    assert decomp["replica/embed"] == pytest.approx(0.004)
    assert decomp["replica/search"] == pytest.approx(0.005)


def test_embedder_memo_metric_line_shapes():
    """Unit (r20): the shared-memo Prometheus series are well-formed
    exposition text — HELP/TYPE per series, escaped embedder labels, and a
    hit ratio that agrees with the counters."""
    import re

    from pathway_tpu.xpacks.llm import embedders as emb_mod

    emb = emb_mod.SentenceTransformerEmbedder("tiny", seed=777, memoize=8)
    emb.func(["trace schema memo q1", "trace schema memo q2"])
    emb.func(["trace schema memo q1"])  # one hit
    lines = emb_mod.memo_prometheus_lines()
    sample = re.compile(
        r"^pathway_embedder_memo_[a-z_]+\{embedder=\"[^\"]+\"\} "
        r"-?\d+(\.\d+)?$"
    )
    for line in lines:
        assert line.startswith("#") or sample.match(line), line
    series = {
        line.split()[2] for line in lines if line.startswith("# TYPE")
    }
    assert {
        "pathway_embedder_memo_hits_total",
        "pathway_embedder_memo_misses_total",
        "pathway_embedder_memo_evictions_total",
        "pathway_embedder_memo_entries",
        "pathway_embedder_memo_hit_ratio",
    } <= series
    label = f'embedder="{emb.memo_fingerprint}"'
    body = "\n".join(lines)
    assert f"pathway_embedder_memo_hits_total{{{label}}} 1" in body
    assert f"pathway_embedder_memo_misses_total{{{label}}} 2" in body


def test_health_alert_and_door_state_event_shapes(monkeypatch):
    """Unit (r21): the health plane's trace events — ``health/door_state`` on
    every lifecycle transition and ``alert/fired`` / ``alert/resolved`` from
    the registry — are valid zero-duration spans with the documented attrs."""
    from pathway_tpu.internals.config import get_pathway_config
    from pathway_tpu.observability import alerts as alerts_mod
    from pathway_tpu.observability import health as health_mod

    monkeypatch.setenv("PATHWAY_TRACE", "on")
    monkeypatch.setenv("PATHWAY_TRACE_SAMPLE", "1.0")
    monkeypatch.setenv("PATHWAY_HEALTH", "off")  # plane driven by hand below
    obs.install_from_env(None)
    try:
        tracer = obs.current()
        assert tracer is not None
        cfg = get_pathway_config()
        plane = health_mod.HealthPlane(cfg)  # no thread: transitions by hand
        plane.mark_ready()
        plane.door_syncing(("ix", "/v1/retrieve", 0))
        plane.door_synced(("ix", "/v1/retrieve", 0))
        plane.mark_draining("rescale")
        registry = alerts_mod.AlertRegistry(cfg)
        registry.fire(
            "slo_latency_burn",
            fingerprint="/v1/retrieve",
            severity="page",
            summary="burn 16.7",
        )
        registry.resolve("slo_latency_burn", "/v1/retrieve")
        spans, _ = tracer.buffer.since(0, limit=100000)
    finally:
        obs.shutdown()
    for s in spans:
        validate_span(s)
    by_name: dict[str, list] = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    door = by_name.get("health/door_state") or []
    states = []
    for s in door:
        attrs = {a["key"]: a["value"] for a in s["attributes"]}
        states.append(attrs["pathway.state"]["stringValue"])
        if attrs["pathway.state"]["stringValue"] == "draining":
            assert attrs["pathway.reason"] == {"stringValue": "rescale"}
    assert states == ["ready", "syncing", "draining"], states
    (fired,) = by_name["alert/fired"]
    attrs = {a["key"]: a["value"] for a in fired["attributes"]}
    assert attrs["pathway.alert"] == {"stringValue": "slo_latency_burn"}
    assert attrs["pathway.fingerprint"] == {"stringValue": "/v1/retrieve"}
    assert attrs["pathway.severity"] == {"stringValue": "page"}
    assert attrs["pathway.summary"] == {"stringValue": "burn 16.7"}
    (resolved,) = by_name["alert/resolved"]
    attrs = {a["key"]: a["value"] for a in resolved["attributes"]}
    assert attrs["pathway.alert"] == {"stringValue": "slo_latency_burn"}
    # zero-duration event contract: start == end, same 32-hex run trace id
    assert fired["startTimeUnixNano"] == fired["endTimeUnixNano"]
    assert fired["traceId"] == door[0]["traceId"]


def test_health_metric_line_shapes(monkeypatch):
    """Unit (r21): the ``pathway_door_*`` / ``pathway_slo_*`` /
    ``pathway_canary_*`` / ``pathway_alert_*`` series are well-formed
    Prometheus exposition text with HELP/TYPE per series."""
    import re

    from pathway_tpu.internals.config import get_pathway_config
    from pathway_tpu.observability import alerts as alerts_mod
    from pathway_tpu.observability import health as health_mod

    monkeypatch.setenv("PATHWAY_SLO_AVAILABILITY", "0.999")
    health_mod.reset_slos()
    try:
        cfg = get_pathway_config()
        plane = health_mod.HealthPlane(cfg)
        plane.mark_ready()
        pw.set_slo(route="/v1/retrieve", p99_ms=125.0)
        plane.canary_total["/v1/retrieve"] = 7
        plane.canary_failed["/v1/retrieve"] = 1
        plane.canary_last_s["/v1/retrieve"] = 0.012345
        plane.burn = {"latency:/v1/retrieve": {"fast": 16.7, "slow": 16.7}}
        plane.budget_remaining = {"latency:/v1/retrieve": 0.0}
        plane.registry = alerts_mod.AlertRegistry(cfg)
        plane.registry.fire(
            "slo_latency_burn", fingerprint="/v1/retrieve", severity="page"
        )
        lines = plane.prometheus_lines()
    finally:
        health_mod.reset_slos()
    sample = re.compile(
        r"^pathway_(door|slo|canary|alert|alerts)_[a-z_]+"
        r"(\{[a-z_]+=\"[^\"]*\"(,[a-z_]+=\"[^\"]*\")*\})? "
        r"-?\d+(\.\d+)?$"
    )
    for line in lines:
        assert line.startswith("#") or sample.match(line), line
    series = {line.split()[2] for line in lines if line.startswith("# TYPE")}
    assert {
        "pathway_door_ready",
        "pathway_door_state",
        "pathway_slo_target",
        "pathway_slo_burn_rate",
        "pathway_slo_error_budget_remaining",
        "pathway_canary_requests_total",
        "pathway_canary_failures_total",
        "pathway_canary_latency_seconds",
        "pathway_alert_active",
        "pathway_alerts_fired_total",
    } <= series, series
    body = "\n".join(lines)
    assert "pathway_door_ready 1" in body
    assert 'pathway_door_state{state="ready"} 1' in body
    assert 'pathway_door_state{state="draining"} 0' in body
    assert 'pathway_slo_target{slo="availability"} 0.999' in body
    # latency target exported in SECONDS
    assert 'pathway_slo_target{slo="latency",route="/v1/retrieve"} 0.125' in body
    assert (
        'pathway_slo_burn_rate{slo="latency",route="/v1/retrieve",window="fast"} 16.7'
        in body
    )
    assert 'pathway_canary_requests_total{route="/v1/retrieve"} 7' in body
    assert 'pathway_canary_failures_total{route="/v1/retrieve"} 1' in body
    assert (
        'pathway_alert_active{alert="slo_latency_burn",fingerprint="/v1/retrieve"} 1'
        in body
    )
    assert 'pathway_alerts_fired_total{alert="slo_latency_burn"} 1' in body


def test_timeline_segment_line_is_valid_otlp_metrics(tmp_path):
    """Unit (r23): every line the timeline segment sink spills is a complete
    OTLP-metrics-JSON document — resource attrs carry the process identity,
    the scope names the recorder, and every series is a gauge whose data
    points use the string-nanos/double encoding."""
    from pathway_tpu.observability import timeline as timeline_mod

    path = str(tmp_path / "timeline-p0.jsonl")
    sink = timeline_mod.TimelineSegmentSink(path, 7, rotate_bytes=1 << 20)
    sink.write({"t": 1234.5, "tick": 3, "serve_qps": 10.0,
                "stage_p99_s:sweep/q": 0.4})
    sink.close()
    with open(path, encoding="utf-8") as fh:
        (line,) = [l for l in fh.read().splitlines() if l.strip()]
    doc = json.loads(line)
    assert set(doc) == {"resourceMetrics"}
    (rm,) = doc["resourceMetrics"]
    attrs = {a["key"]: a["value"] for a in rm["resource"]["attributes"]}
    assert attrs["service.name"] == {"stringValue": "pathway_tpu"}
    assert attrs["pathway.process_id"] == {"intValue": "7"}
    assert set(attrs["process.pid"]) == {"intValue"}
    for a in rm["resource"]["attributes"]:
        (vk,) = a["value"].keys()
        assert vk in _VALUE_KEYS, a
    (sm,) = rm["scopeMetrics"]
    assert sm["scope"] == {"name": "pathway_tpu.timeline", "version": "1"}
    names = set()
    for metric in sm["metrics"]:
        names.add(metric["name"])
        assert set(metric) == {"name", "gauge"}
        (dp,) = metric["gauge"]["dataPoints"]
        assert set(dp) == {"timeUnixNano", "asDouble"}
        assert isinstance(dp["timeUnixNano"], str)  # u64 nanos ride as string
        assert int(dp["timeUnixNano"]) == 1234500000000
        assert isinstance(dp["asDouble"], float)
    assert names == {"tick", "serve_qps", "stage_p99_s:sweep/q"}
    # and the reader round-trips the same point back
    (pt,) = timeline_mod.read_segments(str(tmp_path))
    assert pt["serve_qps"] == 10.0 and pt["t"] == 1234.5


def test_bottleneck_top_event_shape(monkeypatch):
    """Unit (r23): the attributor's ``bottleneck/top`` trace event is a valid
    zero-duration span carrying cause/verdict/knob/score attrs, emitted only
    when the ranked top cause CHANGES."""
    from pathway_tpu.internals.config import get_pathway_config
    from pathway_tpu.observability import timeline as timeline_mod

    monkeypatch.setenv("PATHWAY_TRACE", "on")
    monkeypatch.setenv("PATHWAY_TRACE_SAMPLE", "1.0")
    obs.install_from_env(None)
    try:
        tracer = obs.current()
        assert tracer is not None
        plane = timeline_mod.TimelinePlane(get_pathway_config(), None)
        plane.bottleneck = {
            "top": {"cause": "phase:probe", "score": 0.9,
                    "verdict": "tick probe-bound", "knob": "raise X"},
            "ranked": [], "window_s": 60.0,
        }
        plane._publish_top_change()
        plane._publish_top_change()  # unchanged cause: no second event
        spans, _ = tracer.buffer.since(0, limit=100000)
    finally:
        obs.shutdown()
    events = [s for s in spans if s["name"] == "bottleneck/top"]
    assert len(events) == 1
    (ev,) = events
    validate_span(ev)
    assert ev["startTimeUnixNano"] == ev["endTimeUnixNano"]
    attrs = {a["key"]: a["value"] for a in ev["attributes"]}
    assert attrs["pathway.cause"] == {"stringValue": "phase:probe"}
    assert attrs["pathway.verdict"] == {"stringValue": "tick probe-bound"}
    assert attrs["pathway.knob"] == {"stringValue": "raise X"}
    assert attrs["pathway.score"] == {"doubleValue": 0.9}
