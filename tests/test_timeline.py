"""Pod timeline plane tests (ISSUE 20): tick-granularity telemetry history,
bottleneck attribution, and the live top.

Covers the tentpole surface:

- knob defaults + ``to_dict`` coverage and the off-mode contract (``off``
  constructs no plane, ``/timeline`` answers ``enabled: false``);
- ``derive_point``: per-step rates, per-route qps/p99, stage p99 + busy
  share, engine phase split, flow/delivery/canary folds;
- the rotating OTLP-metrics-JSON segment sink: rotation to ``.1``, round-trip
  through ``read_segments``, torn-final-line crash survival;
- ``/timeline`` cursor endpoint: ``since`` strictly-newer + ``next`` resume
  token, single-``metric`` projection, ``step`` downsampling;
- the pod merge: per-metric sum/max/min rollup across peer rings fed by the
  heartbeat piggyback, retired peers dropping out (r17 discipline);
- bottleneck attribution: dominant stage / phase / backlog candidates ranked
  with knob advice;
- the r23 satellites: burn-rate ladder (ticket rung + in-place escalation),
  fabric link canaries feeding availability + the flap detector, pod-level
  incident bundles merged from per-process fragments;
- the seeded stall (r16 needle discipline): a 0.4 s injected stage delay
  makes the attributor name that stage and the pod bundle carry the lead-up
  window;
- the CLI: ``pathway_tpu top --once`` rendering from a live monitoring
  server and ``pathway_tpu timeline diff`` naming the worst-regressed phase.
"""

from __future__ import annotations

import json
import os
import socket
import threading
import time
import urllib.request
from collections import deque

import pytest

import pathway_tpu as pw
from pathway_tpu.observability import alerts as alerts_mod
from pathway_tpu.observability import bottleneck as bottleneck_mod
from pathway_tpu.observability import health as health_mod
from pathway_tpu.observability import timeline as timeline_mod

_TIMELINE_KNOBS = (
    "PATHWAY_TIMELINE",
    "PATHWAY_TIMELINE_WINDOW_S",
    "PATHWAY_TIMELINE_STEP_MS",
    "PATHWAY_TIMELINE_DIR",
    "PATHWAY_TIMELINE_ROTATE_MB",
    "PATHWAY_SLO_BURN_TICKET_FAST",
    "PATHWAY_SLO_BURN_TICKET_SLOW",
)


def _cfg():
    from pathway_tpu.internals.config import get_pathway_config

    return get_pathway_config()


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
    r = urllib.request.urlopen(req, timeout=timeout)
    return r.status, json.loads(r.read())


def _get_json(url: str, timeout: float = 15.0) -> dict:
    return json.loads(urllib.request.urlopen(url, timeout=timeout).read())


def _stop_run() -> None:
    rt = pw.internals.run.current_runtime()
    if rt is not None:
        rt.request_stop()


def _mk_plane(monkeypatch=None, runtime=None, **env) -> timeline_mod.TimelinePlane:
    """A bare (un-started) plane: tests drive ``sample_now`` by hand."""
    if monkeypatch is not None:
        for k, v in env.items():
            monkeypatch.setenv(k, v)
    return timeline_mod.TimelinePlane(_cfg(), runtime)


# ------------------------------------------------------------------- knobs


def test_knob_defaults_and_validation(monkeypatch):
    for k in _TIMELINE_KNOBS:
        monkeypatch.delenv(k, raising=False)
    cfg = _cfg()
    assert cfg.timeline == "on"
    assert cfg.timeline_window_s == 600.0
    assert cfg.timeline_step_ms == 1000.0
    assert cfg.timeline_dir is None
    assert cfg.timeline_rotate_mb == 32.0
    assert cfg.slo_burn_ticket_fast == 6.0
    assert cfg.slo_burn_ticket_slow == 1.0
    d = cfg.to_dict()
    for key in (
        "timeline",
        "timeline_window_s",
        "timeline_step_ms",
        "timeline_dir",
        "timeline_rotate_mb",
        "slo_burn_ticket_fast",
        "slo_burn_ticket_slow",
    ):
        assert key in d, key
    monkeypatch.setenv("PATHWAY_TIMELINE", "maybe")
    with pytest.raises(ValueError):
        cfg.timeline
    monkeypatch.setenv("PATHWAY_TIMELINE_STEP_MS", "5")
    assert cfg.timeline_step_ms == 100  # clamped: sub-100 ms cadence refused
    monkeypatch.setenv("PATHWAY_TIMELINE_ROTATE_MB", "0.0001")
    assert cfg.timeline_rotate_mb == 0.05


def test_off_mode_constructs_no_plane(monkeypatch):
    monkeypatch.setenv("PATHWAY_TIMELINE", "off")
    assert timeline_mod.install_from_env(None) is None
    assert timeline_mod.current() is None
    from pathway_tpu.internals.monitoring import _timeline_payload

    body = json.loads(_timeline_payload({}))
    assert body == {"enabled": False, "points": [], "next": None}


# ------------------------------------------------------------- derive_point


def _hist(counts_at: dict[int, int]) -> dict:
    from pathway_tpu.observability.metrics import BUCKET_BOUNDS_S

    counts = [0] * (len(BUCKET_BOUNDS_S) + 1)
    for i, n in counts_at.items():
        counts[i] = n
    return {"counts": counts, "sum_s": 0.0, "count": sum(counts)}


def test_derive_point_rates_and_quantiles():
    old = {
        "t": 100.0,
        "tick": 10,
        "rows_in": 1000,
        "rows_out": 500,
        "backlog": 0,
        "wm_lag_s": None,
        "sinks": {},
        "serving": {"/q": {"requests": 0, "responses": 0, "shed": 0,
                           "errors": 0, "timeouts": 0, "forwarded_out": 0,
                           "latency": _hist({})}},
        "stages": {"sweep/q": _hist({}), "serve/q": _hist({})},
        "phases": {"probe": 100.0},
        "flow": {"pressure": 0.0, "occupied": 0, "shed_rows": 0},
        "health": {"canary_failed": 0, "active": []},
    }
    slow = dict(_hist({11: 10}), sum_s=5.0)   # 10 requests in the 0.5 s bucket
    fast = dict(_hist({6: 90}), sum_s=1.0)    # 90 in the 15.6 ms bucket
    new = {
        "t": 110.0,
        "tick": 60,
        "rows_in": 2000,
        "rows_out": 1500,
        "backlog": 7,
        "wm_lag_s": 1.25,
        "sinks": {},
        "serving": {"/q": {"requests": 100, "responses": 100, "shed": 5,
                           "errors": 1, "timeouts": 2, "forwarded_out": 20,
                           "latency": _hist({11: 100})}},
        "stages": {"sweep/q": slow, "serve/q": fast},
        "phases": {"probe": 600.0},
        "flow": {"pressure": 0.5, "occupied": 3, "shed_rows": 10},
        "health": {"canary_failed": 2, "active": ["slo_latency_burn:/q"]},
    }
    p = timeline_mod.derive_point(new, old)
    assert p["t"] == 110.0 and p["tick"] == 60
    assert p["tick_rate"] == pytest.approx(5.0)
    assert p["rows_in_per_s"] == pytest.approx(100.0)
    assert p["rows_out_per_s"] == pytest.approx(100.0)
    assert p["backlog_rows"] == 7
    assert p["watermark_lag_s"] == pytest.approx(1.25)
    assert p["route_qps:/q"] == pytest.approx(10.0)
    assert p["route_p99_s:/q"] == pytest.approx(0.5)
    assert p["serve_qps"] == pytest.approx(10.0)
    assert p["serve_shed_per_s"] == pytest.approx(0.5)
    assert p["serve_forward_share"] == pytest.approx(0.2)
    # the slow stage dominates busy time: share 5/6, p99 at its bucket bound
    assert p["stage_p99_s:sweep/q"] == pytest.approx(0.5)
    assert p["stage_share:sweep/q"] == pytest.approx(5 / 6, abs=1e-3)
    assert p["stage_share:serve/q"] == pytest.approx(1 / 6, abs=1e-3)
    assert p["phase_ms:probe"] == pytest.approx(500.0)
    assert p["flow_pressure"] == pytest.approx(0.5)
    assert p["flow_shed_per_s"] == pytest.approx(1.0)
    assert p["canary_failed_per_s"] == pytest.approx(0.2)
    assert p["alerts_active"] == 1


# ------------------------------------------------------------ segment spill


def test_segment_sink_rotation_roundtrip_and_crash_survival(tmp_path):
    path = str(tmp_path / "timeline-p0.jsonl")
    sink = timeline_mod.TimelineSegmentSink(path, 0, rotate_bytes=1)  # min 4096
    n = 40
    for i in range(n):
        sink.write({"t": 1000.0 + i, "serve_qps": float(i), "tick": i})
    sink.close()
    assert os.path.exists(path + ".1"), "segment never rotated"
    # one rotation generation is kept: disk stays bounded and the MOST RECENT
    # contiguous window of points survives, in order, ending at the last write
    pts = timeline_mod.read_segments(str(tmp_path))
    assert pts, "rotated segments unreadable"
    ticks = [p["tick"] for p in pts]
    assert ticks[-1] == n - 1
    assert ticks == [ticks[0] + i for i in range(len(ticks))]
    assert len(pts) < n  # older generations were dropped, not accumulated
    # crash case: a torn final line (killed mid-write) must not lose the rest
    with open(path, "a", encoding="utf-8") as fh:
        fh.write('{"resourceMetrics": [{"resou')
    survived = timeline_mod.read_segments(str(tmp_path))
    assert [p["tick"] for p in survived] == ticks


def test_diff_summary_orders_worst_regression_first():
    a = [{"t": 1.0, "phase_ms:probe": 10.0, "phase_ms:kernel": 50.0,
          "stage_p99_s:sweep/q": 0.1}]
    b = [{"t": 2.0, "phase_ms:probe": 30.0, "phase_ms:kernel": 40.0,
          "stage_p99_s:sweep/q": 0.1}]
    rows = timeline_mod.diff_summary(a, b)
    assert rows[0]["metric"] == "phase_ms:probe"
    assert rows[0]["regression_pct"] == pytest.approx(200.0)
    assert rows[-1]["metric"] == "phase_ms:kernel"
    assert rows[-1]["regression_pct"] == pytest.approx(-20.0)


# --------------------------------------------------------- /timeline cursor


def test_timeline_endpoint_cursor_metric_and_step(monkeypatch):
    monkeypatch.setenv("PATHWAY_TIMELINE", "on")
    # a huge step keeps the background thread from interleaving samples
    monkeypatch.setenv("PATHWAY_TIMELINE_STEP_MS", "60000")
    from pathway_tpu.internals.monitoring import MonitoringHttpServer

    class RT:
        scheduler = None

    plane = timeline_mod.install_from_env(RT())
    try:
        for i in range(10):
            plane.points.append(
                {"t": 1000.0 + i, "serve_qps": float(i), "backlog_rows": i}
            )
        srv = MonitoringHttpServer(RT(), port=0).start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            body = _get_json(f"{base}/timeline")
            assert body["enabled"] is True
            assert body["proc"] == "0" and body["procs"] == ["0"]
            assert len(body["points"]) == 10
            assert {"serve_qps", "backlog_rows"} <= set(body["metrics"])
            assert body["next"] == pytest.approx(1009.0)
            # cursor: strictly newer than since, next resumes the scan
            page = _get_json(f"{base}/timeline?since={body['next'] - 3}")
            assert [p["t"] for p in page["points"]] == [1007.0, 1008.0, 1009.0]
            empty = _get_json(f"{base}/timeline?since={body['next']}")
            assert empty["points"] == [] and empty["next"] == pytest.approx(1009.0)
            # metric projection: {t, v} pairs only
            proj = _get_json(f"{base}/timeline?metric=serve_qps&since=1007.5")
            assert proj["points"] == [{"t": 1008.0, "v": 8.0},
                                      {"t": 1009.0, "v": 9.0}]
            # step downsampling: first point per 5 s bucket
            coarse = _get_json(f"{base}/timeline?step=5")
            assert [p["t"] for p in coarse["points"]] == [1000.0, 1005.0]
            # /status carries the plane summary
            status = _get_json(f"{base}/status")
            assert status["timeline"]["points"] == 10
            assert status["timeline"]["step_ms"] == 60000
        finally:
            srv.stop()
    finally:
        timeline_mod.shutdown()


# ---------------------------------------------------------------- pod merge


class _HB:
    def __init__(self):
        self.peers: dict[int, dict | None] = {}

    def peer_summaries(self):
        return dict(self.peers)


class _ClusterRT:
    scheduler = None

    def __init__(self):
        self.hb_monitor = _HB()


def test_pod_merge_rules_and_peer_retirement(monkeypatch):
    monkeypatch.setenv("PATHWAY_TIMELINE", "on")
    rt = _ClusterRT()
    plane = _mk_plane(runtime=rt)
    plane.points.append(
        {"t": 1000.0, "tick": 30, "serve_qps": 5.0,
         "route_p99_s:/q": 0.010, "phase_ms:probe": 100.0}
    )
    rt.hb_monitor.peers = {
        1: {"timeline": {"points": [
            {"t": 1000.1, "tick": 20, "serve_qps": 7.0,
             "route_p99_s:/q": 0.050, "phase_ms:probe": 40.0}
        ], "samples": 3, "last_t": 1000.1}}
    }
    plane._merge_peers()
    assert plane.procs() == ["0", "1"]
    pod = plane.pod_points()
    assert len(pod) == 1
    b = pod[0]
    assert b["procs"] == 2
    assert b["serve_qps"] == pytest.approx(12.0)        # rates sum
    assert b["route_p99_s:/q"] == pytest.approx(0.050)  # p99 = worst process
    assert b["tick"] == 20                              # frontier = slowest
    assert b["phase_ms:probe"] == pytest.approx(140.0)  # phase ms sum
    # the payload serves the merged rollup under proc=pod
    body = plane.payload({"proc": ["pod"]})
    assert body["proc"] == "pod" and body["points"][0]["procs"] == 2
    # retired peer (r17): gone from the monitor -> gone from the rollup
    rt.hb_monitor.peers = {}
    plane._merge_peers()
    assert plane.procs() == ["0"]
    assert plane.pod_points()[0]["procs"] == 1


def test_heartbeat_piggyback_and_cluster_rollup(monkeypatch):
    """aggregate.local_summary carries the compressed series block; the
    coordinator's cluster_status rolls reporting pids + sample counts up."""
    monkeypatch.setenv("PATHWAY_TIMELINE", "on")
    monkeypatch.setenv("PATHWAY_TIMELINE_STEP_MS", "60000")
    from pathway_tpu.observability import aggregate as agg_mod

    rt = _ClusterRT()
    plane = timeline_mod.install_from_env(rt)
    try:
        plane.points.append({"t": 1000.0, "serve_qps": 1.0})
        local = agg_mod.local_summary(rt)
        assert local["timeline"]["points"][-1]["serve_qps"] == 1.0
        assert local["timeline"]["samples"] == plane.samples_total
        rt.hb_monitor.peers = {
            1: {"timeline": {"points": [{"t": 1000.5, "serve_qps": 2.0}],
                             "samples": 9, "last_t": 1000.5}}
        }
        cluster = agg_mod.cluster_status(rt)
        assert cluster["timeline"]["reporting"] == ["0", "1"]
        assert cluster["timeline"]["samples"] == plane.samples_total + 9
        assert cluster["timeline"]["last_t"] == pytest.approx(1000.5)
    finally:
        timeline_mod.shutdown()


# ----------------------------------------------------- bottleneck attribution


def test_bottleneck_ranks_dominant_stage_with_knob(monkeypatch):
    monkeypatch.setenv("PATHWAY_TIMELINE", "on")
    plane = _mk_plane(runtime=None)
    slow = dict(_hist({11: 4}), sum_s=2.0)
    fast = dict(_hist({6: 40}), sum_s=0.4)
    plane._raws.append({"t": 100.0, "stages": {"sweep/q": _hist({}),
                                               "serve/q": _hist({})}})
    plane._raws.append({"t": 110.0, "stages": {"sweep/q": slow,
                                               "serve/q": fast}})
    verdict = bottleneck_mod.attribute(plane)
    top = verdict["top"]
    assert top["cause"] == "stage:sweep/q"
    assert top["score"] == pytest.approx(2.0 / 2.4, abs=1e-3)
    assert "sweep-bound" in top["verdict"]
    assert "profile the UDF" in top["knob"]


def test_bottleneck_phase_backlog_and_idle(monkeypatch):
    monkeypatch.setenv("PATHWAY_TIMELINE", "on")
    plane = _mk_plane(runtime=None)
    plane._raws.append({"t": 100.0, "phases": {"rehash": 0.0}, "backlog": 10})
    plane._raws.append({"t": 110.0, "phases": {"rehash": 8000.0}, "backlog": 500})
    verdict = bottleneck_mod.attribute(plane)
    causes = [c["cause"] for c in verdict["ranked"]]
    assert causes[0] == "phase:rehash"  # 80% busy outranks the small backlog
    assert verdict["top"]["evidence"]["busy_frac"] == pytest.approx(0.8)
    assert "ingest:backlog" not in causes or causes.index("ingest:backlog") > 0
    # idle pipeline: nothing scores, top is None
    idle = _mk_plane(runtime=None)
    idle._raws.append({"t": 100.0})
    idle._raws.append({"t": 110.0})
    v = bottleneck_mod.attribute(idle)
    assert v["top"] is None and v["ranked"] == []


# ------------------------------------------------------- burn-rate ladder


def _mk_sample(t, responses=0, timeouts=0, canary=None, hb_misses=0):
    from pathway_tpu.observability.metrics import BUCKET_BOUNDS_S

    counts = [0] * (len(BUCKET_BOUNDS_S) + 1)
    return {
        "t": t,
        "routes": {
            "/q": {
                "requests": 0,
                "responses": responses,
                "errors": 0,
                "timeouts": timeouts,
                "latency": {"counts": counts, "sum_s": 0.0, "count": 0},
            }
        },
        "canary": canary or {},
        "hb_misses": hb_misses,
    }


def test_burn_ladder_ticket_rung_then_escalates_to_page(monkeypatch):
    """A sustained burn between the ticket and page thresholds files a
    ticket-severity alert; crossing the page rung later upgrades the SAME
    active entry in place and never demotes."""
    monkeypatch.setenv("PATHWAY_SLO_AVAILABILITY", "0.999")
    plane = health_mod.HealthPlane(_cfg())
    plane.registry = alerts_mod.AlertRegistry(plane.cfg)
    samples = iter([
        _mk_sample(0.0),
        # 8/1000 failing: burn 8 on both windows -> >= ticket (6/1), < page (14)
        _mk_sample(30.0, responses=992, timeouts=8),
        # 20% failing: burn 200 -> page rung
        _mk_sample(31.0, responses=800, timeouts=200),
        # back to the ticket band: the page must STICK
        _mk_sample(32.0, responses=992, timeouts=8),
    ])
    monkeypatch.setattr(plane, "_sample", lambda: next(samples))
    plane.evaluate()
    plane.evaluate()
    (ent,) = plane.registry.active_alerts()
    assert ent["alert"] == "slo_availability_burn"
    assert ent["severity"] == "ticket"
    assert "ticket thresholds 6.0/1.0" in ent["summary"]
    plane.evaluate()
    (ent,) = plane.registry.active_alerts()
    assert ent["severity"] == "page"
    plane.evaluate()
    (ent,) = plane.registry.active_alerts()
    assert ent["severity"] == "page"  # never demoted while active
    assert plane.registry.fired_total == {"slo_availability_burn": 1}


# --------------------------------------------------- fabric link canaries


class _FabricNodeStub:
    def __init__(self, fail=False):
        self.fail = fail
        self.calls = []

    def call(self, peer, kind, payload, timeout=None):
        self.calls.append((peer, kind, payload))
        if self.fail:
            raise RuntimeError("link down")
        return {"ok": True, "pid": peer, "state": "ready", "from": payload.get("from")}


class _FabricPlaneStub:
    n_proc = 3
    pid = 0
    runtime = None

    def __init__(self, fail=False):
        self.node = _FabricNodeStub(fail)


def test_fabric_link_canaries_feed_slo_and_flap_detector(monkeypatch):
    from pathway_tpu import fabric as fabric_mod

    plane = health_mod.HealthPlane(_cfg())
    monkeypatch.setattr(fabric_mod, "_plane", _FabricPlaneStub(fail=False))
    plane._probe_fabric_links()
    assert [c[0] for c in fabric_mod._plane.node.calls] == [1, 2]
    assert plane.canary_total == {"fabric:p1": 1, "fabric:p2": 1}
    assert plane.canary_failed == {}
    # a rotting link: failures recorded per pseudo-route
    monkeypatch.setattr(fabric_mod, "_plane", _FabricPlaneStub(fail=True))
    plane._probe_fabric_links()
    assert plane.canary_failed == {"fabric:p1": 1, "fabric:p2": 1}
    # failed fabric canaries count as flaps even with zero heartbeat misses
    monkeypatch.setenv("PATHWAY_ALERT_HEARTBEAT_FLAPS", "3")
    det = health_mod.HealthPlane(_cfg())
    det._samples.append(_mk_sample(0.0, canary={"fabric:p1": (2, 0)}))
    det._samples.append(
        _mk_sample(10.0, responses=10, canary={"fabric:p1": (6, 3)})
    )
    names = {b["alert"] for b in det._detectors()}
    assert "heartbeat_flap" in names
    (flap,) = [b for b in det._detectors() if b["alert"] == "heartbeat_flap"]
    assert "3 fabric link canary failures" in flap["summary"]


def test_fabric_canary_req_handler_registered():
    """FabricPlane.install wires the ``canary`` request kind; the handler
    echoes ok + pid + door state without touching user-facing counters."""
    from pathway_tpu.fabric.routing import FabricPlane

    replies = []
    handler = FabricPlane._handle_canary
    stub = type("P", (), {"pid": 2})()
    handler(stub, {"from": 0}, replies.append)
    (reply,) = replies
    assert reply["ok"] is True and reply["pid"] == 2 and reply["from"] == 0


# -------------------------------------------------- pod incident bundles


def test_pod_bundle_merges_fragments_once_with_timeline_window(
    monkeypatch, tmp_path
):
    monkeypatch.setenv("PATHWAY_INCIDENT_DIR", str(tmp_path / "incidents"))
    monkeypatch.setenv("PATHWAY_TIMELINE", "on")
    monkeypatch.setenv("PATHWAY_TIMELINE_STEP_MS", "60000")
    alerts_mod._pod_bundled.clear()
    registry = alerts_mod.AlertRegistry(_cfg())
    now = time.time()
    registry.fragments.append(
        {"alert": "slo_latency_burn", "fingerprint": "/q", "severity": "ticket",
         "summary": "local", "fired_unix": now, "bundle": None, "process_id": 0}
    )
    rt = _ClusterRT()
    rt.hb_monitor.peers = {
        1: {"health": {"fragments": [
            {"alert": "slo_latency_burn", "fingerprint": "/q",
             "severity": "page", "summary": "peer", "fired_unix": now + 0.2,
             "bundle": "/tmp/x.json", "process_id": 1}
        ]}}
    }
    tplane = timeline_mod.install_from_env(rt)
    try:
        tplane.points.append({"t": now - 10.0, "serve_qps": 3.0})
        written = alerts_mod.merge_pod_bundles(rt, registry)
        assert len(written) == 1
        doc = json.loads(open(written[0]).read())
        assert doc["kind"] == "pathway_pod_incident_bundle"
        assert doc["alert"] == "slo_latency_burn"
        assert doc["severity"] == "page"  # max severity across processes
        assert doc["processes"] == [0, 1]
        assert [f["process_id"] for f in doc["fragments"]] == [0, 1]
        # the lead-up window rides along (points since first_fired - 120 s)
        assert doc["pod_timeline_window"][0]["serve_qps"] == 3.0
        # pod bundles never collide with per-process incident-* globs
        name = os.path.basename(written[0])
        assert name.startswith("pod-incident-slo_latency_burn-")
        assert "-page-" in name
        # same activation on the next sweep: deduped, nothing new written
        assert alerts_mod.merge_pod_bundles(rt, registry) == []
    finally:
        timeline_mod.shutdown()


# ------------------------------------------- seeded stall (e2e, the needle)


def test_seeded_stall_attribution_and_pod_bundle(monkeypatch, tmp_path):
    """The ISSUE 20 acceptance seed: a 0.4 s injected stage delay (r16
    needle discipline) makes the bottleneck attributor name that stage as
    the top cause, and the activation leaves exactly one pod-level incident
    bundle carrying the lead-up timeline window."""
    needle = "needle-313"
    port = _free_port()
    incidents = tmp_path / "incidents"
    monkeypatch.setenv("PATHWAY_HEALTH", "on")
    monkeypatch.setenv("PATHWAY_HEALTH_EVAL_MS", "100")
    monkeypatch.setenv("PATHWAY_CANARY_INTERVAL_MS", "0")
    monkeypatch.setenv("PATHWAY_INCIDENT_DIR", str(incidents))
    monkeypatch.setenv("PATHWAY_REQUEST_TRACE", "on")
    monkeypatch.setenv("PATHWAY_REQUEST_TRACE_SLOW_MS", "150")
    monkeypatch.setenv("PATHWAY_SERVE_COALESCE_MS", "2")
    monkeypatch.setenv("PATHWAY_TIMELINE", "on")
    monkeypatch.setenv("PATHWAY_TIMELINE_STEP_MS", "100")
    monkeypatch.setenv("PATHWAY_TIMELINE_DIR", str(tmp_path / "segments"))

    from pathway_tpu.internals.parse_graph import G

    health_mod.reset_slos()
    pw.set_slo(p99_ms=125.0)
    G.clear()
    queries, respond = pw.io.http.rest_connector(
        host="127.0.0.1", port=port, schema=pw.schema_from_types(query=str)
    )

    def work(q: str) -> str:
        if q == needle:
            time.sleep(0.4)  # the injected stage delay
        return q.upper()

    respond(queries.select(result=pw.apply(work, queries.query)))
    out: dict = {}

    def orchestrate() -> None:
        _wait_ready(port)
        for i in range(6):
            q = needle if i == 3 else f"q-{i}"
            _status, body = _post(f"http://127.0.0.1:{port}/", {"query": q})
            assert body == q.upper()
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            plane = timeline_mod.current()
            verdict = plane.bottleneck if plane is not None else None
            top = (verdict or {}).get("top")
            pod_bundles = list(incidents.glob("pod-incident-*.json"))
            if (
                top
                and top["cause"].startswith("stage:sweep/")
                and pod_bundles
            ):
                break
            time.sleep(0.05)
        plane = timeline_mod.current()
        out["verdict"] = dict(plane.bottleneck or {})
        out["status_bn"] = None
        from pathway_tpu.internals import monitoring as mon_mod

        rt = pw.internals.run.current_runtime()
        if rt is not None:
            out["status_bn"] = mon_mod.run_stats(rt).get("bottleneck")
        out["points"] = list(plane.points)
        _stop_run()

    th = threading.Thread(target=orchestrate)
    th.start()
    try:
        pw.run(monitoring_level="none")
    finally:
        th.join()
        G.clear()
        health_mod.reset_slos()

    top = (out["verdict"] or {}).get("top")
    assert top, f"attributor never produced a verdict: {out['verdict']}"
    # the injected stage dominates request time: the verdict NAMES it
    assert top["cause"].startswith("stage:sweep/"), top
    assert "sweep-bound" in top["verdict"]
    assert "profile the UDF" in top["knob"]
    # /status surfaces the same verdict
    assert out["status_bn"] and out["status_bn"]["top"]["cause"] == top["cause"]
    # exactly one pod-level bundle for the activation, lead-up attached
    pod_files = sorted(incidents.glob("pod-incident-slo_latency_burn-*.json"))
    assert len(pod_files) == 1, pod_files
    doc = json.loads(pod_files[0].read_text())
    assert doc["severity"] == "page"
    assert doc["processes"] == [0]
    # the bundle snapshots the verdict at fire time: a stage-bound cause
    # (the live verdict above converges on the exact injected stage)
    assert doc["bottleneck"]["top"]["cause"].startswith("stage:")
    # the per-process bundle also carries its local lead-up window
    (proc_file,) = incidents.glob("incident-slo_latency_burn-*.json")
    proc_doc = json.loads(proc_file.read_text())
    assert "timeline_window" in proc_doc
    # the recorder spilled segments for this run
    segs = timeline_mod.read_segments(str(tmp_path / "segments"))
    assert segs, "no timeline segments spilled"


# ------------------------------------------------------------------- CLI


def test_cli_render_top_and_timeline_diff(tmp_path):
    from click.testing import CliRunner

    from pathway_tpu.cli import cli, render_top

    status = {
        "health": {"doors": {"/q": "ready"}, "alerts": {"active": []}},
        "bottleneck": {"top": {"cause": "stage:sweep/q", "score": 0.83,
                               "verdict": "request sweep-bound",
                               "knob": "profile the UDF"}},
    }
    tl = {
        "proc": "pod",
        "procs": ["0", "1"],
        "metrics": ["serve_qps", "stage_p99_s:sweep/q", "phase_ms:probe"],
        "points": [
            {"t": 1.0, "serve_qps": 10.0, "stage_p99_s:sweep/q": 0.4,
             "phase_ms:probe": 12.0, "backlog_rows": 3},
            {"t": 2.0, "serve_qps": 20.0, "stage_p99_s:sweep/q": 0.5,
             "phase_ms:probe": 14.0, "backlog_rows": 5},
        ],
    }
    frame = render_top(status, tl)
    assert "proc pod of 2" in frame
    assert "qps     20.0" in frame
    assert "sweep/q" in frame and "500.0 ms" in frame
    assert "tick split: probe=14ms" in frame
    assert "bound by: stage:sweep/q" in frame
    assert "knob: profile the UDF" in frame

    # timeline diff: run B's probe phase 3x slower -> named worst
    dir_a, dir_b = tmp_path / "a", tmp_path / "b"
    for d, probe in ((dir_a, 10.0), (dir_b, 30.0)):
        sink = timeline_mod.TimelineSegmentSink(
            str(d / "timeline-p0.jsonl"), 0, rotate_bytes=1 << 20
        )
        sink.write({"t": 5.0, "phase_ms:probe": probe, "phase_ms:kernel": 5.0})
        sink.close()
    runner = CliRunner()
    res = runner.invoke(cli, ["timeline", "diff", str(dir_a), str(dir_b)])
    assert res.exit_code == 0, res.output
    assert "worst regression: phase_ms:probe (+200.0% vs run A)" in res.output


def test_cli_top_once_against_live_monitoring_server(monkeypatch):
    monkeypatch.setenv("PATHWAY_TIMELINE", "on")
    monkeypatch.setenv("PATHWAY_TIMELINE_STEP_MS", "60000")
    from click.testing import CliRunner

    from pathway_tpu.cli import cli
    from pathway_tpu.internals.monitoring import MonitoringHttpServer

    class RT:
        scheduler = None

    plane = timeline_mod.install_from_env(RT())
    try:
        plane.points.append({"t": 1000.0, "serve_qps": 42.0, "backlog_rows": 1})
        srv = MonitoringHttpServer(RT(), port=0).start()
        try:
            res = CliRunner().invoke(
                cli, ["top", "--port", str(srv.port), "--once"]
            )
            assert res.exit_code == 0, res.output
            assert "pathway_tpu top" in res.output
            assert "qps     42.0" in res.output
        finally:
            srv.stop()
    finally:
        timeline_mod.shutdown()


def test_cli_top_reports_disabled_plane(monkeypatch):
    monkeypatch.setenv("PATHWAY_TIMELINE", "off")
    from click.testing import CliRunner

    from pathway_tpu.cli import cli
    from pathway_tpu.internals.monitoring import MonitoringHttpServer

    class RT:
        scheduler = None

    timeline_mod.shutdown()
    srv = MonitoringHttpServer(RT(), port=0).start()
    try:
        res = CliRunner().invoke(cli, ["top", "--port", str(srv.port), "--once"])
        assert res.exit_code != 0
        assert "timeline plane is off" in res.output
    finally:
        srv.stop()
