"""Pod health & SLO plane tests (ISSUE 21): readiness doors, burn-rate
alerts, canary probes, incident bundles.

Covers the tentpole surface:

- the per-door state machine (starting → syncing → ready → draining →
  stopped) and its truthful ``/healthz`` / ``/readyz`` endpoints, including
  the off-mode degradation to unconditional 200s;
- notification sinks: dedupe on (alert, fingerprint), bounded retry with
  doubling backoff, the Slack sink's ``post_message`` delivery and the
  ``pw.io.slack.send_alerts`` fake-transport path;
- the alert registry: fire/refresh/resolve, detector-managed auto-resolution
  via ``sync``, the r10 recompile-storm tripwire unified into it;
- multi-window burn-rate evaluation over synthetic samples and the seeded
  end-to-end breach: a 0.4 s injected stage delay (r16 needle discipline)
  fires ``slo_latency_burn`` within the fast window and writes exactly ONE
  incident bundle naming the injected stage;
- canary exclusion: synthetic probes never touch user-facing counters;
- the monitoring server answering ``/alerts`` always and ``/status`` /
  ``/metrics`` with 503 + Retry-After while the pod quiesces;
- 2-process cluster e2e: a replica resync flips a door's ``/readyz`` to
  ``syncing`` and back; a ``/scale`` rescale drains every door (503 +
  ``Retry-After``) BEFORE the quiesce pause (exit-75); and (slow) SIGKILL +
  Supervisor relaunch re-enters ``starting``.
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
from pathway_tpu.observability import alerts as alerts_mod
from pathway_tpu.observability import health as health_mod
from conftest import free_port_base

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_HEALTH_KNOBS = (
    "PATHWAY_HEALTH",
    "PATHWAY_HEALTH_EVAL_MS",
    "PATHWAY_SLO_AVAILABILITY",
    "PATHWAY_SLO_P99_MS",
    "PATHWAY_SLO_FAST_WINDOW_S",
    "PATHWAY_SLO_SLOW_WINDOW_S",
    "PATHWAY_SLO_BURN_FAST",
    "PATHWAY_SLO_BURN_SLOW",
    "PATHWAY_CANARY_INTERVAL_MS",
    "PATHWAY_CANARY_TIMEOUT_MS",
    "PATHWAY_INCIDENT_DIR",
    "PATHWAY_ALERT_WEBHOOK",
    "PATHWAY_ALERT_SLACK_CHANNEL",
    "PATHWAY_ALERT_SLACK_TOKEN",
    "PATHWAY_ALERT_WATERMARK_STALL_S",
    "PATHWAY_ALERT_ERROR_RATE",
    "PATHWAY_ALERT_BACKLOG_ROWS",
    "PATHWAY_ALERT_THRASH_DECISIONS",
    "PATHWAY_ALERT_HEARTBEAT_FLAPS",
)


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


def _get(url: str, timeout: float = 15.0, headers: dict | None = None):
    """(status, parsed-or-text body, headers) — 4xx/5xx returned, not raised."""
    req = urllib.request.Request(url, headers=headers or {})
    try:
        r = urllib.request.urlopen(req, timeout=timeout)
        raw, hdrs, status = r.read().decode(), dict(r.headers), r.status
    except urllib.error.HTTPError as e:
        raw, hdrs, status = e.read().decode(), dict(e.headers), e.code
    try:
        body = json.loads(raw)
    except ValueError:
        body = raw
    return status, body, hdrs


def _post(url: str, payload: dict, timeout: float = 60.0, headers: dict | None = None):
    req = urllib.request.Request(
        url,
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        r = urllib.request.urlopen(req, timeout=timeout)
        return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _stop_run() -> None:
    rt = pw.internals.run.current_runtime()
    if rt is not None:
        rt.request_stop()


def _hdr(headers: dict, name: str):
    for k, v in headers.items():
        if k.lower() == name.lower():
            return v
    return None


# ------------------------------------------------------------------- knobs


def test_knob_defaults_and_validation(monkeypatch):
    for k in _HEALTH_KNOBS:
        monkeypatch.delenv(k, raising=False)
    from pathway_tpu.internals.config import get_pathway_config

    cfg = get_pathway_config()
    assert cfg.health == "on"
    assert cfg.health_eval_ms == 500.0
    assert cfg.slo_availability == 0.999
    assert cfg.slo_p99_ms == 0.0
    assert cfg.slo_fast_window_s == 60.0
    assert cfg.slo_slow_window_s == 600.0
    assert cfg.slo_burn_fast == 14.0
    assert cfg.slo_burn_slow == 2.0
    assert cfg.canary_interval_ms == 1000.0
    assert cfg.canary_timeout_ms == 2000.0
    assert cfg.incident_dir is None
    assert cfg.alert_webhook is None
    assert cfg.alert_slack_channel is None
    assert cfg.alert_slack_token is None
    assert cfg.alert_watermark_stall_s == 120.0
    assert cfg.alert_error_rate == 0.10
    assert cfg.alert_backlog_rows == 100000
    assert cfg.alert_thrash_decisions == 3
    assert cfg.alert_heartbeat_flaps == 3
    d = cfg.to_dict()
    for key in (
        "health",
        "slo_availability",
        "slo_burn_fast",
        "canary_interval_ms",
        "incident_dir",
        "alert_error_rate",
        "alert_heartbeat_flaps",
    ):
        assert key in d, key
    monkeypatch.setenv("PATHWAY_HEALTH", "maybe")
    with pytest.raises(ValueError):
        cfg.health
    monkeypatch.setenv("PATHWAY_SLO_AVAILABILITY", "1.5")
    with pytest.raises(ValueError):
        cfg.slo_availability


# ------------------------------------------------------------ state machine


def _cfg():
    from pathway_tpu.internals.config import get_pathway_config

    return get_pathway_config()


def test_door_state_machine_transitions():
    plane = health_mod.HealthPlane(_cfg())
    assert plane.door_state() == "starting"
    # syncing tokens on a starting door do not mask the phase
    plane.door_syncing(("ix", "/r", 1))
    assert plane.door_state() == "starting"
    plane.mark_ready()
    assert plane.door_state() == "syncing"  # token still live
    plane.door_synced(("ix", "/r", 1))
    assert plane.door_state() == "ready"
    # overlapping resyncs: the door is ready only when EVERY token drained
    plane.door_syncing("a")
    plane.door_syncing("b")
    plane.door_synced("a")
    assert plane.door_state() == "syncing"
    assert plane.syncing_tokens() == ["b"]
    plane.door_synced("b")
    assert plane.door_state() == "ready"
    # draining is sticky: ready never re-enters, the reason is kept
    plane.mark_draining("rescale")
    plane.mark_ready()
    assert plane.door_state() == "draining"
    assert plane.drain_reason() == "rescale"
    assert plane.quiescing()
    plane.mark_draining("other")  # first reason wins
    assert plane.drain_reason() == "rescale"
    plane.mark_stopped()
    assert plane.door_state() == "stopped" and plane.quiescing()
    states = [s for s, _t in plane.transitions]
    assert states == ["starting", "ready", "draining", "stopped"]


def test_healthz_readyz_payloads_and_off_mode(monkeypatch):
    # off: no plane — both endpoints degrade to unconditional 200
    monkeypatch.setattr(health_mod, "_plane", None)
    assert health_mod.healthz_payload() == (200, {"alive": True, "health": "off"})
    status, doc, hdrs = health_mod.readyz_payload()
    assert (status, doc, hdrs) == (200, {"ready": True, "health": "off"}, {})
    assert not health_mod.quiescing()
    health_mod.mark_ready()  # hooks are no-ops, never raise
    health_mod.mark_draining("x")
    health_mod.door_syncing("t")
    health_mod.door_synced("t")
    assert health_mod.status(None) is None
    assert health_mod.prometheus_lines(None) == []
    assert health_mod.heartbeat_summary() is None

    plane = health_mod.HealthPlane(_cfg())
    monkeypatch.setattr(health_mod, "_plane", plane)
    status, doc, hdrs = health_mod.readyz_payload()
    assert status == 503 and doc["state"] == "starting"
    assert hdrs["Retry-After"] == "1"
    plane.mark_ready()
    assert health_mod.readyz_payload()[0] == 200
    plane.door_syncing(("ix", "/v1", 0))
    status, doc, hdrs = health_mod.readyz_payload()
    assert status == 503 and doc["state"] == "syncing"
    assert any("/v1" in t for t in doc["syncing"])
    assert hdrs["Retry-After"] == "1"
    plane.door_synced(("ix", "/v1", 0))
    plane.mark_draining("rescale")
    status, doc, hdrs = health_mod.readyz_payload()
    assert status == 503 and doc["reason"] == "rescale"
    assert hdrs["Retry-After"] == "5"
    assert health_mod.healthz_payload()[0] == 200  # draining is still alive
    plane.mark_stopped()
    assert health_mod.healthz_payload()[0] == 503


def test_install_off_installs_nothing(monkeypatch):
    monkeypatch.setenv("PATHWAY_HEALTH", "off")
    try:
        assert health_mod.install_from_env(None) is None
        assert health_mod.current() is None
        assert alerts_mod.current() is None
        assert alerts_mod.install_from_env(None) is None
    finally:
        health_mod.shutdown()


def test_install_on_and_shutdown(monkeypatch):
    monkeypatch.setenv("PATHWAY_HEALTH", "on")
    monkeypatch.setenv("PATHWAY_CANARY_INTERVAL_MS", "0")
    monkeypatch.setenv("PATHWAY_HEALTH_EVAL_MS", "10000")
    try:
        plane = health_mod.install_from_env(None)
        assert plane is not None and health_mod.current() is plane
        assert plane.registry is alerts_mod.current()
        assert plane.registry is not None
    finally:
        health_mod.shutdown()
    assert health_mod.current() is None and alerts_mod.current() is None


# ------------------------------------------------------------------- sinks


def test_sink_retry_backoff_and_dedupe():
    calls: list[dict] = []
    fails = {"n": 2}

    def flaky(payload):
        if fails["n"] > 0:
            fails["n"] -= 1
            raise OSError("transient")
        calls.append(payload)

    sink = alerts_mod.NotificationSink(max_retries=3, backoff_s=0.2, transport=flaky)
    slept: list[float] = []
    sink._sleep = slept.append
    alert = {"alert": "disk_full", "fingerprint": "p0", "severity": "page",
             "summary": "disk 99%"}
    assert sink.notify(alert) is True
    assert len(calls) == 1 and calls[0]["alert"] == "disk_full"
    assert slept == [0.2, 0.4]  # doubling backoff between attempts
    # duplicate (alert, fingerprint): dropped without touching the transport
    assert sink.notify(dict(alert)) is False
    assert len(calls) == 1
    # a different fingerprint is a different incident
    assert sink.notify({**alert, "fingerprint": "p1"}) is True
    assert sink.counters() == {"sent": 2, "deduped": 1, "retries": 2, "failed": 0}

    # permanent failure: bounded attempts, counted, never raises
    dead = alerts_mod.NotificationSink(
        max_retries=2, backoff_s=0.1,
        transport=lambda p: (_ for _ in ()).throw(OSError("down")),
    )
    dead._sleep = lambda s: None
    assert dead.notify({"alert": "x", "fingerprint": ""}) is False
    assert dead.counters()["failed"] == 1 and dead.counters()["retries"] == 2


def test_slack_sink_formats_through_post_message(monkeypatch):
    import pathway_tpu.io.slack as slack_io

    posted: list[tuple] = []
    monkeypatch.setattr(
        slack_io, "post_message",
        lambda channel, token, text, transport=None: posted.append(
            (channel, token, text)
        ),
    )
    sink = alerts_mod.SlackSink("C042", "xoxb-test")
    sink.notify({"alert": "slo_latency_burn", "fingerprint": "/v1/retrieve",
                 "severity": "page", "summary": "burn 16.7"})
    assert posted == [(
        "C042", "xoxb-test",
        ":rotating_light: [page] slo_latency_burn (/v1/retrieve): burn 16.7",
    )]


def test_send_alerts_fake_transport():
    """`pw.io.slack.send_alerts` delivers one chat.postMessage per positive
    diff through the injectable transport — no network."""
    from pathway_tpu.internals.parse_graph import G

    sent: list[tuple] = []
    G.clear()
    t = pw.debug.table_from_rows(
        pw.schema_from_types(msg=str), [("backlog growing",), ("disk full",)]
    )
    pw.io.slack.send_alerts(
        t, "C0HEALTH", "xoxb-42",
        _transport=lambda url, headers, body: sent.append((url, headers, body)),
    )
    pw.run(monitoring_level="none")
    G.clear()
    assert len(sent) == 2
    for url, headers, body in sent:
        assert url == "https://slack.com/api/chat.postMessage"
        assert headers == {"Authorization": "Bearer xoxb-42"}
        assert body["channel"] == "C0HEALTH"
    assert {b["text"] for _u, _h, b in sent} == {"backlog growing", "disk full"}


def test_webhook_and_slack_sinks_from_env(monkeypatch):
    monkeypatch.setenv("PATHWAY_ALERT_WEBHOOK", "http://127.0.0.1:1/hook")
    monkeypatch.setenv("PATHWAY_ALERT_SLACK_CHANNEL", "C01")
    monkeypatch.setenv("PATHWAY_ALERT_SLACK_TOKEN", "tok")
    sinks = alerts_mod.AlertRegistry.sinks_from_env(_cfg())
    assert [s.name for s in sinks] == ["webhook", "slack"]
    assert sinks[0].url == "http://127.0.0.1:1/hook"
    assert (sinks[1].channel, sinks[1].token) == ("C01", "tok")
    monkeypatch.delenv("PATHWAY_ALERT_WEBHOOK")
    monkeypatch.delenv("PATHWAY_ALERT_SLACK_TOKEN")
    assert alerts_mod.AlertRegistry.sinks_from_env(_cfg()) == []


# ---------------------------------------------------------------- registry


def test_alert_registry_fire_refresh_resolve_sync():
    reg = alerts_mod.AlertRegistry(_cfg())
    sent: list[dict] = []
    reg.sinks = [alerts_mod.NotificationSink(transport=sent.append)]
    ent = reg.fire("watermark_stall", fingerprint="docs:0", summary="120s behind")
    assert ent["count"] == 1 and len(sent) == 1
    # refresh: same (alert, fingerprint) bumps count, no re-notification
    ent2 = reg.fire("watermark_stall", fingerprint="docs:0")
    assert ent2["count"] == 2 and len(sent) == 1
    assert reg.fired_total == {"watermark_stall": 1}
    lines = "\n".join(reg.prometheus_lines())
    assert 'pathway_alert_active{alert="watermark_stall",fingerprint="docs:0"} 1' in lines
    assert 'pathway_alerts_fired_total{alert="watermark_stall"} 1' in lines
    hb = reg.heartbeat_summary()
    assert hb["active"] == ["watermark_stall:docs:0"]
    assert hb["fired"] == 1
    # r23: the activation also leaves a pod-bundle fragment on the rollup
    (frag,) = hb["fragments"]
    assert frag["alert"] == "watermark_stall" and frag["fingerprint"] == "docs:0"
    assert reg.resolve("watermark_stall", "docs:0") is True
    assert reg.resolve("watermark_stall", "docs:0") is False
    summary = reg.status_summary()
    assert summary["active"] == []
    assert summary["recent_resolved"][-1]["alert"] == "watermark_stall"
    # sync: detector-managed alerts fire on breach, auto-resolve on recovery
    reg.sync([{"alert": "error_rate_spike", "fingerprint": "/q", "summary": "x"}])
    assert [e["alert"] for e in reg.active_alerts()] == ["error_rate_spike"]
    reg.sync([])
    assert reg.active_alerts() == []
    assert reg.fired_total["error_rate_spike"] == 1


def test_recompile_storm_unified_into_registry(monkeypatch):
    """Satellite r10 unification: the device plane's recompile-storm tripwire
    fires into the SAME registry, non-auto (sync sweeps never resolve it)."""
    monkeypatch.setenv("PATHWAY_HEALTH", "on")
    from pathway_tpu.observability import device as device_mod

    try:
        reg = alerts_mod.install_from_env(None)
        assert reg is not None
        device_mod._storm_alert("embed@f32[8,16]", ["f32[8,16]", "f32[9,16]"])
        active = reg.active_alerts()
        assert [e["alert"] for e in active] == ["recompile_storm"]
        assert active[0]["fingerprint"] == "embed@f32[8,16]"
        assert active[0]["auto"] is False
        # a detector sweep with no breaches must NOT resolve the storm alert
        reg.sync([])
        assert [e["alert"] for e in reg.active_alerts()] == ["recompile_storm"]
        # flight snapshot is exposed for bundles
        snap = device_mod.flight_snapshot()
        assert isinstance(snap, dict) and "events" in snap
    finally:
        alerts_mod.shutdown()


# ----------------------------------------------------- burn-rate evaluation


def _mk_sample(t, responses=0, timeouts=0, requests=0, errors=0,
               slow_count=0, fast_count=0, canary=None, hb_misses=0):
    """One synthetic evaluator sample for route /q: ``fast_count`` requests in
    the 2^-6 s bucket (15.6 ms), ``slow_count`` in the 2^-1 s bucket (500 ms)."""
    from pathway_tpu.observability.metrics import BUCKET_BOUNDS_S

    counts = [0] * (len(BUCKET_BOUNDS_S) + 1)
    counts[6] = fast_count  # bound 2^-6 = 15.6 ms
    counts[11] = slow_count  # bound 2^-1 = 0.5 s
    return {
        "t": t,
        "routes": {
            "/q": {
                "requests": requests,
                "responses": responses,
                "errors": errors,
                "timeouts": timeouts,
                "latency": {"counts": counts, "sum_s": 0.0, "count": sum(counts)},
            }
        },
        "canary": canary or {},
        "hb_misses": hb_misses,
    }


def test_burn_rate_breach_fires_resolves_and_bundles_once(monkeypatch, tmp_path):
    """Availability burn over synthetic samples: both windows over threshold
    fires ``slo_availability_burn`` (severity page), a refresh does NOT write
    a second bundle, and recovery auto-resolves through ``sync``."""
    monkeypatch.setenv("PATHWAY_SLO_AVAILABILITY", "0.999")
    monkeypatch.setenv("PATHWAY_INCIDENT_DIR", str(tmp_path / "incidents"))
    plane = health_mod.HealthPlane(_cfg())
    plane.registry = alerts_mod.AlertRegistry(plane.cfg)
    samples = iter([
        _mk_sample(0.0),
        _mk_sample(100.0, responses=80, timeouts=20),  # 20% failing
        _mk_sample(101.0, responses=80, timeouts=20),  # unchanged: refresh
        _mk_sample(200.0, responses=80, timeouts=20),  # recovered window
    ])
    monkeypatch.setattr(plane, "_sample", lambda: next(samples))

    breaches = [plane.evaluate() for _ in range(2)][-1] and None
    # after two evals the breach is active: burn = 0.2 / 0.001 = 200
    assert plane.burn["availability"]["fast"] == pytest.approx(200.0)
    assert plane.burn["availability"]["slow"] == pytest.approx(200.0)
    assert plane.budget_remaining["availability"] == 0.0
    active = plane.registry.active_alerts()
    assert [e["alert"] for e in active] == ["slo_availability_burn"]
    assert active[0]["severity"] == "page"
    bundles = list((tmp_path / "incidents").glob("incident-*.json"))
    assert len(bundles) == 1, bundles  # one activation = one bundle
    doc = json.loads(bundles[0].read_text())
    assert doc["kind"] == "pathway_incident_bundle"
    assert doc["alert"]["alert"] == "slo_availability_burn"
    assert "flight" in doc
    # refresh (third eval, condition still true): count bumps, no new bundle
    plane.evaluate()
    assert plane.registry.active_alerts()[0]["count"] >= 2
    assert len(list((tmp_path / "incidents").glob("incident-*.json"))) == 1
    # recovery (fourth eval: zero deltas in the fast window) auto-resolves
    plane.evaluate()
    assert plane.registry.active_alerts() == []
    assert plane.registry.fired_total == {"slo_availability_burn": 1}


def test_latency_burn_and_canary_availability(monkeypatch):
    """Latency burn counts the fraction of requests over the p99 objective
    against the 1% the objective allows; failed canaries feed availability
    even with zero organic traffic."""
    monkeypatch.setenv("PATHWAY_SLO_AVAILABILITY", "0.99")
    health_mod.reset_slos()
    try:
        pw.set_slo(route="/q", p99_ms=100.0)
        plane = health_mod.HealthPlane(_cfg())
        plane._samples.append(_mk_sample(0.0))
        plane._samples.append(
            # 5 of 50 over 100 ms -> burn (0.1)/0.01 = 10
            _mk_sample(100.0, responses=50, fast_count=45, slow_count=5)
        )
        burns = plane._window_burns(60.0)
        assert burns["latency:/q"] == pytest.approx(10.0)
        # canaries-only traffic: 2 of 10 probes failing vs 1% budget
        plane2 = health_mod.HealthPlane(_cfg())
        plane2._samples.append(_mk_sample(0.0))
        plane2._samples.append(_mk_sample(100.0, canary={"/q": (10, 2)}))
        burns2 = plane2._window_burns(60.0)
        assert burns2["availability"] == pytest.approx((2 / 10) / 0.01)
    finally:
        health_mod.reset_slos()


def test_detectors_error_rate_and_heartbeat_flap(monkeypatch):
    monkeypatch.setenv("PATHWAY_ALERT_ERROR_RATE", "0.10")
    monkeypatch.setenv("PATHWAY_ALERT_HEARTBEAT_FLAPS", "3")
    plane = health_mod.HealthPlane(_cfg())
    plane._samples.append(_mk_sample(0.0))
    plane._samples.append(
        _mk_sample(10.0, requests=40, responses=30, errors=8, timeouts=2,
                   hb_misses=4)
    )
    names = {b["alert"] for b in plane._detectors()}
    assert "error_rate_spike" in names
    assert "heartbeat_flap" in names
    # below both thresholds: clean sweep
    plane2 = health_mod.HealthPlane(_cfg())
    plane2._samples.append(_mk_sample(0.0))
    plane2._samples.append(_mk_sample(10.0, requests=40, responses=40))
    assert plane2._detectors() == []


def test_set_slo_declarations_override_env(monkeypatch):
    monkeypatch.setenv("PATHWAY_SLO_AVAILABILITY", "0.999")
    monkeypatch.setenv("PATHWAY_SLO_P99_MS", "250")
    health_mod.reset_slos()
    try:
        plane = health_mod.HealthPlane(_cfg())
        avail, p99 = plane._objectives()
        assert avail == 0.999 and p99 == {None: 250.0}
        pw.set_slo(route="/v1", p99_ms=50, availability=0.995)
        avail, p99 = plane._objectives()
        assert avail == 0.995 and p99 == {"/v1": 50.0}
    finally:
        health_mod.reset_slos()


# --------------------------------------------- seeded SLO breach (e2e, r16)


def test_seeded_slo_breach_fires_within_fast_window_and_bundles(
    monkeypatch, tmp_path
):
    """The acceptance seed: 6 served requests, one delayed 0.4 s by an
    injected stage delay (r16 needle discipline), p99 objective 125 ms —
    the latency burn (>=16.7x on both windows) fires ``slo_latency_burn``
    within the fast window and writes exactly ONE incident bundle whose
    probable-cause stage is the injected engine stage."""
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
            _status, body, _h = _post(f"http://127.0.0.1:{port}/", {"query": q})
            assert body == q.upper()
        # the evaluator thread (100 ms cadence) must fire within seconds —
        # far inside the 60 s fast window
        registry = alerts_mod.current()
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            if any(
                e["alert"] == "slo_latency_burn" for e in registry.active_alerts()
            ):
                break
            time.sleep(0.05)
        out["active"] = registry.active_alerts()
        out["fired_total"] = dict(registry.fired_total)
        out["bundles"] = list(registry.bundle_paths)
        out["slo"] = health_mod.current().slo_snapshot()
        _stop_run()

    th = threading.Thread(target=orchestrate)
    th.start()
    try:
        pw.run(monitoring_level="none")
    finally:
        th.join()
        G.clear()
        health_mod.reset_slos()

    burn_alerts = [e for e in out["active"] if e["alert"] == "slo_latency_burn"]
    assert burn_alerts, f"burn alert never fired: {out}"
    alert = burn_alerts[0]
    assert alert["fingerprint"] == "/"
    assert alert["severity"] == "page"
    burn = out["slo"]["burn"]["latency:/"]
    assert burn["fast"] >= 14.0 and burn["slow"] >= 2.0, burn
    # exactly one bundle for the activation, naming the injected stage
    assert out["fired_total"].get("slo_latency_burn") == 1
    files = sorted(incidents.glob("incident-slo_latency_burn-*.json"))
    assert len(files) == 1, files
    doc = json.loads(files[0].read_text())
    assert doc["alert"]["alert"] == "slo_latency_burn"
    stage = doc["probable_cause_stage"]
    assert stage and stage.startswith("sweep/"), doc.get("probable_cause_stage")
    # the bundle correlates the r16 exemplars: the slowest carries the stall
    assert doc["slowest_requests"]
    assert doc["slowest_requests"][0]["duration_ms"] >= 380


# --------------------------------- canary exclusion + endpoints + quiescing


def test_canary_exclusion_door_endpoints_and_quiesce_503(monkeypatch):
    """One serving run covers: background canaries probing the door while
    user-facing counters count ONLY organic traffic; /healthz + /readyz on
    the door webserver and the monitoring server; /alerts always answering;
    and the quiesce gate — once the pod drains, /status and /metrics answer
    503 + Retry-After while /healthz and /alerts stay up."""
    port = _free_port()
    mon_port = _free_port()
    monkeypatch.setenv("PATHWAY_HEALTH", "on")
    monkeypatch.setenv("PATHWAY_HEALTH_EVAL_MS", "100")
    monkeypatch.setenv("PATHWAY_CANARY_INTERVAL_MS", "50")
    monkeypatch.setenv("PATHWAY_MONITORING_HTTP_PORT", str(mon_port))

    from pathway_tpu.internals.parse_graph import G

    G.clear()
    queries, respond = pw.io.http.rest_connector(
        host="127.0.0.1", port=port, schema=pw.schema_from_types(query=str)
    )
    respond(queries.select(result=pw.apply(str.upper, queries.query)))
    out: dict = {}

    def orchestrate() -> None:
        from pathway_tpu.io.http import _server as srv_mod

        _wait_ready(port)
        rt = pw.internals.run.current_runtime()
        for i in range(3):
            _post(f"http://127.0.0.1:{port}/", {"query": f"q{i}"})
        # let the 50 ms background canary probe the door repeatedly
        time.sleep(1.0)
        plane = health_mod.current()
        route_state = next(
            rs for rs in list(srv_mod._ROUTES)
            if rs.route == "/" and rs.runtime is rt
        )
        out["requests_total"] = route_state.requests_total
        out["canary"] = plane.canary_snapshot()
        # a tagged probe by hand: short-circuits at the door
        before = route_state.requests_total
        status, doc, _h = _post(
            f"http://127.0.0.1:{port}/", {}, headers={"X-Pathway-Canary": "1"}
        )
        out["manual_canary"] = (status, doc)
        out["counter_after_manual"] = route_state.requests_total - before
        out["door_healthz"] = _get(f"http://127.0.0.1:{port}/healthz")
        out["door_readyz"] = _get(f"http://127.0.0.1:{port}/readyz")
        out["mon_healthz"] = _get(f"http://127.0.0.1:{mon_port}/healthz")
        out["mon_readyz"] = _get(f"http://127.0.0.1:{mon_port}/readyz")
        out["mon_alerts"] = _get(f"http://127.0.0.1:{mon_port}/alerts")
        out["mon_status_ok"] = _get(f"http://127.0.0.1:{mon_port}/status")
        out["metrics_text"] = _get(f"http://127.0.0.1:{mon_port}/metrics")[1]
        # quiesce: drain the pod, monitoring answers 503 like the doors
        plane.mark_draining("rescale")
        out["status_draining"] = _get(f"http://127.0.0.1:{mon_port}/status")
        out["metrics_draining"] = _get(f"http://127.0.0.1:{mon_port}/metrics")
        out["readyz_draining"] = _get(f"http://127.0.0.1:{port}/readyz")
        out["alerts_draining"] = _get(f"http://127.0.0.1:{mon_port}/alerts")
        out["healthz_draining"] = _get(f"http://127.0.0.1:{mon_port}/healthz")
        _stop_run()

    th = threading.Thread(target=orchestrate)
    th.start()
    try:
        pw.run(monitoring_level="none", with_http_server=True)
    finally:
        th.join()
        G.clear()

    # canaries ran (>=5 in the 1 s window) but NEVER count as traffic
    assert out["requests_total"] == 3, out
    assert out["canary"]["/"]["requests"] >= 5, out["canary"]
    assert out["canary"]["/"]["failed"] == 0
    status, doc = out["manual_canary"]
    assert status == 200 and doc == {"canary": True, "state": "ready", "route": "/"}
    assert out["counter_after_manual"] == 0
    # doors and monitoring server both answer the health endpoints
    assert out["door_healthz"][0] == 200 and out["door_healthz"][1]["alive"]
    assert out["door_readyz"][0] == 200 and out["door_readyz"][1]["ready"]
    assert out["mon_healthz"][0] == 200
    assert out["mon_readyz"][0] == 200
    assert out["mon_alerts"][0] == 200 and out["mon_alerts"][1]["ok"] is True
    assert out["mon_status_ok"][0] == 200
    assert out["mon_status_ok"][1]["health"]["state"] == "ready"
    # /metrics carries the new series
    metrics = out["metrics_text"]
    assert "pathway_door_ready 1" in metrics
    assert 'pathway_door_state{state="ready"} 1' in metrics
    assert 'pathway_slo_target{slo="availability"}' in metrics
    assert 'pathway_canary_requests_total{route="/"}' in metrics
    # quiescing: 503 + Retry-After on /status and /metrics, doors drain too
    assert out["status_draining"][0] == 503
    assert out["status_draining"][1]["reason"] == "rescale"
    assert _hdr(out["status_draining"][2], "Retry-After") == "5"
    assert out["metrics_draining"][0] == 503
    assert out["readyz_draining"][0] == 503
    assert out["readyz_draining"][1]["reason"] == "rescale"
    assert _hdr(out["readyz_draining"][2], "Retry-After") == "5"
    # liveness and the alert feed survive the drain window
    assert out["alerts_draining"][0] == 200
    assert out["healthz_draining"][0] == 200


def test_health_off_serving_path_unchanged(monkeypatch):
    """PATHWAY_HEALTH=off: no plane, no canaries, no evaluator thread — the
    door answers exactly like r20 (and /healthz degrades to a plain 200)."""
    port = _free_port()
    monkeypatch.setenv("PATHWAY_HEALTH", "off")

    from pathway_tpu.internals.parse_graph import G

    G.clear()
    queries, respond = pw.io.http.rest_connector(
        host="127.0.0.1", port=port, schema=pw.schema_from_types(query=str)
    )
    respond(queries.select(result=pw.apply(str.upper, queries.query)))
    out: dict = {}

    def orchestrate() -> None:
        _wait_ready(port)
        assert health_mod.current() is None
        assert alerts_mod.current() is None
        out["resp"] = _post(f"http://127.0.0.1:{port}/", {"query": "abc"})
        # the canary header is inert when the plane is off: a normal request
        out["tagged"] = _post(
            f"http://127.0.0.1:{port}/", {"query": "def"},
            headers={"X-Pathway-Canary": "1"},
        )
        out["healthz"] = _get(f"http://127.0.0.1:{port}/healthz")
        out["readyz"] = _get(f"http://127.0.0.1:{port}/readyz")
        no_health_threads = not any(
            t.name == "pathway-health" for t in threading.enumerate()
        )
        out["no_threads"] = no_health_threads
        _stop_run()

    th = threading.Thread(target=orchestrate)
    th.start()
    try:
        pw.run(monitoring_level="none")
    finally:
        th.join()
        G.clear()

    assert out["resp"][1] == "ABC"
    assert out["tagged"][1] == "DEF"  # engine answered: header ignored
    assert out["healthz"] == (200, {"alive": True, "health": "off"}, out["healthz"][2])
    assert out["readyz"][0] == 200 and out["readyz"][1]["health"] == "off"
    assert out["no_threads"]


# ----------------------------------------------- cluster e2e: gap -> resync

_GAP_CLUSTER_SCRIPT = textwrap.dedent(
    """
    import json, os, socket, sys, threading, time, urllib.error, urllib.request
    import pathway_tpu as pw
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm import DocumentStore
    from pathway_tpu.xpacks.llm.mocks import FakeEmbedder
    from pathway_tpu.xpacks.llm.servers import DocumentStoreServer

    port = int(sys.argv[1])
    tmp = sys.argv[2]
    pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
    n_proc = int(os.environ.get("PATHWAY_PROCESSES", "1"))
    stride = int(os.environ.get("PATHWAY_FABRIC_PORT_STRIDE", "1"))
    mon_base = int(os.environ.get("PATHWAY_MONITORING_HTTP_PORT", "0"))

    docs = pw.debug.table_from_rows(
        pw.schema_from_types(data=str),
        [(f"steady doc {i:02d} omega",) for i in range(10)],
    )
    store = DocumentStore(
        docs,
        retriever_factory=BruteForceKnnFactory(embedder=FakeEmbedder(dimension=16)),
    )
    DocumentStoreServer("127.0.0.1", port, store)

    def get(url):
        try:
            r = urllib.request.urlopen(url, timeout=10)
            return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())
        except Exception as e:
            return -1, {"error": str(e)}

    def wait_tcp(p, timeout=60.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                socket.create_connection(("127.0.0.1", p), timeout=0.5).close()
                return
            except OSError:
                time.sleep(0.05)
        raise TimeoutError(p)

    if pid == 1:
        def induce():
            my_port = port + pid * stride
            while not os.path.exists(os.path.join(tmp, "go")):
                time.sleep(0.1)
            from pathway_tpu import fabric as _fabric
            obs = {}
            fp = _fabric.current()
            ir = fp._index_routes.get("/v1/retrieve")
            token = ("ix", "/v1/retrieve", 0)
            deadline = time.monotonic() + 20
            while (token in fp._resyncing or get(
                f"http://127.0.0.1:{my_port}/readyz")[1].get("state") != "ready"
            ) and time.monotonic() < deadline:
                time.sleep(0.1)
            obs["before"] = get(f"http://127.0.0.1:{my_port}/readyz")
            orig = fp.node.call
            def slow_call(dst, kind, payload, **kw):
                if kind == "index_snapshot":
                    time.sleep(1.2)  # hold the resync window open
                return orig(dst, kind, payload, **kw)
            fp.node.call = slow_call
            fp._resync_index(ir, 0)  # the induced gap's resync pull
            seen_syncing = None
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                st, doc = get(f"http://127.0.0.1:{my_port}/readyz")
                if doc.get("state") == "syncing":
                    seen_syncing = (st, doc)
                    break
                time.sleep(0.02)
            obs["during"] = seen_syncing
            obs["healthz_during"] = get(f"http://127.0.0.1:{my_port}/healthz")
            back = None
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                st, doc = get(f"http://127.0.0.1:{my_port}/readyz")
                if st == 200 and doc.get("state") == "ready":
                    back = (st, doc)
                    break
                time.sleep(0.05)
            obs["after"] = back
            fp.node.call = orig
            # a tagged canary against the peer MIRROR door must short-circuit
            # at the state machine: no forward to the owner (an empty payload
            # would crash the engine as a query row), no counter bump
            from pathway_tpu.io.http import _server as _srv
            rs = None
            for ws in list(_srv._WEBSERVERS):
                for route, _m, _h, meta in ws._routes:
                    if route == "/v1/retrieve" and (meta or {}).get("serving"):
                        rs = meta["serving"]
            before = rs.requests_total
            req = urllib.request.Request(
                f"http://127.0.0.1:{my_port}/v1/retrieve", data=b"{}",
                method="POST",
                headers={"X-Pathway-Canary": "1",
                         "Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=10) as resp:
                obs["canary_door"] = json.loads(resp.read())
            obs["canary_counter_delta"] = rs.requests_total - before
            print("PEER:" + json.dumps(obs), flush=True)
            with open(os.path.join(tmp, "peer_done"), "w") as fh:
                fh.write("1")
        threading.Thread(target=induce, daemon=True).start()

    if pid == 0:
        def client():
            doors = [port + i * stride for i in range(n_proc)]
            for p in doors:
                wait_tcp(p)
            out = {"ready": {}, "healthz": {}}
            for p in doors:
                deadline = time.monotonic() + 40
                got = None
                while time.monotonic() < deadline:
                    got = get(f"http://127.0.0.1:{p}/readyz")
                    if got[0] == 200 and got[1].get("state") == "ready":
                        break
                    time.sleep(0.1)
                out["ready"][str(p)] = got
                out["healthz"][str(p)] = get(f"http://127.0.0.1:{p}/healthz")
            with open(os.path.join(tmp, "go"), "w") as fh:
                fh.write("1")
            deadline = time.monotonic() + 60
            while (not os.path.exists(os.path.join(tmp, "peer_done"))
                   and time.monotonic() < deadline):
                time.sleep(0.1)
            out["peer_done"] = os.path.exists(os.path.join(tmp, "peer_done"))
            # coordinator rollup: both doors report their state pod-wide
            rollup = None
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                st, doc = get(f"http://127.0.0.1:{mon_base}/status")
                h = (doc.get("cluster") or {}).get("health") if st == 200 else None
                if h and len(h.get("doors", {})) == n_proc and h["all_ready"]:
                    rollup = h
                    break
                time.sleep(0.5)
            out["rollup"] = rollup
            st, doc = get(f"http://127.0.0.1:{mon_base}/status")
            out["self_health"] = (doc.get("health") or {}).get("state")
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


def _spawn_cluster(script_path, argv_tail, n_proc, extra_env, timeout=240,
                   first_port=None, ok_codes=(0,)):
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
            [sys.executable, str(script_path), *argv_tail],
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
        assert p.returncode in ok_codes, (
            f"process exited {p.returncode} (wanted {ok_codes}):\n{txt}"
        )
    return procs, outputs


def _marked(outputs: list[str], marker: str):
    for txt in outputs:
        for line in txt.splitlines():
            if line.startswith(marker):
                return json.loads(line[len(marker):])
    return None


def test_cluster_replica_gap_flips_readyz_to_syncing_and_back(tmp_path):
    """Acceptance: on a 2-process fabric cluster, an induced replica resync
    (the gap-recovery pull through ``_resync_index``) flips the peer door's
    ``/readyz`` to 503 ``syncing`` — naming the route token — and back to
    200 ``ready`` once the snapshot lands; liveness stays 200 throughout,
    and the coordinator /status rolls every door's state up pod-wide."""
    script = tmp_path / "gap_cluster.py"
    script.write_text(_GAP_CLUSTER_SCRIPT)
    block = free_port_base(3 + 7)
    mon_base = block
    http_port = _free_port()
    procs, outputs = _spawn_cluster(
        script,
        [str(http_port), str(tmp_path)],
        2,
        {
            "PATHWAY_FABRIC": "on",
            "PATHWAY_HEALTH": "on",
            "PATHWAY_CANARY_INTERVAL_MS": "0",
            "PATHWAY_REPLICA_MAX_STALENESS_MS": "60000",
            "PATHWAY_MONITORING_HTTP_PORT": str(mon_base),
        },
        first_port=block + 3,
    )
    result = _marked(outputs, "RESULT:")
    peer = _marked(outputs, "PEER:")
    assert result is not None, outputs[0]
    assert peer is not None, outputs[1]
    # both doors reached ready and answer liveness
    for _door, got in result["ready"].items():
        assert got[0] == 200 and got[1]["state"] == "ready", result["ready"]
    for _door, got in result["healthz"].items():
        assert got[0] == 200 and got[1]["alive"], result["healthz"]
    assert result["peer_done"]
    # the induced resync window: 503 syncing naming the route token
    assert peer["before"][0] == 200, peer
    assert peer["during"] is not None, f"door never showed syncing: {peer}"
    st, doc = peer["during"]
    assert st == 503 and doc["state"] == "syncing"
    assert any("/v1/retrieve" in t for t in doc["syncing"]), doc
    # alive while syncing; ready again once the snapshot lands
    assert peer["healthz_during"][0] == 200
    assert peer["after"] is not None and peer["after"][0] == 200, peer
    # coordinator rollup saw both doors
    assert result["rollup"] is not None, result
    assert result["rollup"]["all_ready"] is True
    assert set(result["rollup"]["doors"]) == {"0", "1"}
    assert result["self_health"] == "ready"
    # a tagged canary at the peer MIRROR door short-circuits at the state
    # machine (never forwarded to the owner's engine, never counted)
    assert peer["canary_door"]["canary"] is True, peer
    assert peer["canary_door"]["state"] == "ready"
    assert peer["canary_counter_delta"] == 0


# ------------------------------------------- cluster e2e: rescale quiesce

_RESCALE_CLUSTER_SCRIPT = textwrap.dedent(
    """
    import json, os, sys, threading, time, urllib.error, urllib.request
    import pathway_tpu as pw
    from pathway_tpu.io.kafka import MockKafkaBroker
    from pathway_tpu.observability import health as _health

    tmp = sys.argv[1]
    port = int(sys.argv[2])
    pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
    mon_base = int(os.environ["PATHWAY_MONITORING_HTTP_PORT"])
    my_mon = mon_base + pid

    broker = MockKafkaBroker(path=os.environ["BROKER_PATH"])
    words = pw.io.kafka.read(
        broker, "words", format="plaintext", mode="streaming", name="words"
    )
    counts = words.groupby(words.data).reduce(words.data, c=pw.reducers.count())
    pw.io.subscribe(counts, on_change=lambda *a, **k: None)
    if pid == 0:
        queries, respond = pw.io.http.rest_connector(
            host="127.0.0.1", port=port, schema=pw.schema_from_types(q=str)
        )
        respond(queries.select(result=queries.q))

    def get(url):
        try:
            r = urllib.request.urlopen(url, timeout=10)
            return [r.status, r.read().decode(), dict(r.headers)]
        except urllib.error.HTTPError as e:
            return [e.code, e.read().decode(), dict(e.headers)]
        except Exception as e:
            return [-1, str(e), {}]

    rec = {"captured": False}

    def on_tick(_t):
        # runs ON the engine thread: after the rescale decision marks the
        # pod draining, the drain tick fires this BEFORE close() — the doors
        # and monitoring servers are still up, so the 503s are observable
        if rec["captured"] or not _health.quiescing():
            return
        rec["captured"] = True
        obs = {
            "state": _health.current().door_state(),
            "reason": _health.current().drain_reason(),
            "status": get(f"http://127.0.0.1:{my_mon}/status"),
            "metrics": get(f"http://127.0.0.1:{my_mon}/metrics"),
            "healthz": get(f"http://127.0.0.1:{my_mon}/healthz"),
            "readyz": get(f"http://127.0.0.1:{my_mon}/readyz"),
            "alerts": get(f"http://127.0.0.1:{my_mon}/alerts"),
        }
        if pid == 0:
            obs["door_readyz"] = get(f"http://127.0.0.1:{port}/readyz")
        with open(os.path.join(tmp, f"quiesce.{pid}.json"), "w") as fh:
            json.dump(obs, fh, default=str)

    def arm():
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            rt = pw.internals.run.current_runtime()
            if rt is not None and hasattr(rt, "on_tick_done"):
                rt.on_tick_done.append(on_tick)
                break
            time.sleep(0.05)
        while time.monotonic() < deadline:
            if get(f"http://127.0.0.1:{my_mon}/readyz")[0] == 200:
                break
            time.sleep(0.1)
        with open(os.path.join(tmp, f"ready.{pid}"), "w") as fh:
            fh.write("1")

    threading.Thread(target=arm, daemon=True).start()
    pw.run(
        monitoring_level="none",
        with_http_server=True,
        autocommit_duration_ms=50,
        persistence_config=pw.persistence.Config(
            backend=pw.persistence.Backend.filesystem(
                os.environ["PATHWAY_PERSISTENT_STORAGE"]
            ),
            persistence_mode="operator_persisting",
            snapshot_interval_ms=150,
        ),
    )
    print("DONE", flush=True)
    """
)


def test_cluster_scale_drains_every_door_before_pause(tmp_path):
    """Acceptance: a manual /scale rescale marks every door ``draining``
    BEFORE the quiesce pause — observed from the drain tick itself: door
    ``/readyz`` answers 503 with reason ``rescale`` + ``Retry-After``, the
    monitoring servers answer 503 on /status and /metrics while /healthz
    and /alerts stay 200, and every process leaves with the rescale status
    (exit 75) for the Supervisor."""
    from pathway_tpu import elastic
    from pathway_tpu.io.kafka import MockKafkaBroker
    from pathway_tpu.persistence.backends import FileBackend

    script = tmp_path / "rescale_cluster.py"
    script.write_text(_RESCALE_CLUSTER_SCRIPT)
    broker = MockKafkaBroker(path=str(tmp_path / "broker"))
    broker.create_topic("words", partitions=2)
    for i in range(8):
        broker.produce("words", f"w{i}", partition=i % 2)
    block = free_port_base(3 + 7)
    mon_base = block
    http_port = _free_port()
    pstore = str(tmp_path / "pstore")

    def driver():
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            if all(
                (tmp_path / f"ready.{p}").exists() for p in range(2)
            ):
                break
            time.sleep(0.2)
        elastic.write_scale_request(FileBackend(pstore), 3, source="test")

    th = threading.Thread(target=driver, daemon=True)
    th.start()
    _procs, outputs = _spawn_cluster(
        script,
        [str(tmp_path), str(http_port)],
        2,
        {
            "PATHWAY_ELASTIC": "manual",
            "PATHWAY_HEALTH": "on",
            "PATHWAY_CANARY_INTERVAL_MS": "0",
            "PATHWAY_PERSISTENT_STORAGE": pstore,
            "BROKER_PATH": str(tmp_path / "broker"),
            "PATHWAY_MONITORING_HTTP_PORT": str(mon_base),
        },
        first_port=block + 3,
        ok_codes=(75,),  # ClusterRescale: every process leaves with exit 75
    )
    th.join(timeout=10)
    for p in range(2):
        path = tmp_path / f"quiesce.{p}.json"
        assert path.exists(), (
            f"process {p} never observed the drain window:\n{outputs[p]}"
        )
        obs = json.loads(path.read_text())
        assert obs["state"] == "draining", obs
        assert obs["reason"] == "rescale", obs
        assert obs["status"][0] == 503, obs["status"]
        assert _hdr(obs["status"][2], "Retry-After") == "5"
        assert obs["metrics"][0] == 503, obs["metrics"]
        assert obs["healthz"][0] == 200, obs["healthz"]
        assert obs["alerts"][0] == 200, obs["alerts"]
        assert obs["readyz"][0] == 503, obs["readyz"]
        assert "rescale" in obs["readyz"][1]
    door = json.loads((tmp_path / "quiesce.0.json").read_text())["door_readyz"]
    assert door[0] == 503, door
    assert "rescale" in door[1]
    assert _hdr(door[2], "Retry-After") == "5"
    # the rescale committed the new membership before the exits
    m = elastic.read_membership(FileBackend(pstore))
    assert m is not None and m.processes == 3


# ------------------------------------- slow: SIGKILL -> Supervisor relaunch

_SUPERVISED_HEALTH_SCRIPT = textwrap.dedent(
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
    pw.run(monitoring_level="none", with_http_server=True,
           autocommit_duration_ms=50)
    """
)


@pytest.mark.slow
def test_sigkill_supervisor_relaunch_reenters_starting(tmp_path):
    """SIGKILL a door, let the Supervisor relaunch the cluster: the fresh
    process re-enters ``starting`` (its transition log begins there, stamped
    after the kill) and the door's ``/readyz`` recovers to 200 ``ready``."""
    from pathway_tpu.resilience.supervisor import Supervisor

    script = tmp_path / "sup_health.py"
    script.write_text(_SUPERVISED_HEALTH_SCRIPT)
    stop_file = tmp_path / "stop"
    http_port = _free_port()
    block = free_port_base(3 + 7)
    mon_base = block
    env = dict(os.environ)
    env.update(
        PATHWAY_FABRIC="on",
        PATHWAY_HEALTH="on",
        PATHWAY_CANARY_INTERVAL_MS="0",
        PATHWAY_REPLICA_MAX_STALENESS_MS="60000",
        PATHWAY_BARRIER_TIMEOUT="45",
        PATHWAY_HEARTBEAT_INTERVAL="0.2",
        PATHWAY_HEARTBEAT_TIMEOUT="3",
        PATHWAY_MONITORING_HTTP_PORT=str(mon_base),
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO,
    )
    peer_port = http_port + 1
    peer_mon = mon_base + 1
    phases: dict = {}

    def wait_ready_state(timeout=90.0):
        deadline = time.monotonic() + timeout
        last = None
        while time.monotonic() < deadline:
            last = _get(f"http://127.0.0.1:{peer_port}/readyz", timeout=5)
            if last[0] == 200 and isinstance(last[1], dict) and last[1].get("ready"):
                return last
            time.sleep(0.3)
        return last

    def drive():
        try:
            _wait_ready(peer_port, timeout=90)
            phases["before"] = wait_ready_state()
            import signal

            peer_os_pid = int((tmp_path / "pid.1").read_text())
            phases["kill_unix"] = time.time()
            os.kill(peer_os_pid, signal.SIGKILL)
            time.sleep(1.0)
            _wait_ready(peer_port, timeout=120)
            phases["after"] = wait_ready_state(timeout=90.0)
            st, doc, _h = _get(f"http://127.0.0.1:{peer_mon}/status", timeout=20)
            phases["health"] = doc.get("health") if st == 200 else None
        finally:
            stop_file.write_text("stop")

    sup = Supervisor(
        [sys.executable, str(script), str(http_port), str(stop_file), str(tmp_path)],
        processes=2,
        threads=1,
        first_port=block + 3,
        max_restarts=2,
        backoff_s=0.2,
        env=env,
        log_dir=str(tmp_path / "logs"),
    )
    th = threading.Thread(target=drive)
    th.start()
    result = sup.run()
    th.join()
    assert result.restarts >= 1
    assert phases.get("before") is not None and phases["before"][0] == 200
    assert phases.get("after") is not None and phases["after"][0] == 200, phases
    # the relaunched process's transition log starts at `starting`, AFTER
    # the kill — the door honestly re-entered the lifecycle from scratch
    health = phases.get("health")
    assert health is not None, phases
    transitions = health["transitions"]
    assert transitions[0]["state"] == "starting", transitions
    assert transitions[0]["t_unix"] >= phases["kill_unix"], (
        transitions, phases["kill_unix"],
    )
    assert health["state"] == "ready"
