"""Device profiling & cost-attribution plane (ISSUE 5 tentpole).

Compile telemetry with recompile-storm detection, padding-waste accounting,
memory attribution, host/device time split, the flight recorder's post-mortem
dumps, the ``/profile`` capture window, and graceful degradation when the jax
probes are unavailable (CPU-only CI).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time
import urllib.request

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.internals.monitoring import (
    MonitoringHttpServer,
    prometheus_text,
    run_stats,
)
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.observability import device
from pathway_tpu.ops.microbatch import MicrobatchDispatcher
from conftest import free_port_base

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _RT:
    scheduler = None
    monitoring_server = None


@pytest.fixture(autouse=True)
def _fresh_device_plane(monkeypatch):
    """Per-run device state reset (pad/split/flight), default knobs."""
    for k in (
        "PATHWAY_PROFILE",
        "PATHWAY_PROFILE_DIR",
        "PATHWAY_PROFILE_SHAPE_WARN",
        "PATHWAY_FLIGHT_DIR",
    ):
        monkeypatch.delenv(k, raising=False)
    device.install_from_env()
    yield
    device.shutdown()


def _jit_square():
    import jax

    return jax.jit(lambda x: x * x)


# ---------------------------------------------------------- compile telemetry


def test_traced_jit_counts_cold_shapes_and_compiles():
    import jax.numpy as jnp

    f = device.traced_jit("test.count_shapes", _jit_square())
    for n in (8, 8, 16, 16, 8):
        f(jnp.ones((n,)))
    assert f.calls == 5
    assert f.cold_calls == 2  # two distinct shapes
    assert len(f._seen) == 2
    assert f.cold_s > 0.0
    view = device.status_summary()["callables"]["test.count_shapes"]
    assert view["shapes"] == 2
    assert view["compiles"] >= 2  # listener-precise or cold-call fallback
    assert view["compile_s"] > 0.0
    assert not view["storm"]


def test_recompile_storm_detected_on_unbucketed_shapes(monkeypatch):
    """ISSUE 5 acceptance: deliberately unbucketed shapes climb the compile
    counter and raise the storm warning on /status, while the bucketed path
    (below) keeps a small closed shape set."""
    import jax.numpy as jnp

    monkeypatch.setenv("PATHWAY_PROFILE_SHAPE_WARN", "4")
    device.install_from_env()
    f = device.traced_jit("test.storm", _jit_square())
    compile_counts = []
    for n in range(3, 10):  # 7 distinct unbucketed shapes
        f(jnp.ones((n,)))
        compile_counts.append(f.cold_calls)
    assert compile_counts == sorted(compile_counts)  # climbing
    assert f.cold_calls == 7
    assert f.storm
    stats = run_stats(_RT())
    dev = stats["device"]
    assert dev["callables"]["test.storm"]["storm"]
    assert any("test.storm" in w for w in dev.get("warnings", ())), dev.get(
        "warnings"
    )


def test_bucketed_dispatch_keeps_closed_shape_set(monkeypatch):
    monkeypatch.setenv("PATHWAY_PROFILE_SHAPE_WARN", "6")
    device.install_from_env()
    calls = []

    def batch_fn(items):
        calls.append(len(items))
        return [v * 2 for v in items]

    d = MicrobatchDispatcher(batch_fn, max_batch=128, label="bucketed")
    for n in (1, 3, 5, 9, 17, 33, 50, 64, 100, 2, 7):
        out = d.map(list(range(n)))
        assert out == [v * 2 for v in range(n)]
    # every launch is a power-of-two bucket from the closed set
    assert set(calls) <= {8, 16, 32, 64, 128}
    view = device.status_summary()["callables"]["udf:bucketed"]
    assert view["shapes"] == len(set(calls))
    assert not view["storm"]


# --------------------------------------------------------- padding accounting


def test_pad_rows_accounting_and_waste_ratio():
    d = MicrobatchDispatcher(lambda items: items, max_batch=64, label="padtest")
    d.map(list(range(5)))  # bucket 8 -> 3 pad rows
    pad = device.status_summary()["pad"]["udf:padtest"]
    assert pad["real_rows"] == 5
    assert pad["pad_rows"] == 3
    assert pad["row_waste_ratio"] == pytest.approx(3 / 8)
    text = prometheus_text(_RT())
    assert 'pathway_pad_rows_total{udf="udf:padtest",kind="real"} 5' in text
    assert 'pathway_pad_rows_total{udf="udf:padtest",kind="pad"} 3' in text
    assert 'pathway_pad_waste_ratio{udf="udf:padtest"}' in text


def test_encoder_token_pad_and_flops_accounting():
    from pathway_tpu.ops.encoder import EncoderConfig, JaxSentenceEncoder

    enc = JaxSentenceEncoder(
        EncoderConfig(n_layers=1, d_model=64, n_heads=2, d_ff=128, vocab_size=512)
    )
    enc.encode_texts(["hello world", "a much longer sentence with many words here"])
    s = device.status_summary()
    pad = s["pad"]["encoder"]
    assert pad["real_tokens"] > 0
    assert pad["pad_tokens"] > 0  # length bucketing always pads some
    assert 0 < pad["token_waste_ratio"] < 1
    # memory attribution: encoder params registered while the object lives
    mem = s["memory"]["components"]
    assert mem.get("encoder_params", 0) > 0


def test_knn_memory_and_flops_attribution():
    from pathway_tpu.ops.knn import BruteForceKnnIndex

    ix = BruteForceKnnIndex(dimension=16, capacity=64)
    for i in range(10):
        ix.add(i, np.random.default_rng(i).standard_normal(16).astype(np.float32))
    ix.search(np.zeros((2, 16), np.float32), k=3)
    s = device.status_summary()
    assert s["memory"]["components"].get("knn_index", 0) >= ix.device_bytes()
    pad = s["pad"]["knn.search"]
    assert pad["real_rows"] == 10 and pad["pad_rows"] == ix.capacity - 10
    text = prometheus_text(_RT())
    assert 'pathway_device_bytes{component="knn_index"}' in text


# ------------------------------------------------- microbatch compile satellite


def test_cold_dispatch_span_carries_compile_ms(monkeypatch):
    """ISSUE 5 satellite: the ``pathway.cold_shape`` dispatch span gains the
    measured compile wall time, and the per-process cumulative compile-seconds
    counter advances."""
    monkeypatch.setenv("PATHWAY_TRACE", "on")
    from pathway_tpu import observability as obs

    before = device.stats().process_compile_s
    tracer = obs.install_from_env()
    try:
        tracer.begin_tick(0)
        d = MicrobatchDispatcher(
            lambda items: [v + 1 for v in items], max_batch=32, label="coldspan"
        )
        d.map(list(range(5)))
        spans, _ = tracer.buffer.since(0)
        dispatch = [s for s in spans if s["name"] == "device/dispatch"]
        assert dispatch
        attrs = {a["key"]: a["value"] for a in dispatch[0]["attributes"]}
        assert attrs["pathway.cold_shape"]["boolValue"] is True
        assert float(attrs["pathway.compile_ms"]["doubleValue"]) >= 0.0
        # warm re-dispatch of the same shape: no compile_ms attr
        d2 = MicrobatchDispatcher(
            lambda items: [v + 1 for v in items], max_batch=32, label="coldspan"
        )
        d2.map(list(range(5)))
        spans, _ = tracer.buffer.since(0)
        warm = [s for s in spans if s["name"] == "device/dispatch"][-1]
        wattrs = {a["key"]: a["value"] for a in warm["attributes"]}
        assert wattrs["pathway.cold_shape"]["boolValue"] is False
        assert "pathway.compile_ms" not in wattrs
    finally:
        obs.shutdown()
    assert device.stats().process_compile_s > before


# ------------------------------------------------------------ host/device split


def test_full_mode_records_host_device_split(monkeypatch):
    import jax.numpy as jnp

    monkeypatch.setenv("PATHWAY_PROFILE", "full")
    device.install_from_env()
    f = device.traced_jit("test.split", _jit_square())
    x = jnp.ones((64,))
    f(x)  # cold
    f(x)  # warm, split-sampled (full mode)
    split = device.status_summary()["time_split"]["test.split"]
    assert split["samples"] == 1
    assert split["host_ms"] >= 0.0 and split["device_ms"] >= 0.0
    assert device.stats().device_wait_ns >= 0


# -------------------------------------------------------------- /status wiring


def test_run_status_has_device_section_and_metric_families():
    G.clear()
    t = pw.debug.table_from_rows(
        pw.schema_from_types(x=int),
        [(i, i // 8, 1) for i in range(64)],
        is_stream=True,
    )
    t = t.with_columns(m=t.x % 3)
    g = t.groupby(t.m).reduce(s=pw.reducers.sum(t.x))
    pw.io.subscribe(g, on_change=lambda **k: None)
    pw.run(monitoring_level="none")
    rt = pw.internals.run.current_runtime()
    stats = run_stats(rt)
    dev = stats["device"]
    assert dev["enabled"] and dev["mode"] == "on"
    for key in ("callables", "pad", "memory", "time_split", "flight"):
        assert key in dev
    text = prometheus_text(rt)
    assert "pathway_jit_compiles_total" in text
    assert "pathway_jit_compile_seconds_total" in text
    assert "pathway_device_bytes" in text


def test_profile_off_disables_all_accounting(monkeypatch):
    import jax.numpy as jnp

    monkeypatch.setenv("PATHWAY_PROFILE", "off")
    device.install_from_env()
    f = device.traced_jit("test.off_mode", _jit_square())
    f(jnp.ones((4,)))
    d = MicrobatchDispatcher(lambda items: items, max_batch=8, label="offpad")
    d.map([1, 2, 3])
    assert f.cold_calls == 0 and f.calls == 0
    summary = device.status_summary()
    assert summary == {"enabled": False, "mode": "off"}
    assert device.prometheus_lines() == []


# ------------------------------------------------------------- flight recorder


def test_flight_dump_on_failing_run(tmp_path, monkeypatch):
    monkeypatch.setenv("PATHWAY_FLIGHT_DIR", str(tmp_path / "flight"))
    G.clear()
    t = pw.debug.table_from_rows(
        pw.schema_from_types(x=int), [(1, 0, 1), (2, 0, 1)], is_stream=True
    )
    t = t.select(y=pw.apply(lambda x: 1 // 0, t.x))
    pw.io.subscribe(t, on_change=lambda **k: None)
    with pytest.raises(Exception):
        pw.run(monitoring_level="none", terminate_on_error=True)
    dumps = sorted((tmp_path / "flight").glob("flight_p0_*.json"))
    assert dumps, "no post-mortem dump written"
    doc = json.loads(dumps[0].read_text())
    assert doc["reason"] == "run_error"
    assert doc["error"]["type"]
    assert isinstance(doc["ticks"], list)
    assert isinstance(doc["events"], list)
    assert doc["device"]["enabled"]


def test_flight_dump_disabled_without_knob(tmp_path):
    # no PATHWAY_FLIGHT_DIR: dump is a no-op, recorder still records
    assert device.flight_dump("unit_test") is None
    device.flight_note("unit_event", n=1)
    assert any(e["kind"] == "unit_event" for e in device._recorder.events)


# ------------------------------------------------------ profiler capture window


def test_profile_window_via_endpoint_and_ticks(tmp_path):
    srv = MonitoringHttpServer(_RT(), port=0).start()
    try:
        state = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/profile", timeout=2
            ).read()
        )
        assert state == {"ok": True, "window": None}
        out = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/profile?ticks=2&dir={tmp_path}/prof",
                timeout=2,
            ).read()
        )
        assert out["ok"] and out["ticks"] == 2
        # second arm while active is refused
        again = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/profile?ticks=2&dir={tmp_path}/prof2",
                timeout=2,
            ).read()
        )
        assert not again["ok"]
        device.tick_hook(0)
        device.tick_hook(1)
        assert device._profile_state() is None  # window closed after 2 ticks
        produced = [
            os.path.join(r, f)
            for r, _, files in os.walk(tmp_path / "prof")
            for f in files
        ]
        assert produced, "jax.profiler produced no trace files"
    finally:
        srv.stop()


def test_cli_profile_command(tmp_path):
    from click.testing import CliRunner

    from pathway_tpu.cli import cli

    srv = MonitoringHttpServer(_RT(), port=0).start()
    try:
        res = CliRunner().invoke(
            cli,
            [
                "profile",
                "--port",
                str(srv.port),
                "--ticks",
                "1",
                "--dir",
                str(tmp_path / "cliprof"),
            ],
        )
        assert res.exit_code == 0, res.output
        assert '"ok": true' in res.output
        device.tick_hook(0)  # close the window
        res = CliRunner().invoke(cli, ["profile", "--port", str(srv.port), "--status"])
        assert res.exit_code == 0, res.output
    finally:
        srv.stop()


# -------------------------------------------------------- graceful degradation


def test_graceful_degradation_without_jax(monkeypatch, recwarn):
    """ISSUE 5 satellite: every probe no-ops cleanly when jax / jax.profiler /
    device memory stats are unavailable — zero warnings, zero crashes."""
    monkeypatch.setattr(device, "_jax", False)  # simulate missing jax
    device._block(object())
    assert device.backend_memory() is None
    out = device.request_profile(2, "/tmp/nowhere")
    assert out["ok"] is False
    device.tick_hook(0)
    summary = device.status_summary()
    assert summary["enabled"]
    assert summary["memory"]["backend"] is None
    assert device.flight_dump("degraded") is None  # knob unset
    assert not [w for w in recwarn.list], [str(w.message) for w in recwarn.list]


def test_cpu_backend_memory_stats_absent_is_clean(recwarn):
    # JAX_PLATFORMS=cpu: TFRT CPU devices expose no memory_stats — the gauge
    # must simply omit the backend block
    summary = device.status_summary()
    assert summary["memory"]["backend"] is None
    text = prometheus_text(_RT())
    assert "backend.bytes_in_use" not in text
    assert not [w for w in recwarn.list], [str(w.message) for w in recwarn.list]


# -------------------------------------------------- cluster aggregation (unit)


def test_heartbeat_summary_merges_across_peers():
    d = MicrobatchDispatcher(lambda items: items, max_batch=16, label="hbmerge")
    d.map(list(range(5)))
    mine = device.heartbeat_summary()
    assert mine is not None and mine["pad_rows"][0] >= 5
    merged = device.merge_heartbeat_summaries([mine, mine, None, {}])
    assert merged["pad_rows"][0] == 2 * mine["pad_rows"][0]
    assert merged["compiles"] == 2 * mine["compiles"]
    assert merged["shapes_max"] == mine["shapes_max"]


# ------------------------------------------------- cluster flight dump (slow)


_STREAMING_PIPELINE = textwrap.dedent(
    """
    import time

    import pathway_tpu as pw

    class Subj(pw.io.python.ConnectorSubject):
        def __init__(self):
            super().__init__()
            self._stop = False
        def run(self):
            i = 0
            while not self._stop:
                self.next(x=i)
                i += 1
                time.sleep(0.02)
        def on_stop(self):
            self._stop = True

    t = pw.io.python.read(Subj(), schema=pw.schema_from_types(x=int), name="src")
    agg = t.reduce(s=pw.reducers.sum(pw.this.x))
    pw.io.subscribe(agg, on_change=lambda **kw: None)
    pw.run(monitoring_level="none")
    """
)


@pytest.mark.slow
def test_flight_dump_names_failed_proc_and_tick_on_cluster_kill(tmp_path):
    """ISSUE 5 satellite: PATHWAY_FAULT_PLAN kills a peer mid-stream; the
    surviving coordinator's post-mortem dump exists, parses, and names the
    failed (proc, tick)."""
    script = tmp_path / "stream.py"
    script.write_text(_STREAMING_PIPELINE)
    flight = tmp_path / "flight"
    env = dict(
        os.environ,
        PYTHONPATH=REPO,
        JAX_PLATFORMS="cpu",
        PATHWAY_PROCESSES="2",
        PATHWAY_THREADS="1",
        PATHWAY_FIRST_PORT=str(free_port_base(3)),
        PATHWAY_BARRIER_TIMEOUT="60",
        PATHWAY_FAULT_PLAN="kill:proc=1,tick=10",
        PATHWAY_FLIGHT_DIR=str(flight),
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
    out1, _ = procs[1].communicate(timeout=90)
    assert procs[1].returncode == -9, out1  # the injected SIGKILL
    out0, _ = procs[0].communicate(timeout=90)
    assert procs[0].returncode != 0
    dumps = sorted(flight.glob("flight_p0_*.json"))
    assert dumps, out0
    doc = json.loads(dumps[-1].read_text())
    assert doc["reason"] == "other_worker_error"
    assert doc["error"]["type"] == "OtherWorkerError"
    assert doc["error"]["process_id"] == 1  # the killed peer
    assert isinstance(doc["error"]["tick"], int)  # its last known tick
    assert doc["ticks"], "flight recorder captured no recent ticks"
