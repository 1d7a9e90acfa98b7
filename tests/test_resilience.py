"""Resilience subsystem tests (ISSUE 2): coordinated checkpoint epochs,
heartbeat failure detection, fault injection, and supervised restart.

Fast paths run in tier-1; the subprocess-killing recovery tests are
``@pytest.mark.slow`` (run them with ``-m slow``). The acceptance kill-test
(``test_supervisor_cluster_kill_recovery``) drives the full loop: a 2-process
cluster with persistence, SIGKILLed via ``FaultPlan`` mid-stream, restarted by
the ``Supervisor`` from the last committed global epoch, final output
byte-identical to an uninterrupted run with O(state + suffix) recovery.
"""

from __future__ import annotations

import csv as _csv
import json
import os
import shutil
import socket
import subprocess
import sys
import textwrap
import time

import pytest

import pathway_tpu as pw
from pathway_tpu.internals import telemetry
from pathway_tpu.internals.errors import OtherWorkerError
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.persistence.backends import MemoryBackend
from pathway_tpu.resilience import (
    FaultPlan,
    Supervisor,
    SupervisorGaveUp,
    faults,
    heartbeat,
    last_committed_epoch,
)
from conftest import free_port_base

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- fault plans


def test_fault_plan_parse_roundtrip():
    plan = FaultPlan.parse(
        "kill:proc=1,tick=40; drop_poll:proc=0,tick=3,count=2 ;delay_barrier:tick=4,ms=250"
    )
    assert len(plan.specs) == 3
    assert plan.to_env() == (
        "kill:proc=1,tick=40;drop_poll:proc=0,tick=3,count=2;delay_barrier:tick=4,ms=250"
    )
    assert FaultPlan.parse(plan.to_env()).to_env() == plan.to_env()
    # kill: exact-tick, proc-scoped
    assert plan.should_kill(1, 40)
    assert not plan.should_kill(1, 39)
    assert not plan.should_kill(0, 40)
    # drop_poll: a [tick, tick+count) window
    assert plan.should_drop_poll(0, 3) and plan.should_drop_poll(0, 4)
    assert not plan.should_drop_poll(0, 5) and not plan.should_drop_poll(1, 3)
    # delay_barrier: count consumes per barrier call, any proc when unscoped
    assert plan.take_barrier_delay(2, 4) is not None
    assert plan.take_barrier_delay(2, 4) is None  # count=1 exhausted


def test_fault_plan_rejects_garbage():
    with pytest.raises(ValueError, match="unknown fault action"):
        FaultPlan.parse("explode:tick=1")
    with pytest.raises(ValueError, match="unknown fault option"):
        FaultPlan.parse("kill:when=later")
    assert FaultPlan.from_env() is None or True  # env-independent smoke


def test_fault_drop_poll_single_process_still_completes():
    """A dropped poll delays events by a tick; the bounded run still produces
    the full result and records the injection in telemetry."""
    telemetry.clear_events()
    faults.install(FaultPlan.parse("drop_poll:proc=0,tick=1,count=2"))
    try:
        G.clear()

        class S(pw.Schema):
            x: int

        class Subject(pw.io.python.ConnectorSubject):
            def run(self):
                for i in range(5):
                    self.next(x=i)
                    time.sleep(0.01)

        t = pw.io.python.read(Subject(), schema=S, name="src")
        got = {}
        pw.io.subscribe(
            t,
            on_change=lambda key, row, time, is_addition: got.__setitem__(
                row["x"], is_addition
            ),
        )
        pw.run(monitoring_level="none")
    finally:
        faults.install(None)
    assert sorted(got) == [0, 1, 2, 3, 4]
    assert telemetry.events("resilience.fault_drop_poll")


def test_other_worker_error_fields():
    from pathway_tpu.internals.errors import EngineError

    e = OtherWorkerError("p1 died", process_id=1, tick=17, reason="disconnected")
    assert isinstance(e, EngineError)
    assert (e.process_id, e.tick, e.reason) == (1, 17, "disconnected")
    defaults = OtherWorkerError("unknown peer")
    assert (defaults.process_id, defaults.tick, defaults.reason) == (None, None, "unknown")
    assert pw.resilience.OtherWorkerError is OtherWorkerError


# ---------------------------------------------------------------- heartbeats


def _hb_connect(port: int):
    sock = socket.create_connection(("127.0.0.1", port), timeout=5)
    return sock


def test_heartbeat_monitor_detects_abrupt_disconnect():
    mon = heartbeat.HeartbeatMonitor(2, 0, timeout=30.0)
    try:
        sock = _hb_connect(mon.port)
        heartbeat._send(sock, ("hb", 1, 7))
        deadline = time.time() + 5
        while mon.seen_peers().get(1) != 7 and time.time() < deadline:
            time.sleep(0.01)
        assert mon.seen_peers() == {1: 7}
        assert mon.dead_peer() is None
        sock.close()  # process death: EOF without a goodbye
        deadline = time.time() + 5
        while mon.dead_peer() is None and time.time() < deadline:
            time.sleep(0.01)
        assert mon.dead_peer() == (1, 7, "disconnected")
    finally:
        mon.close()


def test_heartbeat_monitor_clean_goodbye_is_not_death():
    mon = heartbeat.HeartbeatMonitor(2, 0, timeout=0.2)
    try:
        sock = _hb_connect(mon.port)
        heartbeat._send(sock, ("hb", 1, 3))
        heartbeat._send(sock, ("bye", 1, 4))
        sock.close()
        time.sleep(0.4)  # well past the miss threshold
        assert mon.dead_peer() is None
    finally:
        mon.close()


def test_heartbeat_monitor_detects_silence_and_records_miss():
    telemetry.clear_events()
    mon = heartbeat.HeartbeatMonitor(2, 0, timeout=0.15)
    try:
        sock = _hb_connect(mon.port)
        heartbeat._send(sock, ("hb", 1, 2))
        deadline = time.time() + 5
        dead = None
        while dead is None and time.time() < deadline:
            dead = mon.dead_peer()
            time.sleep(0.02)
        assert dead == (1, 2, "heartbeat-timeout")
        misses = telemetry.events("resilience.heartbeat_miss")
        assert misses and misses[0]["attrs"]["process_id"] == 1
        sock.close()
    finally:
        mon.close()


def test_heartbeat_client_flags_lost_coordinator():
    mon = heartbeat.HeartbeatMonitor(2, 0, timeout=5.0)
    client = heartbeat.HeartbeatClient(1, mon.port, interval=0.05)
    try:
        deadline = time.time() + 5
        while 1 not in mon.seen_peers() and time.time() < deadline:
            time.sleep(0.01)
        assert 1 in mon.seen_peers()
        mon.close()  # coordinator dies
        deadline = time.time() + 5
        while not client.coordinator_lost and time.time() < deadline:
            time.sleep(0.02)
        assert client.coordinator_lost
    finally:
        client.goodbye()
        mon.close()


# ------------------------------------------------- in-process recovery smoke


class WordSchema(pw.Schema):
    word: str
    count: int


class ListSubject(pw.io.python.ConnectorSubject):
    def __init__(self, rows):
        super().__init__()
        self.rows = rows

    def run(self):
        for w, c in self.rows:
            self.next(word=w, count=c)


def _word_session(rows, backend):
    G.clear()
    subj = ListSubject(rows)
    t = pw.io.python.read(subj, schema=WordSchema, name="src")
    agg = t.groupby(pw.this.word).reduce(
        pw.this.word, total=pw.reducers.sum(pw.this.count)
    )
    got = {}
    pw.io.subscribe(
        agg,
        on_change=lambda key, row, time, is_addition: got.__setitem__(
            row["word"], row["total"]
        )
        if is_addition
        else None,
    )
    pw.run(
        monitoring_level="none",
        persistence_config=pw.persistence.Config(
            backend=backend, persistence_mode="operator_persisting"
        ),
    )
    return got


def test_memory_backend_restart_smoke():
    """Tier-1 recovery smoke (ISSUE 2 satellite): the MemoryBackend "restart"
    — a fresh runtime over the same store — recovers O(state + suffix),
    advances the epoch manifest, and records the replay in telemetry."""
    MemoryBackend.clear("resilience-smoke")
    backend = pw.persistence.Backend("memory", "resilience-smoke")
    first = [("a", 1), ("b", 2), ("a", 3)]
    second = [("b", 10), ("c", 5)]

    r1 = _word_session(first, backend)
    assert r1 == {"a": 4, "b": 2}
    ep1 = last_committed_epoch(backend)
    assert ep1 is not None and ep1["input_offsets"] == {"src": len(first)}
    assert ep1["opsnap_gen"] is not None and ep1["acks"] == [0]

    telemetry.clear_events()
    r2 = _word_session(first + second, backend)  # deterministic source replays
    # only NEW deltas emit: untouched aggregate "a" is NOT re-emitted
    assert r2 == {"b": 12, "c": 5}
    replays = telemetry.events("resilience.replay")
    assert replays, "recovery must record a resilience.replay event"
    assert replays[0]["attrs"]["events"] < len(first + second)  # O(suffix)
    ep2 = last_committed_epoch(backend)
    assert ep2["epoch"] > ep1["epoch"]
    assert ep2["input_offsets"] == {"src": len(first + second)}
    # the epoch commits surface in monitoring /status and the OTLP metrics doc
    rt = pw.internals.run.current_runtime()
    from pathway_tpu.internals.monitoring import run_stats

    stats = run_stats(rt)
    assert stats["resilience"]["last_committed_epoch"] == ep2["epoch"]


def test_resilience_events_exported_in_otlp_docs(tmp_path):
    telemetry.clear_events()
    telemetry.record_event("resilience.heartbeat_miss", process_id=1, tick=3)
    telemetry.record_event("resilience.epoch_committed", epoch=7, tick=9)
    telemetry.record_event("resilience.replay", events=12, n_inputs=1)

    class _Rt:
        scheduler = None

    trace_doc = telemetry.export_run_trace(_Rt(), str(tmp_path / "t.json"), 0, 1)
    names = [
        s["name"]
        for s in trace_doc["resourceSpans"][0]["scopeSpans"][0]["spans"]
    ]
    assert "event/resilience.heartbeat_miss" in names
    assert "event/resilience.epoch_committed" in names
    metrics_doc = telemetry.export_run_metrics(_Rt(), str(tmp_path / "m.json"), 1)
    gauges = {
        m["name"]
        for m in metrics_doc["resourceMetrics"][0]["scopeMetrics"][0]["metrics"]
    }
    assert {
        "pathway.resilience.heartbeat_misses",
        "pathway.resilience.replayed_events",
        "pathway.resilience.last_committed_epoch",
    } <= gauges
    telemetry.clear_events()


# ---------------------------------------------------------------- supervisor

_FLAKY_CHILD = textwrap.dedent(
    """
    import os, sys
    marker = sys.argv[1]
    if not os.path.exists(marker):
        open(marker, "w").close()
        sys.exit(3)  # first launch fails
    sys.exit(0)  # relaunch succeeds
    """
)


def test_supervisor_restarts_until_success(tmp_path):
    script = tmp_path / "flaky.py"
    script.write_text(_FLAKY_CHILD)
    marker = str(tmp_path / "marker")
    telemetry.clear_events()
    sup = Supervisor(
        [sys.executable, str(script), marker],
        processes=1,
        max_restarts=3,
        backoff_s=0.05,
        log_dir=str(tmp_path / "logs"),
    )
    result = sup.run()
    assert result.restarts == 1
    assert [a["exit_codes"] for a in result.attempts] == [[3], [0]]
    assert len(result.log_paths) == 2 and all(os.path.exists(p) for p in result.log_paths)
    restarts = telemetry.events("resilience.restart")
    assert restarts and restarts[0]["attrs"]["exit_code"] == 3


def test_supervisor_gives_up_after_budget(tmp_path):
    script = tmp_path / "alwaysfail.py"
    script.write_text("import sys; sys.exit(2)\n")
    sup = Supervisor(
        [sys.executable, str(script)], processes=1, max_restarts=1, backoff_s=0.05
    )
    with pytest.raises(SupervisorGaveUp) as exc:
        sup.run()
    assert len(exc.value.attempts) == 2
    assert all(a["exit_codes"] == [2] for a in exc.value.attempts)


def test_supervisor_clears_fault_plan_after_failure(tmp_path):
    """A `kill at tick N` plan must not re-fire on every relaunch: the child
    env drops PATHWAY_FAULT_PLAN after the first failure by default."""
    script = tmp_path / "envcheck.py"
    script.write_text(
        "import os, sys; sys.exit(4 if os.environ.get('PATHWAY_FAULT_PLAN') else 0)\n"
    )
    env = dict(os.environ, PATHWAY_FAULT_PLAN="kill:proc=0,tick=5")
    sup = Supervisor(
        [sys.executable, str(script)],
        processes=1,
        max_restarts=2,
        backoff_s=0.05,
        env=env,
    )
    result = sup.run()
    assert result.restarts == 1  # attempt 0 saw the plan (exit 4), attempt 1 clean


def test_supervise_cli_runs(tmp_path):
    from click.testing import CliRunner

    from pathway_tpu.cli import cli

    script = tmp_path / "ok.py"
    script.write_text("print('fine')\n")
    runner = CliRunner()
    res = runner.invoke(
        cli, ["supervise", "-n", "1", "--backoff", "0.05", sys.executable, str(script)]
    )
    assert res.exit_code == 0, res.output


# ----------------------------------------------------- cluster recovery (slow)


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
def test_cluster_peer_killed_midrun_raises_other_worker_error(tmp_path):
    """ISSUE 2 tentpole: SIGKILL a peer mid-run (via FaultPlan) — the
    surviving coordinator must raise a structured OtherWorkerError naming the
    dead process, detected via heartbeat EOF well before barrier_timeout."""
    script = tmp_path / "stream.py"
    script.write_text(_STREAMING_PIPELINE)
    env = dict(
        os.environ,
        PYTHONPATH=REPO,
        JAX_PLATFORMS="cpu",
        PATHWAY_PROCESSES="2",
        PATHWAY_THREADS="1",
        PATHWAY_FIRST_PORT=str(free_port_base(3)),
        PATHWAY_BARRIER_TIMEOUT="60",
        PATHWAY_FAULT_PLAN="kill:proc=1,tick=10",
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
    t0 = time.monotonic()
    out0, _ = procs[0].communicate(timeout=90)
    detection = time.monotonic() - t0
    assert procs[0].returncode != 0
    assert "OtherWorkerError" in out0, out0
    assert "cluster process 1 failed" in out0, out0
    # heartbeat EOF detection: far faster than the 60s barrier timeout
    assert detection < 30, f"took {detection:.1f}s to surface the dead peer"


_PERSIST_PIPELINE = textwrap.dedent(
    """
    import os
    import sys

    import pathway_tpu as pw
    from pathway_tpu.io.kafka import MockKafkaBroker

    out = sys.argv[1]
    broker = MockKafkaBroker(path=os.environ["BROKER_PATH"])
    expected = int(os.environ["EXPECTED_WORDS"])

    words = pw.io.kafka.read(
        broker, "words", format="plaintext", mode="streaming", name="words"
    )
    counts = words.groupby(words.data).reduce(words.data, c=pw.reducers.count())
    pw.io.fs.write(counts, out + ".csv", format="csv")
    total = counts.reduce(s=pw.reducers.sum(pw.this.c))

    def on_total(key, row, time, is_addition):
        if is_addition and row["s"] >= expected:
            rt = pw.internals.run.current_runtime()
            if rt is not None:
                rt.request_stop()

    pw.io.subscribe(total, on_change=on_total)
    pw.run(
        monitoring_level="none",
        persistence_config=pw.persistence.Config(
            backend=pw.persistence.Backend.filesystem(os.environ["PSTORE"]),
            persistence_mode="operator_persisting",
            snapshot_interval_ms=150,
        ),
    )
    """
)


@pytest.mark.slow
def test_supervisor_cluster_kill_recovery(tmp_path):
    """ISSUE 2 acceptance criterion: a 2-process cluster pipeline with
    persistence, SIGKILLed via FaultPlan mid-stream, is restarted by the
    Supervisor from the last committed global epoch and produces final output
    byte-identical to an uninterrupted run, replaying fewer events than the
    full history (O(state + suffix) recovery)."""
    from pathway_tpu.io.kafka import MockKafkaBroker

    script = tmp_path / "persist.py"
    script.write_text(_PERSIST_PIPELINE)
    broker_path = str(tmp_path / "broker")
    broker = MockKafkaBroker(path=broker_path)
    broker.create_topic("words", partitions=2)
    # "only*" words appear exclusively before the kill: their aggregates must
    # NOT re-emit after the restart (the O(state) proof)
    first = [f"w{i % 11}" for i in range(80)] + [f"only{i % 3}" for i in range(20)]
    second = [f"w{i % 11}" for i in range(100)]
    for i, w in enumerate(first):
        broker.produce("words", w, partition=i % 2)

    out = str(tmp_path / "run")
    env = dict(
        os.environ,
        PYTHONPATH=REPO,
        JAX_PLATFORMS="cpu",
        BROKER_PATH=broker_path,
        PSTORE=str(tmp_path / "pstore"),
        EXPECTED_WORDS=str(len(first) + len(second)),
        PATHWAY_BARRIER_TIMEOUT="45",
        # by tick 100 (~2s) all of `first` is consumed, snapshotted (150ms
        # cadence) and quiesced, so the crash point has no in-flight suffix
        PATHWAY_FAULT_PLAN="kill:proc=1,tick=100",
        PATHWAY_METRICS_FILE=out + ".metrics",
    )

    def on_restart(attempt, codes):
        # crash point: snapshot the output, then let new data arrive while
        # the pipeline is down (the reference's recovery scenario)
        shutil.copy(out + ".csv", out + ".first.csv")
        for i, w in enumerate(second):
            broker.produce("words", w, partition=i % 2)

    sup = Supervisor(
        [sys.executable, str(script), out],
        processes=2,
        threads=1,
        first_port=free_port_base(3),
        max_restarts=2,
        backoff_s=0.2,
        env=env,
        log_dir=str(tmp_path / "logs"),
        on_restart=on_restart,
    )
    result = sup.run()
    assert result.restarts == 1, result.attempts

    def net(fp):
        state: dict = {}
        with open(fp) as fh:
            for rec in _csv.DictReader(fh):
                w, c, d = rec["data"], int(rec["c"]), int(rec["diff"])
                state[w] = state.get(w, 0) + c * d
                if state[w] == 0:
                    del state[w]
        return state

    truth: dict = {}
    for w in first + second:
        truth[w] = truth.get(w, 0) + 1
    assert net(out + ".csv") == truth, (net(out + ".csv"), truth)
    # byte-identical recovery: run 1's rows stay in place (the restart rewinds
    # the sink to the epoch cut), and nothing re-emits for aggregates
    # untouched since the snapshot
    with open(out + ".first.csv") as fh1, open(out + ".csv") as fh2:
        run1, final = fh1.read(), fh2.read()
    assert final.startswith(run1)
    assert "only" not in final[len(run1):]
    # O(state + suffix): strictly fewer events replayed than the full history
    with open(out + ".metrics.p0") as fh:
        doc = json.load(fh)
    gauges = {
        m["name"]: m
        for m in doc["resourceMetrics"][0]["scopeMetrics"][0]["metrics"]
    }
    replayed = int(
        gauges["pathway.resilience.replayed_events"]["gauge"]["dataPoints"][0]["asInt"]
    )
    assert replayed < len(first) + len(second), replayed
    # the epoch manifest was committed with BOTH processes' durability acks
    ep = last_committed_epoch(
        pw.persistence.Backend.filesystem(env["PSTORE"])
    )
    assert ep is not None and ep["acks"] == [0, 1]
    assert ep["opsnap_gen"] is not None
