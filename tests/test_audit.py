"""Data-plane correctness observability (ISSUE 8 tentpole): invariant
monitors at operator edges, per-edge cardinality/selectivity gauges, sampled
shadow audits, fault-plan data corruption (flip_diff / drop_retract) detected
end-to-end on thread AND 2-proc cluster runtimes, the live error-log wiring,
and the heartbeat aggregation of audit summaries."""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine.blocks import DeltaBatch
from pathway_tpu.internals.monitoring import prometheus_text, run_stats
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.internals.run import current_runtime
from pathway_tpu.observability import audit as audit_mod
from conftest import free_port_base

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _groupby_pipeline(n=64, tick_rows=8):
    G.clear()
    t = pw.debug.table_from_rows(
        pw.schema_from_types(x=int),
        [(i, i // tick_rows, 1) for i in range(n)],
        is_stream=True,
    )
    t = t.with_columns(m=t.x % 5)
    g = t.groupby(t.m).reduce(s=pw.reducers.sum(t.x))
    pw.io.subscribe(g, on_change=lambda **k: None)


# ------------------------------------------------------------- plane basics


def test_audit_on_by_default_and_off_installs_nothing(monkeypatch):
    monkeypatch.delenv("PATHWAY_AUDIT", raising=False)
    _groupby_pipeline()
    pw.run(monitoring_level="none")
    plane = audit_mod.current()
    assert plane is not None and plane.mode == "on"
    assert plane.violation_counts == {}  # a healthy pipeline trips nothing

    monkeypatch.setenv("PATHWAY_AUDIT", "off")
    _groupby_pipeline()
    pw.run(monitoring_level="none")
    assert audit_mod.current() is None


def test_audit_knob_validation(monkeypatch):
    from pathway_tpu.internals.config import get_pathway_config

    monkeypatch.setenv("PATHWAY_AUDIT", "bogus")
    with pytest.raises(ValueError):
        get_pathway_config().audit
    monkeypatch.setenv("PATHWAY_AUDIT", "full")
    assert get_pathway_config().audit == "full"
    monkeypatch.delenv("PATHWAY_AUDIT", raising=False)
    assert get_pathway_config().audit == "on"
    monkeypatch.setenv("PATHWAY_AUDIT_SAMPLE", "2.0")
    with pytest.raises(ValueError):
        get_pathway_config().audit_sample


def test_cardinality_gauges_and_status_and_metrics(monkeypatch):
    # full mode + sample 1.0: every tick records, so the sampled retract/KMV
    # estimators are exact here (production estimates from the tick sample)
    monkeypatch.setenv("PATHWAY_AUDIT", "full")
    monkeypatch.setenv("PATHWAY_AUDIT_SAMPLE", "1.0")
    _groupby_pipeline()
    pw.run(monitoring_level="none")
    rt = current_runtime()
    stats = run_stats(rt)
    a = stats["audit"]
    assert a["enabled"] and a["violations_total"] == 0
    ops = {o["operator"]: o for o in a["operators"]}
    gb = ops["groupby"]
    # 64 inserts in; churny retract+insert output; 5 distinct group keys
    assert gb["rows_in"] == 64
    assert gb["retracts_out"] > 0
    assert 0.0 < gb["retract_fraction_out"] < 1.0
    assert gb["distinct_keys"] == 5
    assert gb["selectivity"] > 1.0
    text = prometheus_text(rt)
    assert 'pathway_operator_rows_total{op="groupby"' in text
    assert 'dir="in"' in text and 'dir="out"' in text
    assert "pathway_operator_selectivity" in text
    assert "pathway_operator_retract_fraction" in text
    assert "pathway_operator_distinct_keys" in text
    assert "pathway_audit_divergence_total 0" in text


def test_shadow_audit_runs_on_sampled_ticks_without_divergence(monkeypatch):
    monkeypatch.setenv("PATHWAY_AUDIT", "full")  # every tick shadow-audited
    _groupby_pipeline()
    pw.run(monitoring_level="none")
    plane = audit_mod.current()
    assert plane.shadow_ticks > 0
    assert plane.divergences == 0
    assert plane.violation_counts == {}


# -------------------------------------------------- fault-injected corruption


def test_flip_diff_detected_within_one_tick_thread_runtime(monkeypatch, tmp_path):
    monkeypatch.setenv("PATHWAY_FAULT_PLAN", "flip_diff:proc=0,tick=2")
    monkeypatch.setenv("PATHWAY_FLIGHT_DIR", str(tmp_path))
    _groupby_pipeline()
    pw.run(monitoring_level="none")
    plane = audit_mod.current()
    assert plane.violation_counts.get("negative_multiplicity", 0) >= 1
    v = next(
        v for v in plane.violations if v["kind"] == "negative_multiplicity"
    )
    # detected at the corrupted input edge, at the corruption tick
    assert v["tick"] == 2 and v["key"] is not None
    assert v["operator"].startswith("stream_fixture")
    # /status carries the structured event
    a = run_stats(current_runtime())["audit"]
    assert a["violations_by_kind"]["negative_multiplicity"] >= 1
    assert any(
        r["kind"] == "negative_multiplicity" for r in a["recent_violations"]
    )
    # ... and the flight-recorder dump names (operator, key, tick)
    dumps = glob.glob(str(tmp_path / "flight_p0_*.json"))
    assert dumps, "violation should trigger one immediate flight dump"
    doc = json.load(open(dumps[0]))
    assert doc["reason"] == "audit_violation"
    assert doc["extra"]["operator"].startswith("stream_fixture")
    assert doc["extra"]["tick"] == 2
    assert any(e.get("kind") == "audit_violation" for e in doc["events"])


def test_flip_diff_detected_on_sharded_thread_runtime(monkeypatch):
    monkeypatch.setenv("PATHWAY_FAULT_PLAN", "flip_diff:proc=0,tick=2")
    _groupby_pipeline()
    pw.run(monitoring_level="none", n_workers=2)
    plane = audit_mod.current()
    assert plane.violation_counts.get("negative_multiplicity", 0) >= 1


def test_drop_retract_detected_on_upsert_session(monkeypatch):
    monkeypatch.setenv("PATHWAY_FAULT_PLAN", "drop_retract:proc=0,tick=1")

    class Subj(pw.io.python.ConnectorSubject):
        def run(self):
            self.next(k=1, v=10)
            time.sleep(0.1)
            self.next(k=1, v=20)  # replace: (-1 old, +1 new); retract dropped
            time.sleep(0.05)

        @property
        def _session_type(self):
            return "upsert"

    class KS(pw.Schema):
        k: int = pw.column_definition(primary_key=True)
        v: int

    G.clear()
    t = pw.io.python.read(Subj(), schema=KS)
    pw.io.subscribe(t, on_change=lambda **k: None)
    pw.run(monitoring_level="none")
    plane = audit_mod.current()
    assert plane.violation_counts.get("upsert_duplicate", 0) >= 1
    v = next(v for v in plane.violations if v["kind"] == "upsert_duplicate")
    assert v["operator"].startswith("python_connector")
    assert v["key"] is not None and v["tick"] is not None


def test_flip_diff_on_index_input_edge_with_tiered_backend_live(monkeypatch):
    """ISSUE 9 satellite: index add/remove deltas ride the audit plane — a
    flip_diff fault on the index DOCS input edge is detected within one tick
    while a TieredKnnBackend serves the index (whose tolerant remove() keeps
    the dataflow alive so the tripwire, not a crash, reports the corruption)."""
    from pathway_tpu.stdlib.indexing import TieredKnnFactory

    monkeypatch.setenv("PATHWAY_FAULT_PLAN", "flip_diff:proc=0,tick=2")
    G.clear()
    rng = np.random.default_rng(21)
    vecs = rng.normal(size=(48, 8)).astype(np.float32)
    docs = pw.debug.table_from_rows(
        pw.schema_from_types(emb=np.ndarray),
        [(v, i // 8, 1) for i, v in enumerate(vecs)],  # 6 ticks of 8 docs
        is_stream=True,
    )
    index = TieredKnnFactory(dimensions=8, hot_rows=8, min_train=10**9).build_index(
        docs.emb, docs
    )
    qs = pw.debug.table_from_rows(
        pw.schema_from_types(emb=np.ndarray), [(vecs[3],)]
    )
    r = index.inner_index.query_as_of_now(qs.emb, number_of_matches=2)
    replies: list = []
    pw.io.subscribe(
        r,
        on_change=lambda key, row, time, is_addition: replies.append(row)
        if is_addition
        else None,
    )
    pw.run(monitoring_level="none")
    plane = audit_mod.current()
    assert plane.violation_counts.get("negative_multiplicity", 0) >= 1
    v = next(v for v in plane.violations if v["kind"] == "negative_multiplicity")
    # detected at the corrupted docs input edge, at the corruption tick
    assert v["tick"] == 2 and v["key"] is not None
    assert v["operator"].startswith("stream_fixture")
    # the index kept serving (the corrupt retraction poisoned only its row)
    assert replies, "index replies must survive the corrupted edge"
    from pathway_tpu.stdlib.indexing.tiered import tier_stats

    ts = tier_stats()
    assert ts is not None and ts["hits_total"] >= 2


_CLUSTER_SCRIPT = textwrap.dedent(
    """
    import json, sys
    import pathway_tpu as pw
    from pathway_tpu.internals.monitoring import run_stats
    from pathway_tpu.internals.run import current_runtime
    from pathway_tpu.observability import audit as audit_mod

    out = sys.argv[1]
    t = pw.debug.table_from_rows(
        pw.schema_from_types(x=int),
        [(i, i // 8, 1) for i in range(64)],
        is_stream=True,
    )
    t = t.with_columns(m=t.x % 5)
    g = t.groupby(t.m).reduce(s=pw.reducers.sum(t.x))
    pw.io.subscribe(g, on_change=lambda **k: None)
    pw.run(monitoring_level="none")
    import os
    pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
    plane = audit_mod.current()
    stats = run_stats(current_runtime())
    doc = {
        "violations": dict(plane.violation_counts),
        "status_kinds": stats["audit"]["violations_by_kind"],
        "recent": [
            {k: v for k, v in r.items() if k != "t_ns"}
            for r in stats["audit"]["recent_violations"]
        ],
    }
    with open(f"{out}.p{pid}.json", "w") as fh:
        json.dump(doc, fh)
    """
)


def test_flip_diff_detected_on_2proc_cluster(tmp_path):
    script = tmp_path / "pipeline.py"
    script.write_text(_CLUSTER_SCRIPT)
    out = str(tmp_path / "out")
    flight = tmp_path / "flight"
    env = dict(os.environ)
    env.update(
        PATHWAY_PROCESSES="2",
        PATHWAY_THREADS="1",
        PATHWAY_PROCESS_ID="0",
        PATHWAY_FIRST_PORT=str(free_port_base(3)),
        PATHWAY_BARRIER_TIMEOUT="45",
        PATHWAY_AUDIT="on",
        PATHWAY_FAULT_PLAN="flip_diff:proc=0,tick=2",
        PATHWAY_FLIGHT_DIR=str(flight),
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO,
    )
    procs = []
    for pid in range(2):
        penv = dict(env, PATHWAY_PROCESS_ID=str(pid))
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script), out],
                env=penv,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    for p in procs:
        try:
            stdout, _ = p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        assert p.returncode == 0, stdout
    # the corruption fired on process 0 (sources live on worker 0) and its
    # monitor caught it; the structured event is on that process's /status
    doc = json.load(open(out + ".p0.json"))
    assert doc["violations"].get("negative_multiplicity", 0) >= 1
    assert doc["status_kinds"].get("negative_multiplicity", 0) >= 1
    rec = next(
        r for r in doc["recent"] if r["kind"] == "negative_multiplicity"
    )
    assert rec["tick"] == 2
    # flight dump written by the detecting process, naming operator + tick
    dumps = glob.glob(str(flight / "flight_p0_*.json"))
    assert dumps
    fdoc = json.load(open(dumps[0]))
    assert fdoc["reason"] == "audit_violation" and fdoc["extra"]["tick"] == 2


# ----------------------------------------------------------- monitor units


def _fake_sink(idx=9):
    class N:
        name = "subscribe"
        node_index = idx

    return N()


def test_shadow_divergence_fires_on_inconsistent_net():
    plane = audit_mod.AuditPlane("full", 1.0, 1 << 20)
    node = _fake_sink()
    raw = DeltaBatch.from_rows([1, 2], [(10,), (20,)], ["v"], 0)
    plane.on_sink_delta(node, raw)
    # a "consolidation" that silently dropped key 2's row
    net = DeltaBatch.from_rows([1], [(10,)], ["v"], 0)
    plane.on_sink_net(node, net, 0)
    assert plane.divergences == 1
    assert plane.violation_counts.get("shadow_divergence") == 1
    # re-synced: the same healthy tick later does not re-fire
    raw2 = DeltaBatch.from_rows([3], [(30,)], ["v"], 1)
    plane.on_sink_delta(node, raw2)
    net2 = DeltaBatch.from_rows([3], [(30,)], ["v"], 1)
    plane.on_sink_net(node, net2, 1)
    assert plane.divergences == 1


def test_sink_negative_multiplicity_and_retract_excess():
    plane = audit_mod.AuditPlane("on", 1.0, 1 << 20)
    node = _fake_sink()
    net = DeltaBatch.from_rows([5], [(1,)], ["v"], 0, diffs=[-1])
    plane.on_sink_net(node, net, 0)
    assert plane.violation_counts.get("negative_multiplicity") == 1
    assert plane.violation_counts.get("retract_excess") == 1


def test_history_truncated_stands_down_multiplicity_monitors():
    """A persistence restart that replays only a log suffix makes retractions
    of pre-snapshot rows LEGAL — note_history_truncated() (called by
    snapshots._replay_all on suffix replay) must stand the history-dependent
    monitors down instead of reporting false violations."""
    plane = audit_mod.AuditPlane("full", 1.0, 1 << 20)
    plane.history_complete = False  # what note_history_truncated() sets

    class N:
        name = "stream_input"
        node_index = 0
        upsert = False

    n = N()
    # an unpaired retract (its insert predates the snapshot)
    retract = DeltaBatch.from_rows([5], [(1,)], ["v"], 0, diffs=[-1])
    plane.observe_input(n, [retract], 0)
    sink = _fake_sink()
    plane.on_sink_delta(sink, retract)
    plane.on_sink_net(sink, retract, 0)
    assert plane.violation_counts == {}
    assert plane.divergences == 0
    # the module-level hook flips the installed plane exactly once
    audit_mod._plane = fresh = audit_mod.AuditPlane("on", 1.0, 1 << 20)
    try:
        audit_mod.note_history_truncated()
        assert fresh.history_complete is False
    finally:
        audit_mod._plane = None


def test_watermark_regression_fires_once_per_input():
    plane = audit_mod.AuditPlane("on", 1.0, 1 << 20)

    class N:
        name = "stream_input"
        node_index = 1
        wm_event_time = 100.0

    n = N()
    plane.observe_input(n, [], 0)
    n.wm_event_time = 99.0
    plane.observe_input(n, [], 1)
    n.wm_event_time = 98.0  # still below the high-water mark: no re-fire
    plane.observe_input(n, [], 2)
    assert plane.violation_counts.get("watermark_regression") == 1


def test_watermark_regression_monitor():
    plane = audit_mod.AuditPlane("on", 1.0, 1 << 20)

    class N:
        name = "stream_input"
        node_index = 1
        wm_event_time = 100.0

    n = N()
    plane.observe_input(n, [], 0)
    n.wm_event_time = 99.0  # bookkeeping bug: the high-water mark regressed
    plane.observe_input(n, [], 1)
    assert plane.violation_counts.get("watermark_regression") == 1


def test_canonical_check_full_mode_only():
    bad = DeltaBatch.from_rows([7, 3], [(1,), (2,)], ["v"], 0)  # unsorted keys
    on = audit_mod.AuditPlane("on", 1.0, 1 << 20)
    on.check_canonical(bad, "test")
    assert on.violation_counts == {}  # "on" mode skips the per-batch check
    full = audit_mod.AuditPlane("full", 1.0, 1 << 20)
    full.check_canonical(bad, "test")
    assert full.violation_counts.get("non_canonical_batch") == 1
    zero = DeltaBatch.from_rows([3, 7], [(1,), (2,)], ["v"], 0, diffs=[0, 1])
    full.check_canonical(zero, "test")
    assert full.violation_counts.get("non_canonical_batch") == 2


def test_monitor_degrades_at_key_bound_instead_of_growing():
    plane = audit_mod.AuditPlane("on", 1.0, 1024)  # floor of the knob

    class N:
        name = "stream_input"
        node_index = 0
        upsert = False

    n = N()
    big = DeltaBatch.from_rows(
        list(range(3000)), [(i,) for i in range(3000)], ["v"], 0
    )
    plane.observe_input(n, [big], 0)
    assert n._audit_input.degraded
    assert n._audit_input.counts.size() == 0  # arrangement released, not retained


def test_heartbeat_summary_merge():
    a = {
        "violations": 2,
        "by_kind": {"negative_multiplicity": 2},
        "divergences": 1,
        "shadow_ticks": 4,
        "recent": [{"kind": "negative_multiplicity", "t_ns": 5}],
    }
    b = {
        "violations": 1,
        "by_kind": {"upsert_duplicate": 1},
        "divergences": 0,
        "shadow_ticks": 4,
        "recent": [{"kind": "upsert_duplicate", "t_ns": 3}],
    }
    merged = audit_mod.merge_heartbeat_summaries([a, None, b])
    assert merged["violations"] == 3
    assert merged["by_kind"] == {"negative_multiplicity": 2, "upsert_duplicate": 1}
    assert merged["divergences"] == 1 and merged["shadow_ticks"] == 8
    assert [r["t_ns"] for r in merged["recent"]] == [3, 5]
    assert audit_mod.merge_heartbeat_summaries([None, {}]) is None


# ----------------------------------------------- error-log live plane wiring


def test_udf_raise_increments_operator_error_counter():
    G.clear()
    t = pw.debug.table_from_rows(pw.schema_from_types(x=int), [(1,), (2,), (3,)])

    def boom(x):
        if x == 2:
            raise ValueError("bad row")
        return x * 10

    s = t.select(y=pw.apply(boom, t.x))
    pw.io.subscribe(s, on_change=lambda **k: None)
    pw.run(monitoring_level="none", terminate_on_error=False)
    rt = current_runtime()
    stats = run_stats(rt)
    assert stats["errors"]["total"] >= 1
    by_op = stats["errors"]["by_operator"]
    # the raise happened inside an engine node's process() — attributed to it
    assert any(c >= 1 for c in by_op.values()), by_op
    label = next(op for op, c in by_op.items() if c >= 1)
    assert label != "(unattributed)"
    text = prometheus_text(rt)
    assert "pathway_operator_errors_total" in text
    from pathway_tpu.internals.monitoring import escape_label_value

    assert f'pathway_operator_errors_total{{op="{escape_label_value(label)}"}}' in text


def test_fault_plan_parse_roundtrip_new_actions():
    from pathway_tpu.resilience.faults import FaultPlan

    plan = FaultPlan.parse("flip_diff:proc=0,tick=3;drop_retract:tick=5,count=2")
    assert [s.action for s in plan.specs] == ["flip_diff", "drop_retract"]
    again = FaultPlan.parse(plan.to_env())
    assert [(s.action, s.proc, s.tick, s.count) for s in again.specs] == [
        ("flip_diff", 0, 3, 1),
        ("drop_retract", None, 5, 2),
    ]
    # drop_retract waits for a block that actually has a retraction, then
    # fires exactly `count` times
    plan = FaultPlan.parse("drop_retract:tick=5")
    assert plan.take_corruption(0, 5, has_retract=False) is None
    spec = plan.take_corruption(0, 6, has_retract=True)
    assert spec is not None and spec.action == "drop_retract"
    assert plan.take_corruption(0, 7, has_retract=True) is None  # exhausted
    # wrong process never fires
    plan = FaultPlan.parse("flip_diff:proc=1,tick=0")
    assert plan.take_corruption(0, 3, has_retract=True) is None
