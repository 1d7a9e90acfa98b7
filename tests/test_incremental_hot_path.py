"""ISSUE-6 incremental hot path.

Property sweep for the consolidation algebra (idempotence, diff-sum
preservation under arbitrary insert/retract interleavings, the O(delta)
``merge_consolidated`` ≡ consolidate∘concat), the lazy capture-sink fold,
arrangement compaction parity, the phase-attribution plane, and the
acceptance bar itself: the benched filter+join+groupby pipeline WITH
retractions is byte-identical between incremental and one-shot static
execution on the thread and 2-proc cluster runtimes.
"""

from __future__ import annotations

import os
import sys
import textwrap

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine import operators as ops
from pathway_tpu.engine.blocks import (
    DeltaBatch,
    concat_batches,
    consolidate,
    merge_consolidated,
    net_input_batch,
)
from pathway_tpu.engine.colstore import ColumnarMultimap
from utils import rows_of

# --------------------------------------------------------------- generators


def _rand_batch(rng, n, key_space=12, val_space=4, time=0, with_obj=False):
    keys = rng.integers(0, key_space, n).astype(np.uint64)
    diffs = rng.choice(np.array([-1, 1, 1, 2], dtype=np.int64), n)
    data = {
        "a": rng.integers(0, val_space, n).astype(np.int64),
        "b": (rng.integers(0, val_space, n) * 0.5).astype(np.float64),
    }
    if with_obj:
        obj = np.empty(n, dtype=object)
        obj[:] = [f"s{int(v)}" for v in rng.integers(0, val_space, n)]
        data["c"] = obj
    return DeltaBatch(keys, diffs, data, time)


def _net_multiset(batch):
    """Reference semantics: net diff per (key, row values)."""
    from collections import Counter

    c = Counter()
    for k, d, row in batch.rows():
        c[(k, row)] += d
    return Counter({k: v for k, v in c.items() if v != 0})


def _batches_equal(a: DeltaBatch, b: DeltaBatch) -> bool:
    """Byte-level equality: keys, diffs, column order AND row order."""
    if not np.array_equal(a.keys, b.keys) or not np.array_equal(a.diffs, b.diffs):
        return False
    if list(a.data) != list(b.data):
        return False
    for n in a.data:
        ca, cb = a.data[n], b.data[n]
        if len(ca) != len(cb):
            return False
        if not all(x == y for x, y in zip(ca.tolist(), cb.tolist())):
            return False
    return True


# ------------------------------------------------------------ property sweep


def test_consolidate_idempotent_sweep():
    rng = np.random.default_rng(42)
    for trial in range(60):
        b = _rand_batch(rng, int(rng.integers(0, 50)), with_obj=bool(trial % 3))
        c1 = consolidate(b)
        c2 = consolidate(c1)
        assert _batches_equal(c1, c2), f"consolidate not idempotent (trial {trial})"


def test_consolidate_preserves_net_diffs_sweep():
    rng = np.random.default_rng(7)
    for trial in range(60):
        b = _rand_batch(rng, int(rng.integers(0, 60)), with_obj=bool(trial % 2))
        c = consolidate(b)
        assert _net_multiset(c) == _net_multiset(b)
        # consolidated form: no (key, row) appears twice, no zero diffs
        seen = set()
        for k, d, row in c.rows():
            assert d != 0
            assert (k, row) not in seen
            seen.add((k, row))


def test_merge_consolidated_equals_consolidate_of_concat_sweep():
    rng = np.random.default_rng(3)
    for trial in range(60):
        with_obj = bool(trial % 3 == 1)
        a = _rand_batch(rng, int(rng.integers(0, 40)), with_obj=with_obj)
        b = _rand_batch(rng, int(rng.integers(0, 40)), time=1, with_obj=with_obj)
        ca, cb = consolidate(a), consolidate(b)
        merged = merge_consolidated(ca, cb)
        expected = concat_batches([a, b])
        expected = consolidate(expected) if expected is not None else None
        if merged is None or len(merged) == 0:
            assert expected is None or len(expected) == 0
            continue
        assert _batches_equal(merged, expected), f"trial {trial}"


def test_merge_consolidated_disjoint_and_empty_edges():
    rng = np.random.default_rng(11)
    a = consolidate(_rand_batch(rng, 20, key_space=5))
    b_keys = np.arange(100, 110, dtype=np.uint64)
    b = consolidate(
        DeltaBatch(
            b_keys,
            np.ones(10, dtype=np.int64),
            {"a": np.arange(10, dtype=np.int64), "b": np.zeros(10)},
            0,
        )
    )
    m = merge_consolidated(a, b)
    assert _batches_equal(m, consolidate(concat_batches([a, b])))
    assert merge_consolidated(None, a) is a
    assert merge_consolidated(a, None) is a
    empty = DeltaBatch.empty(["a", "b"], 0)
    assert merge_consolidated(empty, a) is a


def test_net_input_batch_skips_sort_only_when_safe():
    rng = np.random.default_rng(5)
    # all-insert unique keys: returned AS IS (no copy, no sort)
    keys = rng.permutation(np.arange(50, dtype=np.uint64))
    b = DeltaBatch(keys, np.ones(50, dtype=np.int64), {"a": np.arange(50)}, 0)
    assert net_input_batch(b) is b
    # duplicate keys or retractions: full consolidate semantics
    for mod in ("dup", "retract"):
        if mod == "dup":
            kk = np.concatenate([keys[:10], keys[:10]])
            dd = np.ones(20, dtype=np.int64)
        else:
            kk = np.concatenate([keys[:10], keys[:10]])
            dd = np.concatenate([np.ones(10), -np.ones(10)]).astype(np.int64)
        bb = DeltaBatch(kk, dd, {"a": np.concatenate([np.arange(10)] * 2)}, 0)
        assert _net_multiset(net_input_batch(bb)) == _net_multiset(bb)


# -------------------------------------------------------------- capture fold


def _apply_reference(batches):
    cur, deltas = {}, []
    for batch in batches:
        for k, d, row in batch.rows():
            deltas.append((batch.time, k, d, row))
            if d > 0:
                cur[k] = row
            else:
                cur.pop(k, None)
    return cur, deltas


def test_capture_lazy_fold_matches_sequential_apply():
    rng = np.random.default_rng(9)
    for trial in range(25):
        node = ops.CaptureNode(["a", "b"])
        batches = [
            _rand_batch(rng, int(rng.integers(1, 30)), key_space=8, time=t)
            for t in range(int(rng.integers(1, 6)))
        ]
        for b in batches:
            node.process([b], b.time)
            if trial % 2 and rng.random() < 0.5:
                node.current  # interleaved reads must not disturb the fold
        ref_cur, ref_deltas = _apply_reference(batches)
        assert node.current == ref_cur
        assert node.deltas == ref_deltas


def test_capture_snapshot_restore_roundtrip():
    rng = np.random.default_rng(13)
    node = ops.CaptureNode(["a", "b"])
    b = _rand_batch(rng, 20, time=0)
    node.process([b], 0)
    snap = node.snapshot_state()
    node2 = ops.CaptureNode(["a", "b"])
    node2.restore_state(snap)
    assert node2.current == node.current
    assert node2.deltas == node.deltas
    # restored node keeps accepting batches
    b2 = _rand_batch(rng, 10, time=1)
    node.process([b2], 1)
    node2.process([b2], 1)
    assert node2.current == node.current


# ------------------------------------------------------- compaction parity


def test_multimap_merge_compaction_matches_reference():
    rng = np.random.default_rng(21)
    for trial in range(10):
        mm = ColumnarMultimap(1)
        live = []  # (jk, rk, val) reference
        rk_counter = 0
        for step in range(int(rng.integers(2, 18))):
            n = int(rng.integers(1, 40))
            jk = rng.integers(0, 10, n).astype(np.uint64)
            rk = np.arange(rk_counter, rk_counter + n, dtype=np.uint64)
            rk_counter += n
            vals = np.empty(n, dtype=object)
            vals[:] = [f"v{int(x)}" for x in range(n)]
            mm.insert(jk, rk, [vals])
            live.extend(zip(jk.tolist(), rk.tolist(), vals.tolist()))
            if rng.random() < 0.4 and live:
                kill = rng.choice(len(live), size=min(8, len(live)), replace=False)
                kj = np.array([live[i][0] for i in kill], dtype=np.uint64)
                kr = np.array([live[i][1] for i in kill], dtype=np.uint64)
                mm.delete(kj, kr)
                dead_rk = set(kr.tolist())
                live = [r for r in live if r[1] not in dead_rk]
        mm._compact()
        assert len(mm.segments) <= 1
        if mm.segments:
            seg = mm.segments[0]
            assert seg.sorted
            assert bool((seg.jk[1:] >= seg.jk[:-1]).all())
        q = np.array(sorted({j for j, _, _ in live} | {99}), dtype=np.uint64)
        q_idx, rks, cols = mm.match(q)
        got = sorted(zip(q[q_idx].tolist(), rks.tolist(), cols[0].tolist()))
        want = sorted(live)
        assert got == want, f"trial {trial}"


# ---------------------------------------------------------- phase attribution


def test_engine_phases_breakdown(monkeypatch):
    from pathway_tpu.observability import engine_phases

    monkeypatch.setenv("PATHWAY_ENGINE_PHASES", "on")
    engine_phases.reset()
    t = pw.debug.table_from_rows(
        pw.schema_from_types(k=int, v=int),
        [(i % 7, i, i // 16, 1) for i in range(256)],
        is_stream=True,
    )
    g = t.groupby(t.k).reduce(t.k, s=pw.reducers.sum(t.v))
    rows_of(g)
    snap = engine_phases.snapshot()
    engine_phases.reset()
    assert "groupby" in snap and snap["groupby"]["ms"] >= 0
    assert "capture" in snap
    for ph in snap.values():
        assert ph["calls"] > 0


def test_engine_phases_off_is_silent(monkeypatch):
    from pathway_tpu.observability import engine_phases

    monkeypatch.delenv("PATHWAY_ENGINE_PHASES", raising=False)
    engine_phases.reset()
    t = pw.debug.table_from_rows(
        pw.schema_from_types(k=int, v=int), [(1, 2), (3, 4)]
    )
    rows_of(t.groupby(t.k).reduce(t.k, s=pw.reducers.sum(t.v)))
    assert engine_phases.snapshot() == {}


# ------------------------------------------------- incremental byte identity

_EVENTS = None


def _bench_events():
    """filter+join+groupby rows WITH retractions: every 7th insert is later
    retracted — same ENGINE KEY, same values, the benched churn shape.
    Entries are ``(k, v, engine_key, diff)`` in stream order."""
    global _EVENTS
    if _EVENTS is None:
        rng = np.random.default_rng(17)
        n = 4000
        ks = rng.integers(0, 120, n).tolist()
        vs = rng.integers(0, 100, n).tolist()
        events = []
        for i, (k, v) in enumerate(zip(ks, vs)):
            events.append((k, v, i + 1, 1))
        for i in range(0, n, 7):
            events.append((ks[i], vs[i], i + 1, -1))
        _EVENTS = events
    return _EVENTS


def _identity_pipeline(incremental: bool, n_ticks: int = 16):
    from pathway_tpu.io.python import _StaticStreamSubject, read_subject

    events = _bench_events()
    schema = pw.schema_from_types(k=int, v=int)
    per = (len(events) + n_ticks - 1) // n_ticks
    stream = []
    for i, (k, v, key, d) in enumerate(events):
        t = (i // per) if incremental else 0
        stream.append((t, key, (k, v), d))
    stream.sort(key=lambda e: e[0])
    left = read_subject(_StaticStreamSubject(stream, ["k", "v"]), schema=schema)
    rng = np.random.default_rng(1)
    right = pw.debug.table_from_rows(
        pw.schema_from_types(k=int, w=int),
        list(zip(range(120), rng.integers(0, 50, 120).tolist())),
    )
    f = left.filter(left.v > 10)
    j = f.join(right, f.k == right.k).select(k=f.k, v=f.v, w=right.w)
    return j.groupby(j.k).reduce(j.k, s=pw.reducers.sum(j.v * j.w), c=pw.reducers.count())


def test_incremental_byte_identical_thread_runtime():
    static = rows_of(_identity_pipeline(incremental=False))
    incr = rows_of(_identity_pipeline(incremental=True))
    assert incr == static


def test_incremental_byte_identical_sharded_2_workers():
    from pathway_tpu.internals.logical import LogicalNode
    from pathway_tpu.parallel.sharded import ShardedRuntime

    def run_sharded(incremental):
        table = _identity_pipeline(incremental)
        cols = table.column_names()
        holder = {}

        def factory():
            node = ops.CaptureNode(cols)
            holder["n"] = node
            return node

        lnode = LogicalNode(factory, [table._node], name="capture")
        rt = ShardedRuntime(n_workers=2, autocommit_duration_ms=5)
        rt.run([lnode])
        return dict(holder["n"].current)

    assert run_sharded(True) == run_sharded(False)


_CLUSTER_PIPELINE = textwrap.dedent(
    """
    import sys

    import numpy as np

    import pathway_tpu as pw

    out = sys.argv[1]
    mode = sys.argv[2] if len(sys.argv) > 2 else "incremental"

    rng = np.random.default_rng(17)
    n = 1500
    ks = rng.integers(0, 60, n).tolist()
    vs = rng.integers(0, 100, n).tolist()
    events = [(k, v, i + 1, 1) for i, (k, v) in enumerate(zip(ks, vs))]
    events += [(ks[i], vs[i], i + 1, -1) for i in range(0, n, 7)]

    n_ticks = 12 if mode == "incremental" else 1
    per = (len(events) + n_ticks - 1) // n_ticks
    from pathway_tpu.io.python import _StaticStreamSubject, read_subject

    stream = []
    for i, (k, v, key, d) in enumerate(events):
        stream.append((i // per, key, (k, v), d))
    stream.sort(key=lambda e: e[0])
    left = read_subject(
        _StaticStreamSubject(stream, ["k", "v"]),
        schema=pw.schema_from_types(k=int, v=int),
    )
    right = pw.debug.table_from_rows(
        pw.schema_from_types(k=int, w=int),
        list(zip(range(60), np.random.default_rng(1).integers(0, 50, 60).tolist())),
    )
    f = left.filter(left.v > 10)
    j = f.join(right, f.k == right.k).select(k=f.k, v=f.v, w=right.w)
    g = j.groupby(j.k).reduce(
        j.k, s=pw.reducers.sum(j.v * j.w), c=pw.reducers.count()
    )
    pw.io.fs.write(g, out + ".csv", format="csv")
    pw.run()
    """
)


def test_incremental_byte_identical_2proc_cluster(tmp_path):
    script = tmp_path / "pipeline.py"
    script.write_text(_CLUSTER_PIPELINE)

    solo_static = str(tmp_path / "solo_static")
    solo_incr = str(tmp_path / "solo_incr")
    dist_incr = str(tmp_path / "dist_incr")

    for out, mode, procs in (
        (solo_static, "static", 1),
        (solo_incr, "incremental", 1),
        (dist_incr, "incremental", 2),
    ):
        _run_cluster_with_mode(str(script), out, mode, procs)

    read = lambda p: open(p + ".csv").read()  # noqa: E731
    # 1-proc and 2-proc incremental runs must be byte-identical files
    assert read(dist_incr) == read(solo_incr)
    # and the incremental update stream must NET to exactly the one-shot
    # static state (the stream legitimately logs intermediate aggregate
    # corrections at their tick times; the net effect may not differ)
    assert _net_csv(read(solo_incr)) == _net_csv(read(solo_static))
    assert _net_csv(read(dist_incr)) == _net_csv(read(solo_static))


def _net_csv(text: str) -> dict:
    """CSV update stream → net multiset of value rows (time dropped)."""
    from collections import Counter

    lines = text.strip().splitlines()
    header = lines[0].split(",")
    ti, di = header.index("time"), header.index("diff")
    net: Counter = Counter()
    for line in lines[1:]:
        parts = line.split(",")
        row = tuple(p for i, p in enumerate(parts) if i not in (ti, di))
        net[row] += int(parts[di])
    return {k: v for k, v in net.items() if v != 0}


def _run_cluster_with_mode(
    script: str,
    out: str,
    mode: str,
    processes: int,
    script_args: tuple = (),
    threads: int = 1,
):
    import subprocess

    from conftest import free_port_base
    from test_cluster import REPO

    env = dict(os.environ)
    env.update(
        PATHWAY_PROCESSES=str(processes),
        PATHWAY_THREADS=str(threads),
        PATHWAY_BARRIER_TIMEOUT="45",
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO,
    )
    if processes > 1:
        env["PATHWAY_FIRST_PORT"] = str(free_port_base(processes + 1))
    procs = []
    for pid in range(processes):
        penv = dict(env, PATHWAY_PROCESS_ID=str(pid))
        procs.append(
            subprocess.Popen(
                [sys.executable, script, out, mode, *script_args],
                env=penv,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    for p in procs:
        stdout, _ = p.communicate(timeout=120)
        assert p.returncode == 0, f"process exited {p.returncode}:\n{stdout}"


# ------------------------------------------------- r15 fused tick kernels
# The reference the fused stream is held to is the SAME loop over a plan
# with no chains (every node its own step): ``build_plan(..., fuse=False)``,
# an argument only these tests pass.


def _no_chains(monkeypatch):
    """Every plan built from here on has no chains (undone at test exit, or
    by the ``monkeypatch.context()`` the caller holds)."""
    import functools

    from pathway_tpu.engine import fusion

    monkeypatch.setattr(
        fusion, "build_plan", functools.partial(fusion.build_plan, fuse=False)
    )


def _deltas_with_fuse(monkeypatch, fuse: bool, incremental: bool = True):
    from utils import deltas_of

    with monkeypatch.context() as m:
        if not fuse:
            _no_chains(m)
        return deltas_of(_identity_pipeline(incremental=incremental))


def test_fused_vs_unfused_byte_identity_thread(monkeypatch):
    """The r15 acceptance bar on the thread runtime: the RAW per-tick delta
    stream (not just the net state) of the benched filter+join+groupby
    pipeline with retractions is byte-identical with chains fused vs one
    node per step, for both the incremental and static runs."""
    for incremental in (True, False):
        fused = _deltas_with_fuse(monkeypatch, True, incremental)
        unfused = _deltas_with_fuse(monkeypatch, False, incremental)
        assert fused == unfused


def test_fused_vs_unfused_byte_identity_sharded_2_workers(monkeypatch):
    from pathway_tpu.internals.logical import LogicalNode
    from pathway_tpu.parallel.sharded import ShardedRuntime

    def run_sharded(fuse: bool):
        with monkeypatch.context() as m:
            if not fuse:
                _no_chains(m)
            table = _identity_pipeline(incremental=True)
            cols = table.column_names()
            holder = {}

            def factory():
                node = ops.CaptureNode(cols)
                holder["n"] = node
                return node

            lnode = LogicalNode(factory, [table._node], name="capture")
            rt = ShardedRuntime(n_workers=2, autocommit_duration_ms=5)
            rt.run([lnode])
            return dict(holder["n"].current)

    assert run_sharded(True) == run_sharded(False)


#: the child script's own switch: ``nochains`` as its third argument builds
#: every plan without chains
_NO_CHAINS_PRELUDE = textwrap.dedent(
    """
    import functools
    import sys

    from pathway_tpu.engine import fusion

    if sys.argv[3:] == ["nochains"]:
        fusion.build_plan = functools.partial(fusion.build_plan, fuse=False)
    """
)


def test_fused_vs_unfused_byte_identical_2proc_cluster(tmp_path):
    """2-proc cluster: the written update stream must be byte-for-byte
    identical with chains fused vs one node per step."""
    script = tmp_path / "pipeline.py"
    script.write_text(_NO_CHAINS_PRELUDE + _CLUSTER_PIPELINE)
    outs = {}
    for chains in ("chains", "nochains"):
        out = str(tmp_path / f"fuse_{chains}")
        _run_cluster_with_mode(str(script), out, "incremental", 2, (chains,))
        outs[chains] = open(out + ".csv").read()
    assert outs["chains"] == outs["nochains"]


def test_fused_chain_embed_knn_rerank_byte_identity(monkeypatch):
    """The serving-shaped chain (embed → KNN → rerank → selects) delivers a
    byte-identical subscriber stream fused vs unfused."""
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.mocks import FakeEmbedder
    from pathway_tpu.xpacks.llm.rerankers import EncoderReranker
    from pathway_tpu.internals.parse_graph import G

    def run(fuse: bool):
        with monkeypatch.context() as m:
            if not fuse:
                _no_chains(m)
            G.clear()
            emb = FakeEmbedder(dimension=12)
            docs = [f"document number {i} about topic {i % 3}" for i in range(12)]
            doc_t = pw.debug.table_from_rows(
                pw.schema_from_types(text=str), [(d,) for d in docs]
            )
            index = BruteForceKnnFactory(embedder=emb).build_index(doc_t.text, doc_t)
            q_t = pw.debug.table_from_rows(
                pw.schema_from_types(qi=int, q=str),
                [(i, docs[i], i // 4, 1) for i in range(8)],
                is_stream=True,
            )
            picked = index.query_as_of_now(q_t.q, number_of_matches=1).select(
                qi=pw.left.qi,
                q=pw.left.q,
                top=pw.apply(lambda ts: ts[0] if ts else "", pw.right.text),
            )
            rr = EncoderReranker(emb)
            scored = picked.select(
                picked.qi, picked.top, score=rr(picked.top, picked.q)
            )
            stream: list = []
            pw.io.subscribe(
                scored,
                on_change=lambda key, row, time, is_addition: stream.append(
                    (key, tuple(sorted(row.items())), is_addition)
                ),
            )
            pw.run(monitoring_level="none")
            return stream

    fused = run(True)
    unfused = run(False)
    assert fused and fused == unfused


def test_fused_chain_smoke(monkeypatch):
    """Tier-1-speed smoke: the benched pipeline builds a real multi-node
    chain with a composed expression segment, fused ticks execute its
    compiled register program, and the answer is right — fusion cannot
    silently rot behind the default."""
    from pathway_tpu.engine import fusion

    built: list = []
    ran: list = []
    orig_plan = fusion.build_plan
    orig_fast = fusion.ComposedSegment._run_fast

    def spy_plan(graph, exchange_aware, transient=False):
        plan = orig_plan(graph, exchange_aware, transient=transient)
        built.append(plan)
        return plan

    def spy_fast(self, prog, batch, time, aud=None):
        ran.append(len(batch))
        return orig_fast(self, prog, batch, time, aud)

    monkeypatch.setattr(fusion, "build_plan", spy_plan)
    monkeypatch.setattr(fusion.ComposedSegment, "_run_fast", spy_fast)
    t = pw.debug.table_from_rows(
        pw.schema_from_types(k=int, v=int),
        [(i % 5, i, i // 32, 1) for i in range(128)],
        is_stream=True,
    )
    f = t.filter(t.v > 3)
    s = f.select(k=f.k, d=f.v * 2)
    s2 = s.select(k=s.k, d=s.d, e=s.d + 1)
    g = s2.groupby(s2.k).reduce(s2.k, s=pw.reducers.sum(s2.e))
    got = rows_of(g)
    chains = built[-1].chains
    assert chains, "benched pipeline must fuse at least one chain"
    assert any(len(c.members) >= 3 for c in chains)
    segs = [u[1] for c in chains for u in c.units if u[0] == "seg"]
    assert segs, "filter+select+select must collapse into a ComposedSegment"
    assert ran, "fused ticks must execute the compiled register program"
    # and the answer matches the plan with no chains
    monkeypatch.setattr(fusion, "build_plan", orig_plan)
    _no_chains(monkeypatch)
    n_fused = len(ran)
    assert got == rows_of(g)
    assert len(ran) == n_fused, "a plan with no chains ran a composed segment"


# ------------------------------------------- one tick loop, three runtimes
# The loop is written once (engine/graph.py): every runtime records the same
# phases of a tick and delivers the same delta stream.

_PHASES_PIPELINE = textwrap.dedent(
    """
    import sys

    import pathway_tpu as pw

    out = sys.argv[1]
    t = pw.debug.table_from_rows(
        pw.schema_from_types(k=int, t=int),
        [(i % 4, i, i // 5, 1) for i in range(40)],
        is_stream=True,
    )
    b = t._buffer(pw.this.t + 5, pw.this.t)  # releases rows at the frontier
    f = b.filter(b.t % 7 != 0)
    g = f.groupby(f.k).reduce(f.k, s=pw.reducers.sum(f.t), c=pw.reducers.count())
    pw.io.fs.write(g, out + ".csv", format="csv")
    pw.run()
    """
)


def _phases_run(tmp_path, monkeypatch, name: str, threads: int, processes: int):
    """(phase span names, written update stream) of one run of the pipeline.
    A chain's span stands for its members, so it is expanded into them: the
    exchange-aware plans of the multi-worker runtimes cut chains shorter."""
    import json
    import re

    script = tmp_path / "phases.py"
    script.write_text(_PHASES_PIPELINE)
    out = str(tmp_path / name)
    monkeypatch.setenv("PATHWAY_TRACE", "on")
    monkeypatch.setenv("PATHWAY_TRACE_LIVE_FILE", out + ".spans")
    _run_cluster_with_mode(str(script), out, "incremental", processes, threads=threads)
    paths = (
        [f"{out}.spans.p{pid}" for pid in range(processes)]
        if processes > 1
        else [out + ".spans"]
    )
    names = set()
    for path in paths:
        with open(path) as fh:
            for line in fh:
                for sp in json.loads(line)["resourceSpans"][0]["scopeSpans"][0]["spans"]:
                    n = sp["name"].replace(out, "OUT")  # the sink's name
                    chain = re.fullmatch(r"sweep/chain\{(.*)\}", n)
                    if chain:
                        names.update(f"sweep/{m}" for m in chain.group(1).split("+"))
                    # tick/wait belongs to the RUN loop, which is still
                    # written three times (ROADMAP C1b)
                    elif n != "tick/wait" and (
                        n == "tick" or n.startswith(("tick/", "sweep/", "frontier/"))
                    ):
                        names.add(n)
    return names, open(out + ".csv").read()


@pytest.fixture(scope="module")
def _phases_single(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    try:
        return _phases_run(tmp_path_factory.mktemp("phases"), mp, "single", 1, 1)
    finally:
        mp.undo()


@pytest.mark.parametrize(
    "threads,processes",
    [(1, 1), (2, 1), (1, 2)],
    ids=["single", "sharded-2-workers", "cluster-2-procs"],
)
def test_every_runtime_records_the_same_tick_phases(
    tmp_path, monkeypatch, _phases_single, threads, processes
):
    want_names, want_stream = _phases_single
    families = {n if n == "tick" else n[: n.rindex("/") + 1] for n in want_names}
    assert families >= {"tick", "tick/poll/", "sweep/", "frontier/"}, want_names
    assert {"tick/complete", "tick/done", "frontier/buffer"} <= want_names
    names, stream = _phases_run(tmp_path, monkeypatch, "again", threads, processes)
    assert names == want_names
    assert stream == want_stream and stream.count("\n") > 8


def test_fused_chain_jit_shape_set_closed_under_churn(monkeypatch):
    """PATHWAY_FUSE_JAX=on: 50 ticks of churning row counts must keep the
    fused chain kernel's jit shape set within the pow-2 bucket bound."""
    jax = pytest.importorskip("jax")  # noqa: F841
    from pathway_tpu.engine.jax_kernels import _bucket
    from pathway_tpu.observability import device as device_mod

    monkeypatch.setenv("PATHWAY_FUSE_JAX", "on")
    rng = np.random.default_rng(23)
    sizes = [int(rng.integers(1, 900)) for _ in range(50)]
    rows = []
    for tick, sz in enumerate(sizes):
        for i in range(sz):
            rows.append((int(rng.integers(0, 50)), int(rng.integers(0, 100)), tick, 1))
    t = pw.debug.table_from_rows(
        pw.schema_from_types(k=int, v=int), rows, is_stream=True
    )
    f = t.filter(t.v > 10)
    s = f.select(k=f.k, d=f.v * 3)
    s2 = s.select(k=s.k, e=s.d + s.k)
    # the plan (and its jit wrappers) die with the runtime — record the
    # fused-chain wrappers as they are created
    created: list = []
    orig_tj = device_mod.traced_jit

    def rec(label, fn):
        w = orig_tj(label, fn)
        if label.startswith("engine.fused_chain/"):
            created.append(w)
        return w

    monkeypatch.setattr(device_mod, "traced_jit", rec)
    out = rows_of(s2)
    assert out
    assert created, "the fused chain kernel was never built"
    assert any(w.calls > 0 for w in created), "the jitted kernel never ran"
    allowed = len({_bucket(sz) for sz in sizes})
    for w in created:
        assert len(w._seen) <= allowed, (
            f"{w.label}: {len(w._seen)} jit shapes for {allowed} buckets — "
            "the chain shape set is not closed under churn"
        )


def test_multimap_duplicate_delete_requests_do_not_corrupt_counts():
    """Review regression (pre-existing, surfaced by the r11 fuzz): duplicate
    (jk, rk) pairs in ONE delete call matched the same live offset twice and
    double-counted n_dead — live rows turned invisible and compaction dropped
    whole segments."""
    mm = ColumnarMultimap(1)
    vals = np.empty(2, dtype=object)
    vals[:] = ["a", "b"]
    mm.insert(
        np.array([0, 5], dtype=np.uint64), np.array([959, 401], dtype=np.uint64), [vals]
    )
    mm.delete(
        np.array([5, 5], dtype=np.uint64), np.array([401, 401], dtype=np.uint64)
    )
    assert mm.n_live == 1
    q_idx, rks, cols = mm.match(np.array([0], dtype=np.uint64))
    assert rks.tolist() == [959] and cols[0].tolist() == ["a"]
    mm._compact()
    q_idx, rks, _ = mm.match(np.array([0], dtype=np.uint64))
    assert rks.tolist() == [959]  # survives compaction too


def test_multimap_insert_only_arrangement_stays_bounded():
    """Probe-triggered compaction must not let a never-read store fragment
    without bound: the insert-time HARD backstop caps segment count, and a
    probe against a store fragmented past MAX_SEGMENTS compacts it."""
    mm = ColumnarMultimap(1)
    for i in range(200):
        v = np.empty(4, dtype=object)
        v[:] = [i] * 4
        mm.insert(
            np.arange(4, dtype=np.uint64),
            np.arange(i * 4, i * 4 + 4, dtype=np.uint64),
            [v],
        )
    assert len(mm.segments) <= ColumnarMultimap.MAX_SEGMENTS_HARD + 1
    assert mm.n_live == 800
    # probing while mildly fragmented (< MAX_SEGMENTS leftover segments) must
    # still see every live row — and must NOT compact, that's the amortization
    # the tick benchmark relies on (merge every ~MAX_SEGMENTS ticks, not every
    # probe)
    n_before = len(mm.segments)
    assert n_before <= ColumnarMultimap.MAX_SEGMENTS
    q_idx, rks, _ = mm.match(np.arange(4, dtype=np.uint64))
    assert len(rks) == 800
    assert len(mm.segments) == n_before

    # past MAX_SEGMENTS, the first probe compacts to the steady-state single
    # segment (probe-triggered, not insert-triggered)
    mm2 = ColumnarMultimap(1)
    n_frag = ColumnarMultimap.MAX_SEGMENTS + 4
    for i in range(n_frag):
        v = np.empty(4, dtype=object)
        v[:] = [i] * 4
        mm2.insert(
            np.arange(4, dtype=np.uint64),
            np.arange(i * 4, i * 4 + 4, dtype=np.uint64),
            [v],
        )
    assert len(mm2.segments) == n_frag
    q_idx, rks, _ = mm2.match(np.arange(4, dtype=np.uint64))
    assert len(rks) == n_frag * 4
    assert len(mm2.segments) == 1


# --------------------------------------------- block work is done per block
# A count of work, not a timing: the per-row paths PR 29 took out of the
# store's chain steps cannot come back unnoticed.

_BLOCK_DOCS = 4096


def _counting(monkeypatch, module, name):
    calls = []
    fn = getattr(module, name)

    def counted(*args, **kwargs):
        calls.append(1)
        return fn(*args, **kwargs)

    monkeypatch.setattr(module, name, counted)
    return calls


def test_flatten_hashes_a_block_in_a_constant_number_of_calls(monkeypatch):
    from pathway_tpu.internals import keys

    chunks = np.empty(_BLOCK_DOCS, dtype=object)
    for i in range(_BLOCK_DOCS):
        chunks[i] = [(f"text {i}.{j}", {}) for j in range(1 + i % 3)]
    batch = DeltaBatch(
        keys.splitmix64(np.arange(_BLOCK_DOCS, dtype=np.uint64)),
        np.ones(_BLOCK_DOCS, dtype=np.int64),
        {"chunks": chunks, "meta": np.arange(_BLOCK_DOCS)},
        0,
    )
    in_ops = _counting(monkeypatch, ops, "splitmix64")
    in_keys = _counting(monkeypatch, keys, "splitmix64")
    (out,) = ops.FlattenNode("chunks", ["meta"]).process([batch], 0)
    assert len(out) == sum(1 + i % 3 for i in range(_BLOCK_DOCS))
    assert len(in_ops) + len(in_keys) <= 4


def test_groupby_encodes_each_distinct_value_of_a_block_once(monkeypatch):
    from pathway_tpu.engine import reducers_impl

    distinct = [-0.5, 0, 1, 2.5, 3, 7, 11, 13]
    t = pw.debug.table_from_rows(
        pw.schema_from_types(g=int, v=float, s=str),
        [(i % 3, distinct[i % 8], f"s{i % 5}") for i in range(_BLOCK_DOCS)],
    )
    encoded = _counting(monkeypatch, reducers_impl, "_canonical_bytes")
    r = t.groupby(t.g).reduce(
        t.g,
        lo=pw.reducers.min(t.v),
        hi=pw.reducers.max(t.v),
        some=pw.reducers.any(t.s),
        all=pw.reducers.sorted_tuple(t.s),
    )
    rows = rows_of(r)
    assert {row[:3] for row in rows} == {(g, -0.5, 13.0) for g in range(3)}
    assert sum(len(row[4]) for row in rows) == _BLOCK_DOCS
    # one tick, four reducers: 8 + 8 distinct numbers, 5 + 5 distinct strings
    assert 0 < len(encoded) <= 26
