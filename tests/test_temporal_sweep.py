"""ROADMAP #5 seed: temporal edge-case sweep (ISSUE 8 satellite).

Parametrized probes of the classic incremental-engine bug nests — late data
exactly AT the window cutoff and watermark ties at frontier close — run with
the r12 audit plane on (``PATHWAY_AUDIT=full``) so the data-plane invariant
monitors themselves get exercised by window retract/insert churn, on the
thread runtime AND (the tie case) a real 2-process cluster with byte-identical
output.

Cutoff semantics under sweep (``_freeze``): a late row is DROPPED iff the
watermark (max time seen at the last frontier) is ``>=`` its window's
``end + cutoff`` when it arrives — so the exact-tie arrival is dropped, and a
same-tick tie (row arrives in the tick that ADVANCES the watermark to the
threshold) is kept, because the watermark only moves at frontier close.
"""

from __future__ import annotations

import csv
import os
import subprocess
import sys
import textwrap

import pytest

import pathway_tpu as pw
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.observability import audit as audit_mod
from utils import rows_of
from conftest import free_port_base

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DURATION = 10
CUTOFF = 5
# window A = [0, 10): freeze threshold = 10 + 5 = 15


def _window_counts(late_tick_time: int, wm_t: int, late_t: int = 9):
    """Tumbling windows over: an on-time A row, a watermark-advancing B row,
    and a late A row arriving at ``late_tick_time``. Returns net rows."""
    G.clear()
    t = pw.debug.table_from_markdown(
        f'''
            | t        | __time__
        1   | 2        | 2
        2   | {wm_t}   | 2
        3   | {late_t} | {late_tick_time}
        '''
    )
    r = t.windowby(
        t.t, window=pw.temporal.tumbling(duration=DURATION),
        behavior=pw.temporal.common_behavior(cutoff=CUTOFF),
    ).reduce(pw.this._pw_window_start, cnt=pw.reducers.count())
    return rows_of(r)


@pytest.mark.parametrize(
    "offset,late_counted",
    [
        (-1, True),   # wm 14 < 15: late row still inside the cutoff
        (0, False),   # wm == 15 exactly: the tie at the cutoff — dropped (>=)
        (1, False),   # wm 16 > 15: unambiguously late
    ],
)
def test_late_row_exactly_at_window_cutoff_thread(monkeypatch, offset, late_counted):
    monkeypatch.setenv("PATHWAY_AUDIT", "full")
    out = _window_counts(late_tick_time=4, wm_t=15 + offset)
    expect_a = 2 if late_counted else 1
    assert out.get((0, expect_a)) == 1, out  # window A count
    assert (0, 2 if not late_counted else 1) not in out
    # the monitors ran over the window churn without false positives
    plane = audit_mod.current()
    assert plane is not None and plane.violation_counts == {}


@pytest.mark.parametrize("offset", [-1, 0, 1])
def test_same_tick_watermark_tie_is_kept_thread(monkeypatch, offset):
    """The 'late' row rides the SAME tick as the row advancing the watermark
    to threshold+offset: the watermark only moves at frontier close, so the
    row is on time regardless of offset — for every offset, window A counts
    both rows."""
    monkeypatch.setenv("PATHWAY_AUDIT", "full")
    out = _window_counts(late_tick_time=2, wm_t=15 + offset)
    assert out.get((0, 2)) == 1, out
    assert audit_mod.current().violation_counts == {}


@pytest.mark.parametrize("offset,released_late", [(-1, True), (0, False), (1, False)])
def test_buffer_threshold_tie_at_frontier_close(monkeypatch, offset, released_late):
    """_buffer release at an exact watermark tie: a buffered row whose
    threshold equals the watermark releases (>=); one past it waits for the
    close flush. Either way no row is lost at END_OF_STREAM — and both paths
    run under the full audit plane."""
    monkeypatch.setenv("PATHWAY_AUDIT", "full")
    G.clear()
    t = pw.debug.table_from_markdown(
        f'''
            | t            | __time__
        1   | 5            | 2
        2   | {10 + offset} | 4
        '''
    )
    buffered = t._buffer(pw.this.t + 5, pw.this.t)  # row t=5 releases at wm>=10
    from utils import deltas_of

    deltas = deltas_of(buffered)
    released = {d[3][0]: d[0] for d in deltas if d[2] > 0}
    assert set(released) == {5, 10 + offset}  # nothing lost at close
    from pathway_tpu.engine.graph import END_OF_STREAM

    if released_late:
        # wm only reached 9 < 10: the buffered row waited for the close flush
        assert released[5] == END_OF_STREAM, released
    else:
        # tie (wm == 10) and past-tie both release at a live frontier
        assert released[5] != END_OF_STREAM, released
    assert audit_mod.current().violation_counts == {}


# --------------------------------------------------- 2-proc cluster parity

_SWEEP_PIPELINE = textwrap.dedent(
    """
    import sys

    import pathway_tpu as pw

    out = sys.argv[1]
    t = pw.debug.table_from_markdown(
        '''
            | t  | __time__
        1   | 2  | 2
        2   | 15 | 2
        3   | 9  | 4
        4   | 14 | 6
        5   | 3  | 6
        '''
    )
    w = t.windowby(
        t.t, window=pw.temporal.tumbling(duration=10),
        behavior=pw.temporal.common_behavior(cutoff=5),
    ).reduce(
        start=pw.this._pw_window_start,
        cnt=pw.reducers.count(),
        mx=pw.reducers.max(pw.this.t),
    )
    pw.io.fs.write(w, out + ".window.csv", format="csv")
    b = t._buffer(pw.this.t + 5, pw.this.t)
    pw.io.fs.write(b, out + ".buffer.csv", format="csv")
    pw.run()
    """
)


def _run_procs(script: str, out: str, processes: int) -> None:
    env = dict(os.environ)
    env.update(
        PATHWAY_PROCESSES=str(processes),
        PATHWAY_THREADS="1",
        PATHWAY_BARRIER_TIMEOUT="45",
        PATHWAY_AUDIT="full",
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
                [sys.executable, script, out],
                env=penv,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    for p in procs:
        stdout, _ = p.communicate(timeout=120)
        assert p.returncode == 0, stdout


def _net(path: str) -> dict:
    state: dict = {}
    with open(path) as fh:
        for rec in csv.DictReader(fh):
            key = tuple(
                v for k, v in sorted(rec.items()) if k not in ("time", "diff")
            )
            state[key] = state.get(key, 0) + int(rec["diff"])
    return {k: v for k, v in state.items() if v != 0}


# ------------------------------------- session-merge edges (ROADMAP #6, r17)


def _session_table(md: str):
    G.clear()
    t = pw.debug.table_from_markdown(md)
    return t.windowby(t.t, window=pw.temporal.session(max_gap=6)).reduce(
        start=pw.this._pw_window_start,
        end=pw.this._pw_window_end,
        cnt=pw.reducers.count(),
    )


def test_session_merge_retracts_both_emitted_sessions(monkeypatch):
    """A late bridging row lands in the GAP between two already-emitted
    sessions: both retract and one merged session replaces them — the classic
    incremental session-merge edge, run under the full audit plane."""
    monkeypatch.setenv("PATHWAY_AUDIT", "full")
    r = _session_table(
        '''
            | t  | __time__
        1   | 0  | 2
        2   | 10 | 2
        3   | 5  | 4
        '''
    )
    from utils import deltas_of

    deltas = deltas_of(r)
    out = rows_of(r)
    assert out == {(0, 10, 3): 1}, out
    # the separate sessions really were EMITTED at tick 2, then retracted at
    # tick 4 when the bridge arrived — not silently skipped
    emitted_t2 = {d[3] for d in deltas if d[0] == 2 and d[2] > 0}
    assert (0, 0, 1) in emitted_t2 and (10, 10, 1) in emitted_t2, deltas
    retracted_t4 = {d[3] for d in deltas if d[0] == 4 and d[2] < 0}
    assert (0, 0, 1) in retracted_t4 and (10, 10, 1) in retracted_t4, deltas
    assert audit_mod.current().violation_counts == {}


def test_session_split_on_bridge_deletion(monkeypatch):
    """Deleting the bridge row of an emitted merged session splits it back
    into two — the retraction-of-emitted-window inverse edge."""
    monkeypatch.setenv("PATHWAY_AUDIT", "full")
    r = _session_table(
        '''
            | t  | __time__ | __diff__
        1   | 0  | 2        | 1
        2   | 10 | 2        | 1
        3   | 5  | 2        | 1
        3   | 5  | 4        | -1
        '''
    )
    out = rows_of(r)
    assert out == {(0, 0, 1): 1, (10, 10, 1): 1}, out
    from utils import deltas_of

    # the merged [0, 10] session was emitted, then retracted by the deletion
    deltas = deltas_of(r)
    assert any(d[0] == 2 and d[2] > 0 and d[3] == (0, 10, 3) for d in deltas)
    assert any(d[0] == 4 and d[2] < 0 and d[3] == (0, 10, 3) for d in deltas)
    assert audit_mod.current().violation_counts == {}


@pytest.mark.parametrize("gap_offset,merged", [(-1, False), (0, False), (1, True)])
def test_session_gap_boundary_tie(monkeypatch, gap_offset, merged):
    """Exactly AT the max_gap the rows do NOT group (the predicate is
    ``b - a < max_gap``, strict) — the tie sits on the split side; one past
    it merges. Pins the boundary so semantic drift is caught."""
    monkeypatch.setenv("PATHWAY_AUDIT", "full")
    # rows at t=0 and t=max_gap - gap_offset (gap 6): offsets -1/0 leave the
    # gap >= 6 (split), +1 brings it to 5 < 6 (merge)
    second = 6 - gap_offset
    r = _session_table(
        f'''
            | t         | __time__
        1   | 0         | 2
        2   | {second}  | 2
        '''
    )
    out = rows_of(r)
    if merged:
        assert out == {(0, second, 2): 1}, out
    else:
        assert out == {(0, 0, 1): 1, (second, second, 1): 1}, out
    assert audit_mod.current().violation_counts == {}


# --------------------------- prev_next retraction-of-emitted (ROADMAP #6, r17)


def _sorted_chain(md: str):
    G.clear()
    t = pw.debug.table_from_markdown(md)
    s = t.sort(t.t)
    joined = t.with_columns(prev=s.prev, next=s.next)
    prv = t.ix(joined.prev, optional=True)
    nxt = t.ix(joined.next, optional=True)
    return t.select(pw.this.t, pt=prv.t, nt=nxt.t)


def test_prev_next_insert_between_retracts_emitted_pointers(monkeypatch):
    """Inserting a row BETWEEN two already-emitted neighbors retracts both
    emitted pointer rows (10's next, 30's prev) and relinks through the new
    middle — the reference's prev_next bug nest, under the full audit
    plane."""
    monkeypatch.setenv("PATHWAY_AUDIT", "full")
    r = _sorted_chain(
        '''
            | t  | __time__
        1   | 10 | 2
        2   | 30 | 2
        3   | 20 | 4
        '''
    )
    from utils import deltas_of

    deltas = deltas_of(r)
    out = rows_of(r)
    assert out == {(10, None, 20): 1, (20, 10, 30): 1, (30, 20, None): 1}, out
    # the direct 10<->30 link really was emitted before the middle arrived
    emitted_t2 = {d[3] for d in deltas if d[0] == 2 and d[2] > 0}
    assert (10, None, 30) in emitted_t2 and (30, 10, None) in emitted_t2
    retracted_t4 = {d[3] for d in deltas if d[0] == 4 and d[2] < 0}
    assert (10, None, 30) in retracted_t4 and (30, 10, None) in retracted_t4
    assert audit_mod.current().violation_counts == {}


def test_prev_next_delete_middle_relinks(monkeypatch):
    """Deleting an emitted middle row retracts its pointer row AND both
    neighbors' rows, relinking prev<->next across the hole."""
    monkeypatch.setenv("PATHWAY_AUDIT", "full")
    r = _sorted_chain(
        '''
            | t  | __time__ | __diff__
        1   | 10 | 2        | 1
        2   | 20 | 2        | 1
        3   | 30 | 2        | 1
        2   | 20 | 4        | -1
        '''
    )
    out = rows_of(r)
    assert out == {(10, None, 30): 1, (30, 10, None): 1}, out
    from utils import deltas_of

    deltas = deltas_of(r)
    assert any(d[0] == 4 and d[2] < 0 and d[3] == (20, 10, 30) for d in deltas)
    assert audit_mod.current().violation_counts == {}


def test_temporal_sweep_cluster_matches_thread(tmp_path):
    """The cutoff-tie pipeline (late row at exactly window_end + cutoff, plus
    an in-cutoff late row) produces byte-identical net output on 1 and 2
    processes, with the full audit plane live on every process."""
    script = tmp_path / "sweep.py"
    script.write_text(_SWEEP_PIPELINE)
    solo = str(tmp_path / "solo")
    _run_procs(str(script), solo, processes=1)
    dist = str(tmp_path / "dist")
    _run_procs(str(script), dist, processes=2)
    for suffix in (".window.csv", ".buffer.csv"):
        assert _net(solo + suffix) == _net(dist + suffix), suffix
    # the tie row (t=9 arriving at wm==15) was dropped; the in-cutoff late
    # row (t=3 arriving at wm==15 for window [0,10)... also at the tie) —
    # pin the window-A count so semantic drift is caught, not just parity
    win = _net(solo + ".window.csv")
    a_rows = {k: v for k, v in win.items() if k[-1] == "0" or k[0] == "0"}
    assert a_rows, win


_SESSION_SORT_PIPELINE = textwrap.dedent(
    """
    import sys

    import pathway_tpu as pw

    out = sys.argv[1]
    t = pw.debug.table_from_markdown(
        '''
            | t  | __time__ | __diff__
        1   | 0  | 2        | 1
        2   | 10 | 2        | 1
        3   | 5  | 4        | 1
        4   | 20 | 4        | 1
        3   | 5  | 6        | -1
        5   | 12 | 6        | 1
        '''
    )
    sess = t.windowby(t.t, window=pw.temporal.session(max_gap=6)).reduce(
        start=pw.this._pw_window_start,
        end=pw.this._pw_window_end,
        cnt=pw.reducers.count(),
    )
    pw.io.fs.write(sess, out + ".session.csv", format="csv")
    s = t.sort(t.t)
    joined = t.with_columns(prev=s.prev, next=s.next)
    prv = t.ix(joined.prev, optional=True)
    nxt = t.ix(joined.next, optional=True)
    chain = t.select(pw.this.t, pt=prv.t, nt=nxt.t)
    pw.io.fs.write(chain, out + ".chain.csv", format="csv")
    pw.run()
    """
)


def test_session_merge_and_prev_next_cluster_matches_thread(tmp_path):
    """r17 satellite: the session-merge (bridge in, bridge deleted) and
    prev_next (insert-between, delete-middle) churn produces byte-identical
    net output on 1 and 2 processes, full audit plane live on every
    process."""
    script = tmp_path / "ss.py"
    script.write_text(_SESSION_SORT_PIPELINE)
    solo = str(tmp_path / "solo")
    _run_procs(str(script), solo, processes=1)
    dist = str(tmp_path / "dist")
    _run_procs(str(script), dist, processes=2)
    for suffix in (".session.csv", ".chain.csv"):
        assert _net(solo + suffix) == _net(dist + suffix), suffix
    # pin the semantics, not just the parity: after the bridge deletion the
    # merged [0, 10] session split, and 12 re-merged with 10
    sess = _net(solo + ".session.csv")
    assert sess == {("1", "0", "0"): 1, ("2", "12", "10"): 1, ("1", "20", "20"): 1}, sess
    # column order in _net keys is alphabetical: (nt, pt, t)
    chain = _net(solo + ".chain.csv")
    assert chain == {
        ("10", "", "0"): 1,
        ("12", "0", "10"): 1,
        ("20", "10", "12"): 1,
        ("", "12", "20"): 1,
    }, chain
