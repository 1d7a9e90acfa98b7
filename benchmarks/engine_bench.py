"""Relational-engine benchmark: static vs incremental throughput + phase tax.

VERDICT r1 demanded visibility into the dataflow engine's own throughput; the
ISSUE-6 hot-path overhaul demands the *ratio* — differential dataflow's
promise is incremental ≈ O(touched state), so
``engine_incremental_pct_of_static`` (BENCH_r05: 63) is the repo's
load-bearing weakness metric. This bench measures it reproducibly and
attributes it:

- ``python benchmarks/engine_bench.py [N] [N_TIMES]`` — one run, one JSON
  line (the r1-era interface, kept for ad-hoc probes).
- ``python benchmarks/engine_bench.py --small-ticks [ROWS ...]`` — the r15
  protocol: a deep stateless transform chain (filters / arithmetic maps /
  projections — the row-microbatch shape of RAG preprocessing pipelines)
  driven by pre-columnar delta blocks at 64/256/1024 rows per tick,
  best-of-``REPS`` ticks per second, and a quiescent-tick rate (empty ticks
  — the no-op sweep short-circuit). The fused-vs-``PATHWAY_FUSE=off`` A/B
  and its gate went with the second sweep (PR 30): there is one loop left
  to time.
- ``python benchmarks/engine_bench.py --full [N]`` — the r11 protocol:
  interleaved best-of-``REPS`` static (one load) vs incremental (the same
  rows over ``N_TIMES`` logical timestamps), a per-phase tick breakdown of
  the incremental run from the ``PATHWAY_ENGINE_PHASES`` attribution plane
  (consolidate / rehash / probe / groupby / join / realloc / kernel /
  exchange / capture), byte-identity assertion of incremental-vs-static
  output, and a **regression gate**: if the measured pct drops more than
  ``GATE_DROP_PTS`` points below the last committed BENCH value, warn — or
  exit 1 under ``BENCH_MODE=1`` (the observability_bench gate discipline).
  Writes BENCH_r11-style JSON to ``--out PATH`` (default: print only).

Pipeline (unchanged since BENCH_r05 for comparability): filter → join →
groupby/sum over N rows, right side N/10 keys.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

REPS = 5
N_TIMES = 20
GATE_DROP_PTS = 5.0


def _pipeline(n: int, n_times: int):
    import pathway_tpu as pw
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    rng = np.random.default_rng(0)
    lk = rng.integers(0, n // 10, n).tolist()
    lv = rng.integers(0, 100, n).tolist()
    schema_l = pw.schema_from_types(k=int, v=int)
    if n_times == 1:
        left = pw.debug.table_from_rows(schema_l, list(zip(lk, lv)))
    else:
        per = (n + n_times - 1) // n_times
        left = pw.debug.table_from_rows(
            schema_l,
            [(k, v, i // per, 1) for i, (k, v) in enumerate(zip(lk, lv))],
            is_stream=True,
        )
    right = pw.debug.table_from_rows(
        pw.schema_from_types(k=int, w=int),
        list(zip(range(n // 10), rng.integers(0, 100, n // 10).tolist())),
    )
    f = left.filter(left.v > 10)
    j = f.join(right, f.k == right.k).select(k=f.k, v=f.v, w=right.w)
    return j.groupby(j.k).reduce(j.k, s=pw.reducers.sum(j.v * j.w))


def run(n: int = 1_000_000, n_times: int = 1) -> dict:
    """``n_times=1``: one static load. ``n_times>1``: the same rows split over
    that many logical timestamps — the streaming/incremental path."""
    from tests.utils import rows_of

    g = _pipeline(n, n_times)
    t0 = time.perf_counter()
    out = rows_of(g)
    elapsed = time.perf_counter() - t0
    label = (
        f"{n} rows static load"
        if n_times == 1
        else f"{n} rows over {n_times} timestamps"
    )
    return {
        "metric": f"engine rows/s (filter+join+groupby, {label})",
        "value": round(n / elapsed, 0),
        "unit": "rows/s",
        "out_groups": len(out),
        "seconds": round(elapsed, 3),
        "rows": out,
    }


# ------------------------------------------------------------- small ticks (r15)

SMALL_TICKS = 300


def _small_tick_pipeline(blocks):
    """An 18-operator stateless transform chain — filters, arithmetic maps
    and the projection/rename plumbing real row-microbatch pipelines stack
    up (the reference's DocumentStore preprocessing shape: parse → unpack →
    select → rename → filter → select …). Fed by pre-columnar delta blocks:
    the engine's native unit, isolating the per-tick SWEEP cost from
    connector-side row materialization."""
    import pathway_tpu as pw
    from pathway_tpu.internals.logical import LogicalNode
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.internals.table import Table
    from pathway_tpu.internals.universe import Universe

    G.clear()
    src = LogicalNode(lambda: _BlockReplayNode(blocks), [], name="block_replay")
    schema = pw.schema_from_types(k=int, v=int, x=float)
    t = Table(src, schema, Universe())
    f = t.filter(t.v > 2)
    a = f.select(k=f.k, v=f.v, x=f.x, y=f.v * 3)
    b = a.select(k=a.k, v=a.v, x=a.x, z=a.y + a.v)
    b = b.rename(vv=b.v)
    c = b.select(k=b.k, v=b.vv, x=b.x, w=pw.if_else(b.x > 5.0, b.x, -b.x), z=b.z)
    d = c.filter(c.z < 400)
    e = d.select(k=d.k, v=d.v, x=d.x, s=d.z * 2 + d.v, w=d.w)
    e = e.select(k=e.k, v=e.v, x=e.x, s=e.s, w=e.w)  # projection plumbing
    g = e.select(k=e.k, v=e.v, x=e.x, s=e.s, w=e.w, q=e.s - e.v)
    h = g.filter(g.q >= 0)
    i = h.select(k=h.k, v=h.v, r=h.q * 3 + h.v, w=h.w, x=h.x)
    i = i.rename(rr=i.r)
    j = i.select(k=i.k, u=pw.if_else(i.rr > 100, i.rr, -i.rr), w=i.w, x=i.x, v=i.v)
    kk = j.filter(j.u < 3000)
    ll = kk.select(k=kk.k, u=kk.u, w=kk.w + kk.x, v=kk.v)
    return ll.select(k=ll.k, final=ll.u + ll.v, w=ll.w)


class _BlockReplayNode:
    """Source emitting one pre-built DeltaBatch per tick (defined lazily as
    a real Node subclass on first use — module import stays engine-free)."""

    def __new__(cls, blocks):
        from pathway_tpu.engine.blocks import DeltaBatch
        from pathway_tpu.engine.graph import END_OF_STREAM, SOLO, Node

        class _Replay(Node):
            name = "block_replay"

            def __init__(self, blocks):
                super().__init__(n_inputs=0)
                self.blocks = blocks
                self.i = 0

            def exchange_key(self, port):
                return SOLO

            def poll(self, t):
                if t == END_OF_STREAM or self.i >= len(self.blocks):
                    return []
                b = self.blocks[self.i]
                self.i += 1
                # blocks are pre-stamped with their tick time and freshly
                # built per run — emit directly, like a columnar connector
                return [b]

        return _Replay(blocks)


class _TickDriver:
    """Virtual connector driving exactly ``n`` engine ticks, no sleeps."""

    virtual = True

    def __init__(self, n: int):
        self.n = n
        self.t = 0

    def start(self) -> None: ...

    def stop(self) -> None: ...

    def is_finished(self) -> bool:
        self.t += 1
        return self.t >= self.n


def _small_tick_blocks(rpt: int, n_ticks: int, seed: int = 3):
    from pathway_tpu.engine.blocks import DeltaBatch

    rng = np.random.default_rng(seed)
    return [
        DeltaBatch(
            rng.integers(0, 1 << 62, rpt).astype(np.uint64),
            np.ones(rpt, dtype=np.int64),
            {
                "k": rng.integers(0, 1000, rpt).astype(np.int64),
                "v": rng.integers(0, 100, rpt).astype(np.int64),
                "x": rng.random(rpt) * 10,
            },
            t,
        )
        for t in range(n_ticks)
    ]


def _small_tick_run(rpt: int, n_ticks: int) -> tuple[float, dict]:
    """One engine run over ``n_ticks`` blocks; returns (engine seconds,
    final captured state). rpt=0 drives EMPTY ticks (quiescence cost)."""
    from pathway_tpu.engine import operators as ops
    from pathway_tpu.engine.runtime import Runtime
    from pathway_tpu.internals.logical import LogicalNode

    blocks = _small_tick_blocks(rpt, n_ticks) if rpt else _small_tick_blocks(64, 1)
    table = _small_tick_pipeline(blocks)
    holder: dict = {}
    cols = table.column_names()

    def factory():
        holder["n"] = ops.CaptureNode(cols)
        return holder["n"]

    ln = LogicalNode(factory, [table._node], name="capture")
    rt = Runtime(autocommit_duration_ms=5)
    rt.register_connector(_TickDriver(n_ticks))
    t0 = time.perf_counter()
    rt.run([ln])
    dt = time.perf_counter() - t0
    return dt, dict(holder["n"].current)


def _last_committed_metric(
    metric: str,
    exclude: str | None = None,
    tail_fallback: bool = False,
    raw: bool = False,
):
    """(value, filename) of ``metric`` in the newest committed BENCH_r*.json
    carrying it, or None. ``exclude`` skips the file the current run is
    about to overwrite; ``tail_fallback`` also greps r05-era files that
    wrapped their metrics inside a log tail string."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    best: tuple[int, float, str] | None = None
    for path in glob.glob(os.path.join(root, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        if exclude and os.path.abspath(path) == os.path.abspath(exclude):
            continue
        try:
            blob = json.loads(open(path).read())
        except (OSError, ValueError):
            continue
        if not isinstance(blob, dict):
            continue
        val = blob.get(metric)
        if val is None and tail_fallback and "tail" in blob:
            mm = re.search(rf'"{metric}":\s*([0-9.]+)', blob["tail"])
            val = float(mm.group(1)) if mm else None
        if val is None:
            continue
        rev = int(m.group(1))
        if best is None or rev > best[0]:
            best = (rev, val if raw else float(val), os.path.basename(path))
    if best is None:
        return None
    return best[1], best[2]


def small_ticks(
    rows_per_tick=(64, 256, 1024),
    n_ticks: int = SMALL_TICKS,
    reps: int = REPS,
    out_path: str | None = None,
) -> dict:
    """Tick rate at small tick sizes, best-of-reps; plus the quiescent
    (empty) tick rate."""
    results: dict = {"bench": "engine_small_ticks", "n_ticks": n_ticks, "reps": reps}
    spread = 1.0
    for rpt in rows_per_tick:
        times = [_small_tick_run(rpt, n_ticks)[0] for _ in range(reps)]
        results[f"small_tick_ticks_per_s_{rpt}"] = round(n_ticks / min(times), 1)
        spread = max(spread, max(times) / max(min(times), 1e-9))
    # quiescent ticks: nothing arrives — the sweep short-circuit
    best_q = min(_small_tick_run(0, 2000)[0] for _ in range(3))
    results["quiescent_ticks_per_s"] = round(2000 / best_q, 1)
    results["rep_spread_max"] = round(spread, 2)
    results["noisy_host"] = spread > 1.6
    return results


def _last_committed_pct(exclude: str | None = None) -> tuple[float, str] | None:
    """Newest committed BENCH_r*.json carrying the pct metric (delegates to
    the generic metric scan; keeps the r05-era fallback where the metrics
    were wrapped inside a log tail string)."""
    found = _last_committed_metric(
        "engine_incremental_pct_of_static",
        exclude=exclude,
        tail_fallback=True,
    )
    return found


def full(
    n: int = 300_000,
    reps: int = REPS,
    n_times: int = N_TIMES,
    out_path: str | None = None,
) -> dict:
    from pathway_tpu.observability import engine_phases

    best = {1: None, n_times: None}
    allruns: dict[int, list[float]] = {1: [], n_times: []}
    static_rows = incr_rows = None
    for _ in range(reps):
        for nt in (1, n_times):
            r = run(n, nt)
            allruns[nt].append(round(n / r["seconds"], 1))
            if best[nt] is None or r["seconds"] < best[nt]:
                best[nt] = r["seconds"]
            if nt == 1:
                static_rows = r["rows"]
            else:
                incr_rows = r["rows"]

    # byte-identity: the incremental run's final multiset must equal the
    # static load's, exactly
    identical = static_rows == incr_rows

    # attribution run: one extra incremental pass with the phase plane AND
    # the r23 pod-timeline plane on (env, not enable(): every runtime.run
    # re-installs the planes from env). The timeline plane spills a
    # tick-granularity segment next to the bench output so a later
    # ``pathway_tpu timeline diff`` can compare runs phase-by-phase.
    import tempfile

    tl_dir = (
        os.path.splitext(os.path.abspath(out_path))[0] + ".timeline"
        if out_path
        else tempfile.mkdtemp(prefix="engine_bench_tl_")
    )
    os.environ["PATHWAY_ENGINE_PHASES"] = "on"
    os.environ["PATHWAY_TIMELINE"] = "on"
    os.environ["PATHWAY_TIMELINE_STEP_MS"] = "100"
    os.environ["PATHWAY_TIMELINE_DIR"] = tl_dir
    try:
        engine_phases.reset()
        phased = run(n, n_times)
        phases = engine_phases.snapshot()
        engine_phases.reset()
    finally:
        os.environ.pop("PATHWAY_ENGINE_PHASES", None)
        os.environ.pop("PATHWAY_TIMELINE", None)
        os.environ.pop("PATHWAY_TIMELINE_STEP_MS", None)
        os.environ.pop("PATHWAY_TIMELINE_DIR", None)
        engine_phases.enable(False)

    static_s, incr_s = best[1], best[n_times]
    pct = round(100.0 * static_s / incr_s, 1)
    # the tick-granularity crossover point (r15): over 5 ticks instead of
    # 20, the run pays 4x fewer rounds of per-tick aggregate corrections
    # (each touched group re-emits retract+insert once per tick it is
    # touched in) and 4x fewer per-tick fixed costs, while the bigger
    # blocks amortize the numpy fixed costs better than one 300k-row
    # monolith sorts — so incremental BEATS the one-shot load, the paper's
    # promise. (The headline pct above stays at the historical n/20 point
    # for BENCH comparability.)
    coarse = min(run(n, 5)["seconds"] for _ in range(3))
    pct_coarse = round(100.0 * static_s / coarse, 1)
    results: dict = {
        "bench": "engine_incremental",
        "n": n,
        "n_times": n_times,
        "reps": reps,
        "engine_static_rows_per_s": round(n / static_s, 1),
        "engine_static_rows_per_s_all": allruns[1],
        "engine_incremental_rows_per_s": round(n / incr_s, 1),
        "engine_incremental_rows_per_s_all": allruns[n_times],
        "engine_incremental_pct_of_static": pct,
        "engine_incremental_pct_of_static_coarse_ticks": pct_coarse,
        "outputs_byte_identical": identical,
        "phase_breakdown_ms": {k: v["ms"] for k, v in phases.items()},
        "phase_breakdown_per_tick_ms": {
            k: round(v["ms"] / n_times, 3) for k, v in phases.items()
        },
        "phase_run_seconds": phased["seconds"],
    }
    try:
        from pathway_tpu.observability.timeline import diff_summary, read_segments

        results["timeline_segment_dir"] = tl_dir
        results["timeline_segment_points"] = len(read_segments(tl_dir))
    except Exception:
        diff_summary = None  # plane unavailable: the gate still fires, unnamed

    # name the phase that moved (ISSUE 20): diff this run's per-tick phase
    # split against the newest committed BENCH file carrying one — the same
    # comparison ``pathway_tpu timeline diff`` makes across spilled segments
    prev_split = _last_committed_metric(
        "phase_breakdown_per_tick_ms", exclude=out_path, raw=True
    )
    worst_phase = None
    if diff_summary is not None and isinstance(
        prev_split[0] if prev_split else None, dict
    ):
        rows = diff_summary(
            [{f"phase_ms:{k}": v for k, v in prev_split[0].items()}],
            [
                {
                    f"phase_ms:{k}": v
                    for k, v in results["phase_breakdown_per_tick_ms"].items()
                }
            ],
            prefixes=("phase_ms:",),
        )
        if rows:
            worst_phase = rows[0]
            results["worst_regressed_phase"] = worst_phase["metric"].split(":", 1)[1]
            results["worst_regressed_phase_pct"] = worst_phase["regression_pct"]
            results["phase_diff_baseline_file"] = prev_split[1]

    # spread-based noise detection (the observability_bench discipline): on a
    # host where same-config reps swing >1.6x, a 5-point pct drop is not a
    # trustworthy regression signal — downgrade the hard gate to a warning
    spread = max(
        max(v) / max(min(v), 1e-9) for v in allruns.values() if v
    )
    noisy = spread > 1.6
    results["rep_spread_max"] = round(spread, 2)
    results["noisy_host"] = noisy

    prev = _last_committed_pct(exclude=out_path)
    gate_ok = True
    if prev is not None:
        prev_pct, prev_file = prev
        results["gate_baseline_pct"] = prev_pct
        results["gate_baseline_file"] = prev_file
        if pct < prev_pct - GATE_DROP_PTS:
            gate_ok = False
            msg = (
                f"engine_incremental_pct_of_static regressed: {pct} vs "
                f"{prev_pct} in {prev_file} (allowed drop {GATE_DROP_PTS} pts)"
            )
            if worst_phase is not None:
                msg += (
                    f"; worst-regressed phase: "
                    f"{worst_phase['metric'].split(':', 1)[1]} "
                    f"({worst_phase['regression_pct']:+.1f}% per-tick ms vs "
                    f"{prev_split[1]})"
                )
            if os.environ.get("BENCH_MODE") == "1" and not noisy:
                results["gate_ok"] = False
                print(json.dumps(results))
                print(f"GATE FAILURE: {msg}", file=sys.stderr)
                sys.exit(1)
            print(f"WARNING: {msg}", file=sys.stderr)
    if not identical:
        results["gate_ok"] = False
        print(json.dumps(results))
        print(
            "GATE FAILURE: incremental output differs from static", file=sys.stderr
        )
        sys.exit(1)
    results["gate_ok"] = gate_ok
    return results


if __name__ == "__main__":
    args = [a for a in sys.argv[1:]]
    out_path = None
    if "--out" in args:
        i = args.index("--out")
        out_path = args[i + 1]
        del args[i : i + 2]
    if args and args[0] == "--small-ticks":
        sizes = tuple(int(a) for a in args[1:]) or (64, 256, 1024)
        res = small_ticks(sizes, out_path=out_path)
        line = json.dumps(res)
        print(line)
        if out_path:
            with open(out_path, "w") as f:
                f.write(line + "\n")
    elif args and args[0] == "--full":
        n = int(args[1]) if len(args) > 1 else 300_000
        res = full(n, out_path=out_path)
        line = json.dumps(res)
        print(line)
        if out_path:
            with open(out_path, "w") as f:
                f.write(line + "\n")
    else:
        n = int(args[0]) if len(args) > 0 else 1_000_000
        n_times = int(args[1]) if len(args) > 1 else 1
        res = run(n, n_times)
        res.pop("rows", None)
        print(json.dumps(res))
