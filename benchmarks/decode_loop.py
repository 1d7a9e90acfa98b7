"""The decode loop and the collector in one run of a benchmark cell, from the
program's own spans (PR 36): what the new readers read, and, in a traced run,
how the trace's idle time splits around them.

    chiprun -- python3 benchmarks/decode_loop.py --workload kimi-k2.answer-rag --seed 7 --seconds 50 --trace 1

Runs ``chipbench.run`` in this process (its result line comes first,
unchanged), then prints one more line, ``{"decode_loop": {...}}``:

- ``ring_records``, ``dropped``, ``window_spans``: what the span ring held,
  and how much of it touches the window;
- ``readers``: the span readers of the cell, computed on the run's window
  (in an untraced run too, when ``PATHWAY_TRACE=on`` kept a ring);
- ``launches``: per ``device/launch`` label, calls, median and total ms;
- ``yields``: per ``generate/yield`` reason, count and steps;
- ``gc``: the collector's passes per generation (count, seconds) and the
  generation-0 sums the ticks carry;
- ``steps``: decoder launches, and the seconds of the host's gaps and of the
  decoder's fetches over the whole window;
- traced only, ``trace``: the part of the window the device trace holds, the
  share of the host gaps' seconds inside its idle gaps (the readers' and the
  clock's check), and its idle time while rows are live split into the host
  gaps, the decoder's fetches and the rest;
- traced only, ``clock``: each decoder launch's executable (the trace's
  ``XLA Modules``) against the same launch's host stamps as the trace itself
  holds them (a traced run's spans are also ``TraceAnnotation``s), so no
  marker is involved: where the executable starts and ends against its
  hand-over and its fetch (a step cannot end after its fetch returned), the
  shifts of the device's times that keep every launch inside its stamps, per
  tenth of the window, the marker's error (ring against annotation), and the
  share of the host gaps in idle time on the trace's own clock, with the gap
  ended at the hand-over's end (the reader's) and at its start. Where the
  session launches a step before it fetches the one before, a
  ``generate/step`` span hands over one step and fetches another, so these
  pairs hold only for a program without that lookahead;
  ``step_after_step_gap_us`` (quartiles of the chip's idle time between two
  consecutive decode steps) and the share of them within 100 us hold for both.

A chip tool, not a benchmark: ``chipbench.run`` refuses to run off a TPU."""

from __future__ import annotations

import bisect
import json
import os
import statistics
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import reduce_trace, run, spanlib, steplib  # noqa: E402

DECODER = ("decoder.step", "decoder.prefill")
READERS = ("step_launch_ms.answer", "step_gap_ms.answer", "yield_gap_share.answer", "idle_in_gc.ingest",
           "idle_in_microbatch_host.ingest", "idle_in_index_add.ingest", "idle_in_tokenize.ingest",
           "idle_unexplained.ingest", "tick_host_ms.query", "fetches_per_query", "idle_in_wait.query")
FETCHES = ("decoder.tokens", "decoder.first_tokens")


def total(intervals) -> float:
    return sum(b - a for a, b in intervals) / 1e9


def live_intervals(ls: list[dict]) -> list[tuple[int, int]]:
    """From the launch that found no row live to the fetch of the one that
    left none."""
    out, start = [], None
    for x in ls:
        start = x["t0"] if start is None else start
        if x["live"] == 0:
            out.append((start, x["fetched"]))
            start = None
    if start is not None and ls:
        out.append((start, ls[-1]["fetched"]))
    return out


def quartiles_us(values: list) -> list | None:
    if len(values) < 4:
        return None
    return [round(q / 1e3, 1) for q in statistics.quantiles(values, n=4)]


def rank(values: list, p: float) -> int:
    v = sorted(values)
    return v[min(len(v) - 1, int(p * len(v)))]


def host_launches(events: list[tuple]) -> list[tuple]:
    """Per decoder launch as the trace's host lines hold it, in order:
    (label, hand-over start, hand-over end, fetch end) on the trace's clock."""
    lines: dict = {}
    for p, line, name, s, d in events:
        if not p.startswith("/device:") and name in ("generate/step", "generate/prefill", "device/launch", "device/fetch"):
            lines.setdefault((p, line), []).append((s, s + d, name))
    out = []
    for evs in lines.values():
        evs.sort()
        kids = {k: [(s, e) for s, e, n in evs if n == k] for k in ("device/launch", "device/fetch")}
        for s, e, n in evs:
            if n.startswith("generate/"):
                got = {}
                for k, v in kids.items():
                    i = bisect.bisect_left(v, (s, s))
                    if i < len(v) and v[i][1] <= e:
                        got[k] = v[i]
                if len(got) == 2:
                    out.append(("decoder." + n.split("/")[1], got["device/launch"][0], got["device/launch"][1],
                                got["device/fetch"][1]))
    return sorted(out, key=lambda x: x[1])


def device_launches(events: list[tuple]) -> list[tuple]:
    """The decoder's executables on the first device: (label, start, end)."""
    names, planes = reduce_trace.kernel_names(), reduce_trace.device_planes(events)[:1]
    out = [(reduce_trace.label_of(n, names), s, s + d) for p, line, n, s, d in events
           if p in planes and line == reduce_trace.MODULES]
    return sorted((x for x in out if x[0] in DECODER), key=lambda x: x[1])


def pair(host: list, dev: list, slack: int = 3_000_000) -> tuple[int, list]:
    """Host launches against executables in order, at the index offset that
    makes the most pairs of one label whose executable lies within
    ``slack`` of its stamps (launches before or after the profiler's
    session leave one side longer)."""
    best = (-1, 0, [])
    for k in range(-4, 5):
        pairs = [(h, dev[i + k]) for i, h in enumerate(host) if 0 <= i + k < len(dev)]
        ok = sum(h[0] == d[0] and d[1] >= h[1] - slack and d[2] <= h[3] + slack for h, d in pairs)
        best = max(best, (ok, k, pairs), key=lambda b: b[0])
    return best[1], best[2]


def in_idle(gaps: list, idle: list) -> float | None:
    tot = total(gaps)
    return 100.0 * total(spanlib.intersect(spanlib.merge(gaps), idle)) / tot if tot else None


def clock(events: list[tuple], rep: dict, trace: dict) -> dict:
    """The ``clock`` entry of the module's docstring, all on the trace's clock."""
    host, dev = host_launches(events), device_launches(events)
    out: dict = {"host_launches": len(host), "device_launches": len(dev)}
    # a step right after a step is one of a row's answer: the chip's idle time between the two
    after = [b[1] - a[2] for a, b in zip(dev, dev[1:]) if a[0] == b[0] == "decoder.step"]
    out["step_after_step_gap_us"] = quartiles_us(after)
    out["step_after_step_within_100us_pct"] = 100.0 * sum(g <= 100_000 for g in after) / len(after) if after else None
    if not host or not dev:
        return out
    k, pairs = pair(host, dev)
    out["pair_offset"], out["pairs"] = k, len(pairs)
    out["start_minus_handover_start_us"] = quartiles_us([d[1] - h[1] for h, d in pairs])
    out["start_minus_handover_end_us"] = quartiles_us([d[1] - h[2] for h, d in pairs])
    out["fetch_end_minus_end_us"] = quartiles_us([h[3] - d[2] for h, d in pairs])
    out["ends_after_fetch_pct"] = 100.0 * sum(d[2] > h[3] for h, d in pairs) / len(pairs)
    out["starts_before_handover_pct"] = 100.0 * sum(d[1] < h[1] for h, d in pairs) / len(pairs)
    # a shift s of the device's times is consistent with a launch when its
    # executable then starts after the hand-over began and ends before the
    # fetch returned: s >= start of hand-over - start, s <= fetch end - end
    tenths, shifts = [], []
    n = len(pairs)
    for g in range(10):
        part = pairs[g * n // 10 : (g + 1) * n // 10]
        if not part:
            continue
        lo = rank([h[1] - d[1] for h, d in part], 0.99)
        hi = rank([h[3] - d[2] for h, d in part], 0.01)
        s = min(max(0, lo), hi) if lo <= hi else (lo + hi) // 2
        shifts.append((part[0][0][1], s))
        tenths.append([round(lo / 1e3, 1), round(hi / 1e3, 1)])
    out["feasible_shift_us_by_tenth"] = tenths
    # the ring's stamps against the annotation of the same hand-over: the marker's error
    off = trace["offset_ns"]
    handed = [h[2] for h in host]
    live, err = {}, []
    for x in steplib.launches(rep):
        t = x["launched"] + off
        i = bisect.bisect_left(handed, t)
        j = min((c for c in (i - 1, i) if 0 <= c < len(host)), key=lambda c: abs(handed[c] - t), default=None)
        if j is not None and abs(handed[j] - t) < 2_000_000:
            live[j] = x["live"]
            err.append(handed[j] - t)
    out["annotation_minus_ring_us"] = quartiles_us(err)  # hand-over ends; ~0 when the marker is right
    out["live_matched"] = len(live)
    w0, w1 = rep["window"]
    win = [(w0 + off, w1 + off)]
    idle = spanlib.merge(trace["gaps"])
    ends = [(a[3], max(a[3], b[2])) for i, (a, b) in enumerate(zip(host, host[1:])) if live.get(i, 0) > 0]
    starts_ = [(a[3], max(a[3], b[1])) for i, (a, b) in enumerate(zip(host, host[1:])) if live.get(i, 0) > 0]
    ends, starts_ = spanlib.intersect(sorted(ends), win), spanlib.intersect(sorted(starts_), win)
    out["host_gap_s"], out["host_gap_to_handover_start_s"] = total(ends), total(starts_)
    out["host_gap_in_idle_pct"] = in_idle(ends, idle)
    out["host_gap_to_handover_start_in_idle_pct"] = in_idle(starts_, idle)

    def moved(gaps):  # each gap against the device's times shifted by its tenth's s
        t0s = [t for t, _s in shifts]
        return sorted((a - shifts[max(0, bisect.bisect_right(t0s, a) - 1)][1],
                       b - shifts[max(0, bisect.bisect_right(t0s, a) - 1)][1]) for a, b in gaps)

    out["host_gap_in_idle_shifted_pct"] = in_idle(moved(ends), idle)
    out["host_gap_to_handover_start_in_idle_shifted_pct"] = in_idle(moved(starts_), idle)
    return out


def report(ctx, events: list | None = None) -> dict:
    got = spanlib.ring()
    out: dict = {"ring_records": len(got[0]) if got else 0, "dropped": got[1] if got else None}
    out["readers"] = {}
    for name in READERS:
        try:
            out["readers"][name] = run.load_metric(name).read(ctx)
        except Exception as e:  # noqa: BLE001 - a reader of another cell's window
            out["readers"][name] = f"{type(e).__name__}: {e}"
    rep = spanlib.report(ctx)
    if rep is None:
        return out
    out["window_spans"] = len(rep["spans"])
    by_label: dict = {}
    for s in spanlib.named(rep, "device/launch"):
        by_label.setdefault(spanlib.attr(s, "label"), []).append((s["t1"] - s["t0"]) / 1e6)
    out["launches"] = {k: {"calls": len(v), "p50_ms": spanlib.median(v), "total_ms": sum(v)}
                       for k, v in sorted(by_label.items())}
    yields: dict = {}
    for s in spanlib.named(rep, "generate/yield"):
        y = yields.setdefault(spanlib.attr(s, "reason"), {"count": 0, "steps": 0})
        y["count"] += 1
        y["steps"] += spanlib.attr(s, "steps")
    out["yields"] = yields
    gcs: dict = {}
    for s in spanlib.named(rep, "gc/collect"):
        g = gcs.setdefault(str(spanlib.attr(s, "generation")), {"passes": 0, "seconds": 0.0})
        g["passes"] += 1
        g["seconds"] += (s["t1"] - s["t0"]) / 1e9
    names = {s["id"]: s["name"] for s in rep["spans"] if s["id"] is not None}
    under: dict = {}  # a pass's seconds by the span it interrupted
    for s in spanlib.named(rep, "gc/collect"):
        key = names.get(s["parent"], "")
        under[key] = under.get(key, 0.0) + (s["t1"] - s["t0"]) / 1e9
    gcs["under"] = dict(sorted(under.items(), key=lambda kv: -kv[1])[:12])
    ticks = spanlib.named(rep, "tick")
    gcs["0_in_ticks"] = {"passes": sum(spanlib.attr(t, "gc0_passes", 0) for t in ticks),
                         "seconds": sum(spanlib.attr(t, "gc0_ns", 0) for t in ticks) / 1e9}
    out["gc"] = gcs
    ls = steplib.launches(rep)
    gaps = spanlib.merge(steplib.host_gaps(rep))
    fetches = spanlib.merge([(s["t0"], s["t1"]) for s in spanlib.named(rep, "device/fetch")
                             if spanlib.attr(s, "label") in FETCHES])
    live = spanlib.merge(live_intervals(ls))
    out["steps"] = {"launches": len(ls), "host_gaps": len(steplib.host_gaps(rep)), "host_gap_s": total(gaps),
                    "fetch_s": total(fetches), "live_s": total(live)}
    trace = getattr(ctx, "trace", None)
    if trace is None or trace.get("offset_ns") is None:
        return out
    off = trace["offset_ns"]
    idle = spanlib.merge([(a - off, b - off) for a, b in trace["gaps"]])
    w0, w1 = rep["window"]
    held = w1
    if idle and idle[-1][1] >= w1 - 1_000_000 and idle[-1][1] - idle[-1][0] > 1_000_000_000:
        held = idle[-1][0]  # the profiler's buffer filled: the rest reads as one gap
    part = [(w0, held)]
    idle_h, gaps_h = spanlib.intersect(idle, part), spanlib.intersect(gaps, part)
    idle_live = spanlib.intersect(idle_h, spanlib.intersect(live, part))
    in_gaps, in_fetch = total(spanlib.intersect(idle_live, gaps)), total(spanlib.intersect(idle_live, fetches))
    out["trace"] = {
        "held_s": (held - w0) / 1e9, "window_s": (w1 - w0) / 1e9, "idle_s": total(idle_h),
        "host_gap_s": total(gaps_h),
        "host_gap_in_idle_pct": 100.0 * total(spanlib.intersect(gaps_h, idle_h)) / total(gaps_h) if gaps_h else None,
        "idle_live_s": total(idle_live), "idle_live_in_host_gaps_s": in_gaps, "idle_live_in_fetch_s": in_fetch,
        "idle_live_rest_s": total(idle_live) - in_gaps - in_fetch,
        "idle_under_gc_s": total(spanlib.intersect(idle_h, spanlib.merge(
            [(s["t0"], s["t1"]) for s in spanlib.named(rep, "gc/collect")]))),
    }
    if events:
        out["clock"] = clock(events, rep, trace)
    return out


def main(argv: list[str]) -> int:
    seen: dict = {}
    load, load_trace = run.load_metric, reduce_trace.load

    def keep(trace_dir: str) -> list:
        seen["events"] = load_trace(trace_dir)
        return seen["events"]

    def spy(name: str):
        reader = load(name)

        def read(ctx):
            seen["ctx"] = ctx
            return reader.read(ctx)

        return types.SimpleNamespace(read=read)

    run.load_metric, reduce_trace.load = spy, keep
    try:
        rc = run.main(argv)
    finally:
        run.load_metric, reduce_trace.load = load, load_trace
    if rc == 0 and "ctx" in seen:
        print(json.dumps({"decode_loop": report(seen["ctx"], seen.get("events"))}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
