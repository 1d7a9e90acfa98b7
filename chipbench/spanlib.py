"""From the program's own spans to numbers. The program records spans into
a ring while a profiler session is on (``pathway_tpu.observability``), on
``CLOCK_MONOTONIC`` — the clock of the load generator's window and, through
the harness's one marker, of the device trace. The readers run in the
program's process after the run, so they read the ring directly; a program
that has no ring (an older commit) gives them nothing, and they return
nothing.

A span here is a dict ``name, id, parent, t0, t1, attrs, trace, thread``
(nanoseconds; ``trace`` is None for the run's own spans and a request's trace
id for its ``serve/*`` spans; ``thread`` is set on a span that was really
open on that thread between its stamps). Everything below ``ring`` works on
plain lists, so it is checked against a small recorded list in the tests.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "out")
PREFIX = "pathway."  # the program's attribute namespace


def ring() -> tuple[list, int] | None:
    """(raw records, dropped) of the last recording, or None."""
    from pathway_tpu import observability as obs

    last = getattr(obs, "last_recording", None)
    buf = last() if last is not None else None
    return (buf.records(), buf.dropped) if buf is not None else None


def spans_of(records: list) -> list[dict]:
    return [
        {"name": n, "id": i, "parent": p, "t0": t0, "t1": t1, "attrs": a or {}, "trace": tr, "thread": th}
        for n, i, p, t0, t1, a, tr, th in records
    ]


def attr(span: dict, key: str, default=None):
    return span["attrs"].get(PREFIX + key, default)


def window_spans(ctx) -> list[dict] | None:
    """The last run's spans that touch the measured window; None when there
    is no ring, it is empty, or it dropped a record (never a number from part
    of a window)."""
    got = ring()
    if got is None or not got[0]:
        return None
    if got[1]:
        print(f"chipbench: spans: the ring dropped {got[1]} records: the span readers return nothing",
              file=sys.stderr, flush=True)
        return None
    w0 = ctx.window["start_ns"]
    w1 = w0 + int(ctx.window["end_s"] * 1e9)
    ctx._ring_records = len(got[0])  # what the ring had to hold: the window and the session's edges
    spans = spans_of(got[0])
    # a request is kept whole: its first boundaries may lie before the window
    whole = {s["trace"] for s in spans if s["name"] == "serve/request" and s["t1"] > w0 and s["t0"] < w1}
    return [s for s in spans if (s["trace"] in whole if s["trace"] is not None else s["t1"] > w0 and s["t0"] < w1)]


def self_time(spans: list[dict]) -> list[int]:
    """Per span, its duration minus what its children cover: the children are
    the spans that name it as parent and were open on a thread."""
    kids: dict = {}
    for s in spans:
        if s["thread"] is not None and s["parent"] is not None:
            kids.setdefault(s["parent"], []).append(s)
    out = []
    for s in spans:
        inside = [(max(k["t0"], s["t0"]), min(k["t1"], s["t1"])) for k in kids.get(s["id"], ()) if s["id"] is not None]
        out.append(s["t1"] - s["t0"] - sum(b - a for a, b in merge([(a, b) for a, b in inside if b > a])))
    return out


def engine_spans(spans: list[dict]) -> list[dict]:
    """The run's own spans that were open on the thread the ticks ran on."""
    ticks = [s["thread"] for s in spans if s["name"] == "tick" and s["trace"] is None]
    if not ticks:
        return []
    engine = max(set(ticks), key=ticks.count)
    return [s for s in spans if s["thread"] == engine and s["trace"] is None]


def innermost(spans: list[dict]) -> list[tuple[int, int, str]]:
    """One thread's nested spans as disjoint segments ``(t0, t1, name)`` of
    the innermost span open at each instant: its self time, laid out in
    time."""
    segs: list[tuple[int, int, str]] = []
    stack: list[tuple[int, str]] = []  # (end, name) of the open spans
    cursor = 0

    def close_until(t: int) -> None:
        nonlocal cursor
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cursor:
                segs.append((cursor, end, name))
                cursor = end

    for s in sorted(spans, key=lambda s: (s["t0"], -s["t1"])):
        close_until(s["t0"])
        if stack and s["t0"] > cursor:
            segs.append((cursor, s["t0"], stack[-1][1]))
        cursor = max(cursor, s["t0"])
        stack.append((s["t1"], s["name"]))
    close_until(1 << 62)
    return segs


def gap_cover(spans: list[dict], gaps: list[tuple[int, int]]) -> dict[str, float]:
    """The device's idle gaps (on the spans' clock) split by the span whose
    self time each instant falls in, in seconds; the key ``""`` holds the
    seconds under no span at all."""
    segs = innermost(engine_spans(spans))
    out: dict[str, float] = {"": 0.0}
    i = 0
    for g0, g1 in sorted(gaps):
        while i < len(segs) and segs[i][1] <= g0:
            i += 1
        covered, j = 0, i
        while j < len(segs) and segs[j][0] < g1:
            s0, s1, name = segs[j]
            part = min(s1, g1) - max(s0, g0)
            if part > 0:
                out[name] = out.get(name, 0.0) + part / 1e9
                covered += part
            j += 1
        out[""] += (g1 - g0 - covered) / 1e9
    return out


def merge(intervals: list) -> list[tuple[int, int]]:
    """The union of some intervals, as sorted disjoint intervals."""
    out: list[list[int]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def intersect(a: list, b: list) -> list[tuple[int, int]]:
    """Two sorted lists of disjoint intervals, intersected."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def requests_of(spans: list[dict]) -> list[dict]:
    """Per answered request the boundary times (ns): arrival, admitted (its
    row pushed to the engine), first_tick (the tick that drained it began),
    respond (its answer resolved) and done."""
    by_trace: dict = {}
    for s in spans:
        if s["trace"] is not None:
            by_trace.setdefault(s["trace"], {})[s["name"]] = s
    out = []
    for group in by_trace.values():
        root = group.get("serve/request")
        if root is None:
            continue
        admitted = group["serve/admission"]["t1"] if "serve/admission" in group else root["t0"]
        out.append({
            "status": attr(root, "status"), "arrival": root["t0"], "admitted": admitted,
            "first_tick": group["serve/coalesce"]["t1"] if "serve/coalesce" in group else admitted,
            "respond": group["serve/respond"]["t0"] if "serve/respond" in group else root["t1"],
            "done": root["t1"],
        })
    return sorted(out, key=lambda r: r["arrival"])


def median(values: list) -> float | None:
    vals = sorted(values)
    if not vals:
        return None
    mid = len(vals) // 2
    return float(vals[mid]) if len(vals) % 2 else (vals[mid - 1] + vals[mid]) / 2.0


def report(ctx) -> dict | None:
    """What the readers share, computed once a run and kept on ``ctx``: the
    window's spans, the per-name table (count, total, self and idle-gap
    seconds), the idle split and the requests. Writes ``out/last_spans.json``
    and says the ten largest rows on stderr."""
    if hasattr(ctx, "_span_report"):
        return ctx._span_report
    ctx._span_report = None
    spans = window_spans(ctx)
    if spans is None:
        return None
    w0 = ctx.window["start_ns"]
    w1 = w0 + int(ctx.window["end_s"] * 1e9)
    idle = None
    trace = getattr(ctx, "trace", None)
    if trace is not None and trace.get("offset_ns") is not None:
        off = trace["offset_ns"]
        idle = gap_cover(spans, [(g0 - off, g1 - off) for g0, g1 in trace["gaps"]])
    names: dict[str, dict] = {}
    for s, own in zip(spans, self_time(spans)):
        if s["trace"] is not None or s["thread"] is None:
            continue  # a request's spans are reported apart; a span from stamps was open on no thread
        row = names.setdefault(s["name"], {"count": 0, "total_s": 0.0, "self_s": 0.0, "idle_gap_s": 0.0})
        row["count"] += 1
        row["total_s"] += (min(s["t1"], w1) - max(s["t0"], w0)) / 1e9
        row["self_s"] += own / 1e9
    for name, secs in (idle or {}).items():
        if name:
            names[name]["idle_gap_s"] = secs
    reqs = requests_of(spans)
    rep = {"spans": spans, "names": names, "idle": idle, "requests": reqs, "window": (w0, w1)}
    if idle is not None and reqs:
        # the idle time while the server held a request, and its part under no span
        held = merge([(r["arrival"], r["done"]) for r in reqs])
        off = trace["offset_ns"]
        in_flight = gap_cover(spans, intersect(sorted((g0 - off, g1 - off) for g0, g1 in trace["gaps"]), held))
        rep["idle_in_flight_s"] = sum(in_flight.values())
        rep["idle_in_flight_under_no_span_s"] = in_flight[""]
    ctx._span_report = rep
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "last_spans.json"), "w", encoding="utf-8") as f:
        json.dump({
            "window_ns": [w0, w1], "spans": len(spans), "ring_records": ctx._ring_records, "dropped": 0,
            "names": names, "idle_s": idle,
            "idle_in_flight_s": rep.get("idle_in_flight_s"),
            "idle_in_flight_under_no_span_s": rep.get("idle_in_flight_under_no_span_s"),
            "requests_ms": [
                {k: (v if k == "status" else (v - w0) / 1e6) for k, v in r.items()} for r in reqs
            ],
        }, f)
    top = sorted(names.items(), key=lambda kv: -kv[1]["self_s"])[:10]
    print(
        f"chipbench: spans: {len(spans)} in the window, {len(reqs)} requests; largest by self time "
        + "; ".join(f"{n} x{r['count']} self {r['self_s']:.3f} s idle {r['idle_gap_s']:.3f} s" for n, r in top)
        + (f"; idle under no span {idle['']:.3f} s of {sum(idle.values()):.3f} s" if idle else ""),
        file=sys.stderr, flush=True,
    )
    return rep


def named(rep: dict, name: str) -> list[dict]:
    """The run's own spans of that name (a kept request repeats its engine
    stages under its own trace id)."""
    return [s for s in rep["spans"] if s["name"] == name and s["trace"] is None]


def idle_share(ctx, pick) -> float | None:
    """Percent of the device's idle time under the spans ``pick(name)``
    accepts (``""`` is no span at all)."""
    rep = report(ctx)
    if rep is None or not rep["idle"]:
        return None
    total = sum(rep["idle"].values())
    return 100.0 * sum(v for k, v in rep["idle"].items() if pick(k)) / total if total else None
