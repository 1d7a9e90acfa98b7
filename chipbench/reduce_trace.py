"""From a profiler trace to numbers. ``load`` flattens an ``.xplane.pb`` into
events ``(plane, line, name, start_ns, dur_ns)``; everything else works on
that list, so it is checked against a small recorded list in the tests.

Device planes are the planes named ``/device:TPU:<n>``. On one, the line
``XLA Modules`` holds one event per executable launched (named
``jit_<function>(<fingerprint>)``) and ``XLA Ops`` one per operation.
"""

from __future__ import annotations

import glob
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
MODULES, OPS = "XLA Modules", "XLA Ops"


def kernel_names() -> dict[str, list[str]]:
    """label -> module-name prefixes, from ``kernels.json`` and any
    ``kernels.d/*.json`` a later PR added."""
    out: dict[str, list[str]] = {}
    for path in [os.path.join(HERE, "kernels.json")] + sorted(glob.glob(os.path.join(HERE, "kernels.d", "*.json"))):
        with open(path, encoding="utf-8") as f:
            for label, names in json.load(f).items():
                if not label.startswith("_"):
                    out.setdefault(label, []).extend(names)
    return out


def load(trace_dir: str) -> list[tuple]:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events = []
    for plane in ProfileData.from_file(max(paths, key=os.path.getmtime)).planes:
        for line in plane.lines:
            for ev in line.events:
                events.append((plane.name, line.name, ev.name, int(ev.start_ns), int(ev.duration_ns)))
    return events


def device_planes(events: list[tuple]) -> list[str]:
    return sorted({p for p, *_ in events if re.match(r"/device:TPU:\d+$", p)})


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def label_of(name: str, names: dict[str, list[str]]) -> str:
    bare = name.split("(")[0]
    for label, prefixes in names.items():
        if bare in prefixes:
            return label
    return bare


def reduce(events: list[tuple], window: tuple[int, int] | None = None, names: dict | None = None) -> dict:
    """``window`` is (start_ns, end_ns) on the trace's clock; None takes the
    span of the device events. Returns busy and window seconds (averaged over
    the device planes), per-label launches and seconds, the top device
    operations and the idle gaps of the first device."""
    names = kernel_names() if names is None else names
    planes = device_planes(events)
    if not planes:
        raise ValueError("the trace holds no /device:TPU plane: nothing ran on a chip")
    dev = [e for e in events if e[0] in planes]
    if window is None:
        window = (min(e[3] for e in dev), max(e[3] + e[4] for e in dev))
    w0, w1 = window

    def clip(evs):
        return [(max(s, w0), min(s + d, w1)) for _p, _l, _n, s, d in evs if s + d > w0 and s < w1]

    busy, gaps0 = [], []
    for p in planes:
        ops = [e for e in dev if e[0] == p and e[1] == OPS] or [e for e in dev if e[0] == p and e[1] == MODULES]
        u = _union(clip(ops))
        busy.append(sum(e - s for s, e in u))
        if p == planes[0]:
            edges = [w0] + [t for se in u for t in se] + [w1]
            gaps0 = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    launches: dict[str, int] = {}
    seconds: dict[str, float] = {}
    for _p, line, name, s, d in dev:
        if line == MODULES and s + d > w0 and s < w1:
            label = label_of(name, names)
            launches[label] = launches.get(label, 0) + 1
            seconds[label] = seconds.get(label, 0.0) + (min(s + d, w1) - max(s, w0)) / 1e9
    n = len(planes)
    op_s: dict[str, float] = {}
    for _p, line, name, s, d in dev:
        if line == OPS and s + d > w0 and s < w1:
            short = name.split(" = ")[0]  # "%fusion.12 = (f32[...]) fusion(...)" -> "%fusion.12"
            op_s[short] = op_s.get(short, 0.0) + d / 1e9
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "launches": {k: v / n for k, v in launches.items()},
        "seconds": {k: v / n for k, v in seconds.items()},
        "ops": sorted(([k, v / n] for k, v in op_s.items()), key=lambda kv: -kv[1]),
        "gaps": gaps0,
    }


def head(events: list[tuple], window: tuple[int, int], seconds: float = 0.25) -> list[list]:
    """The device events of the window's first ``seconds`` and the host
    markers, names cut short: small enough to keep, enough to check a
    reduction by hand."""
    w0 = window[0]
    w1 = w0 + int(seconds * 1e9)
    return [
        [p, l, n.split(" = ")[0][:80], s, d]
        for p, l, n, s, d in events
        if (p.startswith("/device:") and s + d > w0 and s < w1) or n.startswith("chipbench.")
    ]


def find_marker(events: list[tuple], name: str) -> int | None:
    """Start (trace clock, ns) of the host TraceAnnotation ``name``."""
    hits = [s for p, _l, n, s, _d in events if n == name and not p.startswith("/device:")]
    return min(hits) if hits else None


def name_gaps(gaps: list[tuple[int, int]], in_flight: list[tuple[int, int]], busy_name: str, idle_name: str) -> list:
    """The ten entries of ``idle_gaps``: the idle seconds by what the host
    was doing (a gap overlapping a request or backlog interval the load
    generator recorded is the host's; any other is a wait for arrivals), and
    then the longest single gaps."""
    flights = _union(in_flight)
    total = {busy_name: 0.0, idle_name: 0.0}
    singles = []
    for s, e in gaps:
        cover = sum(max(0, min(e, fe) - max(s, fs)) for fs, fe in flights)
        total[busy_name] += cover / 1e9
        total[idle_name] += (e - s - cover) / 1e9
        singles.append([busy_name if cover * 2 >= e - s else idle_name, (e - s) / 1e9])
    singles.sort(key=lambda kv: -kv[1])
    out = [[k, v] for k, v in total.items() if v > 0]
    return out + [[f"longest:{k}", v] for k, v in singles[: 10 - len(out)]]
