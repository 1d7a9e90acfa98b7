"""The decode loop from the program's spans (PR 36). A decoder launch is a
``generate/step`` or ``generate/prefill`` span on the engine thread; its
``device/launch`` child is the hand-over to the executable, its
``device/fetch`` child the host blocked until the tokens came back, and its
``pathway.live`` the rows the session held after it. A ring from a program
without those stamps has no such launch, and every reader here returns
nothing."""

from __future__ import annotations

from chipbench import spanlib

LAUNCHES = ("generate/step", "generate/prefill")


def launches(rep: dict) -> list[dict]:
    """The window's decoder launches on the engine thread, in order: start,
    the end of its ``device/launch`` and of its ``device/fetch``, and the
    rows left live."""
    spans = spanlib.engine_spans(rep["spans"])
    kids: dict = {}
    for s in spans:
        if s["name"] in ("device/launch", "device/fetch"):
            kids.setdefault(s["parent"], {})[s["name"]] = s
    out = []
    for s in sorted(spans, key=lambda s: s["t0"]):
        k = kids.get(s["id"], {}) if s["id"] is not None else {}
        live = spanlib.attr(s, "live")
        if s["name"] in LAUNCHES and live is not None and "device/launch" in k and "device/fetch" in k:
            out.append({"name": s["name"], "t0": s["t0"], "launched": k["device/launch"]["t1"],
                        "fetched": k["device/fetch"]["t1"], "live": live})
    return out


def host_gaps(rep: dict) -> list[tuple[int, int]]:
    """Per pair of consecutive decoder launches whose first left rows live:
    from the first one's fetch to the end of the next one's hand-over (empty
    where the next was handed over first) — the host's time between steps.
    When the fetch returns the last executable has ended; whether the next
    one starts inside its hand-over only the trace says (PERF.md §7)."""
    ls = launches(rep)
    return [(a["fetched"], max(a["fetched"], b["launched"])) for a, b in zip(ls, ls[1:]) if a["live"] > 0]
