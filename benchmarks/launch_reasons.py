"""Why the microbatcher's launches left their buffers in one traced run of a
benchmark cell: the ``pathway.reason`` of every ``microbatch/launch`` span
the run recorded (``full``, ``deadline``, ``idle``, ``drain``), with the rows
and the median wait of each reason.

    chiprun -- python3 benchmarks/launch_reasons.py --workload minilm-l6.retrieve-short --seed 7 --seconds 50

Runs ``chipbench.run`` with ``--trace 1`` in this process (its result line
comes first, unchanged), then reads the span ring the run left behind and
prints one more line, ``{"launch_reasons": {...}}``. The ring holds what the
profiler session covered: the window and its edges. A chip tool, not a
benchmark: ``chipbench.run`` refuses to run off a TPU."""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import run, spanlib  # noqa: E402


def reasons() -> dict:
    got = spanlib.ring()
    by_reason: dict[str, list] = {}
    for s in spanlib.spans_of(got[0] if got is not None else []):
        if s["name"] == "microbatch/launch":
            by_reason.setdefault(spanlib.attr(s, "reason"), []).append(s)
    return {
        reason: {
            "launches": len(spans),
            "rows": sum(spanlib.attr(s, "rows") for s in spans),
            "wait_p50_ms": spanlib.median([spanlib.attr(s, "oldest_wait_ns") / 1e6 for s in spans]),
        }
        for reason, spans in sorted(by_reason.items())
    }


def main(argv: list[str]) -> int:
    rc = run.main([*argv, "--trace", "1"])
    if rc == 0:
        print(json.dumps({"launch_reasons": reasons()}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
