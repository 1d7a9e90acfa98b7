"""A rehearsal on the CPU: each cell end to end at a tiny archive and a cut
model depth, to find wrong paths, arguments and control flow before a chip
call. It prints counts only; a time, a rate or a share taken here is never
written under the name of a device metric."""

from __future__ import annotations

import json
import sys

from chipbench import run

CUT = {
    "config.archive_rows": 8192, "config.archive_block_rows": 4096, "config.reserved_space": 16384,
    "config.live_documents": 512, "config.num_hidden_layers": 1, "cell.warm": {}, "cell.probe": 16,
    "traffic.rate": 4.0, "traffic.sample": 8, "traffic.warm_seconds": 0,
}


def main() -> int:
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    for w in bench["workloads"]:
        cell = run.load_cell(w["name"])
        if "reranker" in cell.config:
            cell.config["reranker"]["num_hidden_layers"] = 1
        cut = {k: v for k, v in CUT.items() if not k.startswith("traffic.") or k.split(".")[1] in cell.traffic}
        res = run.run_cell(w["name"], 2**31 + 1, 2.0, False, scale=cut, cell=cell)
        print(json.dumps({
            "workload": w["name"], "rehearsal_on": res["device"]["platform"], "correct": res["correct"],
            "attempted": res["attempted"], "failed": res["failed"],
            "metrics_reported": sorted(res["metrics"]), "compared": sorted(res["compared"]),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
