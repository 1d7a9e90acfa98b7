"""Every configuration of ``BENCHMARK.json`` whose ``source`` is a row of the
catalog beside the ``model-configs`` guide holds each key of that row's
``config`` at the top level of its ``file``, equal to the source's value
unless ``reduced`` lists it, and null where the source is null: that is what
the driver's check reads before any run (it refused PR 28 for one key that
differed). Skipped where the catalog is not on the machine."""

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCH = json.load(_f)
#: what ``reduced`` may never name: a width
WIDTH_ENDINGS = ("_dim", "_rank", "_size")


def catalog_row(source: str) -> dict:
    if not os.path.exists(CATALOG):
        pytest.skip(f"the catalog {CATALOG} is not on this machine")
    with open(CATALOG, encoding="utf-8") as f:
        rows = {r["source_url"]: r for r in map(json.loads, filter(str.strip, f))}
    if source not in rows:
        pytest.skip(f"{source} is not a row of the catalog")
    return rows[source]


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_a_catalogued_configuration_holds_every_key_of_its_row(entry):
    row = catalog_row(entry["source"])
    with open(os.path.join(ROOT, entry["file"]), encoding="utf-8") as f:
        held = json.load(f)
    for key, want in row["config"].items():
        assert key in held, f"{entry['file']} lacks {key}"
        if key in entry["reduced"]:
            assert held[key] != want, f"reduced lists {key}, which is unchanged"
        else:
            assert held[key] == want and type(held[key]) is type(want), (
                f"{entry['file']} gives {key} as {held[key]!r}; its source gives {want!r}")
    widths = [k for k in entry["reduced"]
              if k.endswith(WIDTH_ENDINGS) and k != "vocab_size" or k == "num_experts_per_tok"]
    assert not widths, f"reduced names a width: {widths}"
    used = {w["config"] for w in BENCH["workloads"]}
    assert entry["name"] in used, "a configuration no cell runs is never measured"


def test_the_hybrid_configuration_cuts_nothing_of_the_model():
    entry = next(e for e in BENCH["configs"] if e["name"] == "adaptive-rag-granite-4h-micro")
    with open(os.path.join(ROOT, entry["file"]), encoding="utf-8") as f:
        held = json.load(f)
    assert entry["reduced"] == ["archive_rows"] and held["archive_rows"] == 0
    assert held["rope_scaling"] is None and len(held["layer_types"]) == held["num_hidden_layers"] == 40
    assert held["layer_types"].count("attention") == 4 and held["layer_types"][5::10] == ["attention"] * 4
    assert held["state_dtype"] == "float32" and held["reference"] == "chipbench.reference_granite_4h"
    assert held["arithmetic"] == "chipbench.flops_hybrid"
    assert {"deployment", "guarantees", "assumed"} <= set(held)
    with open(os.path.join(ROOT, "chipbench", "configs", entry["name"] + ".json"), encoding="utf-8") as f:
        deployment = json.load(f)
    assert deployment["llm"] == entry["file"] and deployment["max_tokens"] == 96
