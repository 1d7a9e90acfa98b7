"""Every configuration whose ``source`` names a row of the catalog beside the
``model-configs`` guide holds each key of that row's ``config`` — numbers,
strings and nested groups alike — equal unless ``BENCHMARK.json``'s
``reduced`` lists it, and none null, at the file's top level: that is where
the driver's check reads them (it refused a file that kept them in a group).
``run.build_models`` reads the embedder at the top level of the file it finds
by the configuration's name, so a language model's ``file`` is one of its own
and the deployment's file names it (``llm``)."""

import json
import os

import pytest

from chipbench import run

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")


def catalog() -> dict:
    if not os.path.exists(CATALOG):
        pytest.skip(f"the catalog {CATALOG} is not on this machine")
    with open(CATALOG, encoding="utf-8") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return {r["source_url"]: r for r in rows}


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_a_catalogued_configuration_holds_every_key_of_its_row(entry):
    row = catalog().get(entry["source"])
    if row is None:
        pytest.skip(f"{entry['source']} is not a row of the catalog")
    held = run.load_json(run.ROOT, entry["file"])
    for key, want in row["config"].items():
        assert key in held and held[key] is not None, f"{entry['file']} gives {key} as null or not at all"
        if key not in entry["reduced"]:
            assert held[key] == want, f"{entry['file']} gives {key} as {held[key]!r}; its source gives {want!r}"
    assert not any(v is None for v in held.values())
    changed = [k for k in entry["reduced"] if k in row["config"]]
    assert all(held[k] != row["config"][k] for k in changed), "reduced lists a key that is unchanged"
    widths = [k for k in changed if k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size" or k == "num_experts_per_tok"]
    assert not widths, f"reduced names a width: {widths}"


def test_the_file_the_benchmark_names_is_the_one_the_cell_runs():
    """What the driver checks is what the pipeline, the comparison and the
    metrics read: the deployment names the entry's ``file``, and its own top
    level still builds the embedder."""
    from chipbench.flops_decoder import llm_config

    entry = next(e for e in BENCH["configs"] if e["name"] == "adaptive-rag-kimi-k2")
    held = run.load_json(run.HERE, "configs", entry["name"] + ".json")
    assert held["llm"] == entry["file"] != "chipbench/configs/" + entry["name"] + ".json"
    assert llm_config(held) == run.load_json(run.ROOT, entry["file"])
    assert (held["hidden_size"], held["num_hidden_layers"], held["vocab_size"]) == (384, 6, 30522)
    assert llm_config(held)["archive_rows"] == held["archive_rows"]


def test_the_kimi_file_states_its_share_and_the_published_counts():
    held = run.load_json(run.HERE, "configs", "adaptive-rag-kimi-k2.json")
    llm = run.load_json(run.ROOT, held["llm"])
    assert (llm["num_hidden_layers"], llm["n_routed_experts"], llm["vocab_size"]) == (6, 12, 20480)
    assert (llm["num_hidden_layers_published"], llm["n_routed_experts_published"], llm["vocab_size_published"],
            llm["first_expert"]) == (61, 384, 163840, 0)
    assert llm["n_routed_experts"] >= 8 and llm["vocab_size"] * 8 >= llm["vocab_size_published"]
    assert llm["num_hidden_layers"] - llm["first_k_dense_replace"] >= 4
    assert "32 chips" in llm["deployment"] and held["archive_rows"] == 0 and held["pipeline"] == "answer"
