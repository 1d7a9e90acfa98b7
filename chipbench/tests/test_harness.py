"""The harness end to end on the CPU at a tiny size, the look for a chip
skipped: result keys, data-driven discovery, the control, and the timed path
broken underneath."""

import json
import os
import shutil

import pytest

from chipbench import check, control, run
from chipbench.tests.util import tiny_cell

RETRIEVE, INGEST, RERANK = "minilm-l6.retrieve-short", "minilm-l6.ingest-backfill", "rerank-l6.retrieve-rerank-k32"
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]


@pytest.mark.parametrize("name", [RETRIEVE, INGEST, RERANK])
def test_a_sound_run_is_correct_and_prints_the_contracts_keys(name):
    cell = tiny_cell(name)
    res = run.run_cell(name, 2**31 + 5, 2.0, False, cell=cell)
    assert list(res) == CONTRACT_KEYS  # 'compared' comes last
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(set(m) == {"value", "unit"} and m["value"] > 0 for m in res["metrics"].values())
    assert set(res["compared"]) == set(cell.cell["limits"])
    json.dumps(res)


def test_a_missing_chip_fails_and_prints_no_result(capsys):
    rc = run.main(["--workload", RETRIEVE, "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert rc == 2 and capsys.readouterr().out == ""


def test_a_new_cell_metric_and_traffic_are_found_as_new_files(tmp_path, monkeypatch):
    """A later PR adds a cell, a traffic mix and a per-layer metric by adding
    files and entries; no file that is there is edited."""
    here = tmp_path / "chipbench"
    for d in ("configs", "traffic", "workloads", "metrics"):
        shutil.copytree(os.path.join(run.HERE, d), here / d)
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    new = "minilm-l6.retrieve-slow"
    bench["workloads"].append({"name": new, "config": "live-rag-minilm-l6", "traffic": "retrieve-slow", "chips": 1, "why": "t"})
    bench["end_to_end"].append({"name": "answered_share", "unit": "%", "better": "higher", "bound": 0.01,
                                "source": "host_clock", "workloads": [new]})
    for m in bench["end_to_end"]:
        if m["name"].startswith("query_"):
            m["workloads"].append(new)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    slow = run.load_json(run.HERE, "traffic", "retrieve-short.json")
    slow["rate"] = 7.0
    (here / "traffic" / "retrieve-slow.json").write_text(json.dumps(slow))
    shutil.copy(os.path.join(run.HERE, "workloads", RETRIEVE + ".json"), here / "workloads" / (new + ".json"))
    (here / "metrics" / "answered_share.py").write_text(
        "def read(ctx):\n    w = ctx.window\n    return 100.0 * (w['attempted'] - w['failed']) / w['attempted']\n"
    )
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    monkeypatch.setattr(run, "HERE", str(here))
    cell = run.load_cell(new)
    assert cell.traffic["rate"] == 7.0 and "answered_share" in {m["name"] for m in cell.end_to_end}
    assert run.load_metric("answered_share").read(
        type("C", (), {"window": {"attempted": 8, "failed": 2}})()) == 75.0


@pytest.mark.parametrize("name", [RETRIEVE, INGEST, RERANK])
def test_the_control_comes_out_not_correct(name):
    """The reference in the program's place at the precision below the one
    the stand-in states (bfloat16 for float32) fails a limit; at the
    reference's own precision it passes."""
    cell = tiny_cell(name)
    assert check.verdict(control.control_numbers(cell, 3, "f32", 24, 1), cell.cell["limits"])[0]
    ok, compared = check.verdict(control.control_numbers(cell, 3, "bf16", 24, 1), cell.cell["limits"])
    assert not ok, compared


def _break_embedder(monkeypatch):
    from pathway_tpu.ops import encoder

    sound = encoder.JaxSentenceEncoder.encode_texts

    def altered(self, texts):  # every embedding turned a little: an answer altered where it is produced
        import numpy as np

        v = sound(self, texts)
        v = v + 0.2 * np.roll(v, 1, axis=-1)
        return v / np.linalg.norm(v, axis=-1, keepdims=True)

    monkeypatch.setattr(encoder.JaxSentenceEncoder, "encode_texts", altered)


def _skip_archive(monkeypatch):
    from chipbench import archive

    monkeypatch.setattr(archive.Archive, "load_into", lambda self, index: None)


def _swap_hits(monkeypatch):
    from pathway_tpu.ops import knn

    sound = knn._decode_hits

    def altered(scores, ids, slot_to_key, k):  # keys of the first two hits exchanged, scores left
        out = sound(scores, ids, slot_to_key, k)
        if k <= 16:  # the set-up's k=1 look for a document stays sound
            return out
        return [[(h[1][0], h[0][1]), (h[0][0], h[1][1])] + h[2:] if len(h) > 1 else h for h in out]

    monkeypatch.setattr(knn, "_decode_hits", altered)


def _shift_scores(monkeypatch):
    from pathway_tpu.ops import reranker

    sound = reranker.JaxCrossEncoder.score_pairs
    monkeypatch.setattr(reranker.JaxCrossEncoder, "score_pairs", lambda self, pairs: sound(self, pairs)[::-1].copy())


@pytest.mark.parametrize("name,fault", [
    (RETRIEVE, _break_embedder), (RETRIEVE, _skip_archive), (RETRIEVE, _swap_hits),
    (INGEST, _break_embedder), (RERANK, _shift_scores),
])
def test_a_broken_timed_path_comes_out_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    res = run.run_cell(name, 11, 2.0, False, cell=tiny_cell(name))
    assert res["correct"] is False, res["compared"]


@pytest.mark.parametrize("retry_s", [0.2, None])
def test_a_refused_query_is_late_or_failed_and_never_incorrect(retry_s, capfd):
    """Arrivals that overrun the in-flight budget (a host that stood still
    does this to any rate) are refused with 429. Sent again they are answered,
    counted with their wait and compared like any other; where the traffic
    does not send them again they are failed, and no answer to compare."""
    cell = tiny_cell(RETRIEVE, rate=80.0)
    cell.config["serve_max_inflight"] = 2
    cell.traffic["retry_refused_s"] = retry_s
    res = run.run_cell(RETRIEVE, 13, 2.0, False, cell=cell)
    err = capfd.readouterr().err
    assert res["correct"] is True, res["compared"]
    if retry_s:
        assert res["failed"] == 0 and "sent again 0 times" not in err
    else:
        assert res["failed"] > 0
