"""A cell at a size a CPU test can hold: the real cell's traffic, comparison
and pipeline over a two-layer, 64-wide stand-in configuration, float32."""

import copy

from chipbench import run

TINY_RERANK = {
    "architectures": ["BertForSequenceClassification"], "hidden_act": "gelu", "hidden_size": 64,
    "intermediate_size": 128, "layer_norm_eps": 1e-12, "max_position_embeddings": 512,
    "num_attention_heads": 4, "num_hidden_layers": 2, "type_vocab_size": 2, "vocab_size": 2048,
}


#: the rerank cell's files are kept and tested; its entry waits for a later PR (PERF.md, Open questions)
ENTRIES = {
    "rerank-l6.retrieve-rerank-k32": {"config": "live-rag-rerank-l6", "traffic": "retrieve-rerank-k32", "chips": 1},
}


def tiny_cell(name: str, rate: float = 20.0):
    cell = run.load_cell(name, ENTRIES.get(name))
    tiny = run.load_json(run.HERE, "tests", "data", "tiny.json")
    real = cell.config
    cell.config = {**tiny, "pipeline": real["pipeline"], "archive": real["archive"]}
    if "reranker" in real:
        cell.config.update(reranker=copy.deepcopy(TINY_RERANK), rerank_candidates=real["rerank_candidates"],
                           rerank_top=real["rerank_top"])
    # the stand-in states float32, so its readings are rounding (4e-7) and its
    # limits are its own: the real cells' limits belong to bf16 at 384 wide
    cell.cell["limits"] = {n: (0 if n == "malformed" else 2e-5) for n in cell.cell["limits"]}
    cell.cell["tie_margin"] = 2e-5
    cell.cell["warm"] = {}
    cell.cell["probe"] = 16
    if "rate" in cell.traffic:
        cell.traffic.update(rate=rate, sample=32, warm_seconds=0)
    return cell
