"""The yardstick's arithmetic: operations and bytes a call needs, from its
shapes, and the chip's peaks. Copied from ``ops/encoder.py``
``encoder_flops_per_doc`` (matmul FLOPs of one forward pass) so that a later
PR cannot move it; ``tests/test_flops.py`` holds the two equal."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json"), encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in chipbench/peaks.json")
    return table[device_kind]


def encoder_flops(config: dict, tokens: int) -> float:
    """Matmul FLOPs of one BERT forward pass over one text of ``tokens`` real
    tokens ([CLS] included, padding not): per layer the qkv and output
    projections, scores and context, and both feed-forward matmuls."""
    d, f, L = config["hidden_size"], config["intermediate_size"], tokens
    per_layer = 2 * L * d * (3 * d) + 2 * L * d * d + 2 * 2 * L * L * d + 2 * L * d * f * 2
    return float(config["num_hidden_layers"] * per_layer)


def text_tokens(words: int, max_len: int) -> int:
    return min(words + 1, max_len)


def pair_tokens(query_words: int, doc_words: int, max_len: int) -> int:
    budget = max_len - 2
    q = min(query_words, budget // 2)
    return 2 + q + min(doc_words, budget - q)


def scan_bytes(rows: int, dim: int, row_bytes: int = 4) -> float:
    """Bytes one exact scan has to read: every live row's vector, squared
    norm (f32), validity (1 B) and key bits (u32)."""
    return float(rows) * (dim * row_bytes + 4 + 1 + 4)


def scan_flops(queries: int, rows: int, dim: int) -> float:
    return 2.0 * queries * rows * dim


def roofline_pct(flops: float, nbytes: float, seconds: float, peak: dict) -> tuple[float, str]:
    """Share of the roofline: the least time the chip could take (the larger
    of FLOPs over peak FLOP/s and bytes over peak bytes/s) over the time
    taken; and which of the two bounds it."""
    t_flops = flops / peak["bf16_flops"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return 100.0 * max(t_flops, t_bytes) / seconds, ("compute" if t_flops >= t_bytes else "memory")
