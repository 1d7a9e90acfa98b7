"""Benchmark: embed→index docs/sec on one chip (the north-star loop's ingest side),
plus engine static/incremental rows/s, 1M-row KNN build/query, and RAG query p50.

Runs on a TPU only (``bench_tpu`` refuses any other platform), and a phase
that fails fails the run: there is no ``*_error`` key and no ``null`` metric.
ROADMAP A1 replaces this file with the per-cell benchmark; until then:

- Every latency metric is reported twice: ``*_ms`` = host clock around the
  call, transfers and dispatch included, and ``*_device_ms`` = on-device time
  measured by chaining K data-dependent kernels inside one jit and
  differencing a K-step against a 2K-step chain, so the fetch and the
  dispatch cancel.
- FLOP accounting uses the ACTUAL padded sequence length of the tokenized
  corpus (round 3 hardcoded 128 while the data bucketed to 64 — overstating
  achieved TFLOP/s 2x; docs are now long enough to genuinely fill L=128).
- The baseline is **batched** torch CPU on the same architecture fed
  pre-built token tensors; our timed loop gets the same treatment via the
  C tokenizer kernel running once up front (tokenization speed is reported
  separately as ``tokenize_docs_per_s``). The reference's actual per-row
  dispatch (``xpacks/llm/embedders.py:385-398``) is ``vs_per_row_baseline``.
- Weights are random (bf16): throughput does not depend on weight values.
  Output *quality* parity is covered by ``tests/test_encoder_pretrained.py``
  (real MiniLM/BERT checkpoints reproduce HuggingFace embeddings).
- The headline is the median of 5 timed runs; all runs and their relative
  spread are reported (r3 recorded a 1.5x swing from first-run compile
  leakage; warmup now covers every shape).
- ``bench_exchange`` / ``bench_scaling`` run in CPU-forced subprocesses (the
  parent holds the chip): they count host work and are not device numbers.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
"""

from __future__ import annotations

import json
import os
import statistics
import time

import numpy as np

N_DOCS = 8192  # ~0.5 s per timed run
BATCH = 256  # torch-baseline batch (its CPU sweet spot)
INGEST_BATCH = 1024  # TPU ingest microbatch (r5 sweep)
DOC_WORDS = 120  # tokenizes to ~121 ids -> bucket 128: genuinely fills L=128
N_QUERIES = 64
PER_ROW_BASELINE_ROWS = 24  # per-row torch CPU sample size (extrapolated)
BATCHED_BASELINE_DOCS = 1024
SEQ_LEN = 128  # torch-baseline token length; must match the actual bucket

_PEAK_TFLOPS = {
    # bf16 peak per chip, keyed by ``device_kind`` exactly as JAX reports it;
    # a device that is not listed is an error, not a default
    "TPU v4": 275.0,
    "TPU v5e": 197.0,
    "TPU v5 lite": 197.0,  # what JAX calls a v5e
    "TPU v5p": 459.0,
    "TPU v6e": 918.0,
}


def synth_docs(n: int, words: int = DOC_WORDS) -> list[str]:
    rng = np.random.default_rng(0)
    vocab = [f"word{i}" for i in range(5000)]
    return [" ".join(rng.choice(vocab, size=words)) for _ in range(n)]


def bench_tpu(docs: list[str]) -> tuple[float, dict]:
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py measures a TPU; JAX found platform {dev.platform!r}")
    if dev.device_kind not in _PEAK_TFLOPS:
        raise SystemExit(f"bench.py has no peak FLOP/s for device_kind {dev.device_kind!r}")
    peak = _PEAK_TFLOPS[dev.device_kind]

    from pathway_tpu.ops.encoder import (
        EncoderConfig,
        JaxSentenceEncoder,
        encode,
        encoder_flops_per_doc,
    )
    from pathway_tpu.ops.knn import BruteForceKnnIndex, _search_kernel

    cfg = EncoderConfig(
        vocab_size=32768, d_model=384, n_heads=6, n_layers=6, d_ff=1536, max_len=SEQ_LEN
    )
    enc = JaxSentenceEncoder(cfg, seed=0, param_dtype=jnp.bfloat16)

    extras: dict = {}

    # -- tokenization: once, up front, C kernel; measured on its own ---------
    t0 = time.perf_counter()
    ids_all, _mask = enc.tokenizer(docs)
    tok_s = time.perf_counter() - t0
    L = ids_all.shape[1]
    # the torch baselines run at SEQ_LEN tokens: a silent bucket change would
    # re-create round 3's FLOP/baseline mismatch
    assert L == SEQ_LEN, f"corpus bucketed to L={L}, baselines assume {SEQ_LEN}"
    extras["tokenize_docs_per_s"] = round(len(docs) / tok_s, 0)
    extras["seq_len_actual"] = int(L)
    flops_per_doc = encoder_flops_per_doc(cfg, L)

    def run(index: BruteForceKnnIndex, ids: np.ndarray) -> None:
        # streaming-shaped ingest: per-batch host→device put of int16 ids,
        # jitted encode, device-resident scatter into the index — every step
        # async, one packed fetch at the final search syncs the whole pipeline
        for i in range(0, len(ids), INGEST_BATCH):
            embs = enc.encode_ids_device(jnp.asarray(ids[i : i + INGEST_BATCH]))
            index.add_batch_device(range(i, i + int(embs.shape[0])), embs)
            index._flush()  # per-batch scatter: fixed shape, compiles once
        index.search(embs[:N_QUERIES], k=10)

    # warmup compiles every timed shape (encode, scatter, search)
    run(BruteForceKnnIndex(dimension=cfg.d_model, capacity=8192), ids_all[: 2 * INGEST_BATCH])
    rates = []
    for _ in range(5):
        index = BruteForceKnnIndex(dimension=cfg.d_model, capacity=8192)
        t0 = time.perf_counter()
        run(index, ids_all)
        rates.append(len(docs) / (time.perf_counter() - t0))
    rate = statistics.median(rates)

    tflops = rate * flops_per_doc / 1e12
    extras.update(
        {
            "runs": [round(r, 1) for r in rates],
            "run_spread_pct": round(100 * (max(rates) - min(rates)) / rate, 1),
            "device": dev.device_kind,
            "tflops": round(tflops, 2),
            "mfu_pct": round(100 * tflops / peak, 2),
        }
    )

    # -- device-side compute rate: chained encodes, K vs 2K differencing ----
    # (one fetch amortized over the chain; the K/2K difference cancels the
    # fetch and the dispatch). Batch sweep (r5): bigger batches amortize
    # per-layer overheads and lift MXU occupancy — report the curve and
    # headline the best point.
    from functools import partial as _partial

    @_partial(jax.jit, static_argnames=("length",))
    def enc_chain(params, ids0, length):
        def body(ids, _):
            emb = encode(params, cfg, ids.astype(jnp.int32), ids != 0)
            # data-dependent perturbation serializes the chain (not foldable)
            bump = (jnp.argmax(emb[0]) % 2).astype(ids.dtype)
            return ids ^ bump, emb[0, 0]
        _, outs = jax.lax.scan(body, ids0, None, length=length)
        return outs

    sweep: dict = {}
    best = (None, None)  # (docs_per_s, batch)
    for B in (512, 1024, 2048):
        ids_dev = jnp.asarray(ids_all[:B])
        K = max(4, 8192 // B)
        per_batch = _chain_rate(
            lambda length: np.asarray(enc_chain(enc.params, ids_dev, length)), K
        )
        dev_rate = B / per_batch
        sweep[str(B)] = round(dev_rate, 0)
        if best[0] is None or dev_rate > best[0]:
            best = (dev_rate, B)
    extras["device_docs_per_s_by_batch"] = sweep
    dev_tflops = best[0] * flops_per_doc / 1e12
    extras["device_docs_per_s"] = round(best[0], 0)
    extras["device_best_batch"] = best[1]
    extras["device_tflops"] = round(dev_tflops, 2)
    extras["device_mfu_pct"] = round(100 * dev_tflops / peak, 2)

    # -- RAG query loop (Adaptive RAG hot path minus the external LLM) ------
    from pathway_tpu.ops.reranker import JaxCrossEncoder, score as rerank_score

    ce = JaxCrossEncoder(EncoderConfig(
        vocab_size=32768, d_model=384, n_heads=6, n_layers=4, d_ff=1536, max_len=256
    ), seed=1)
    q = "what is word42 about"
    qids, _ = enc.tokenizer([q])
    index.search(enc.encode_ids_device(jnp.asarray(qids)), k=10)  # warm [1, Lq]
    # warm the rerank shape (10 pairs)
    ce.score_pairs([(q, docs[i][:800]) for i in range(10)])
    lat = []
    lat_rr = []
    for _ in range(30):
        t0 = time.perf_counter()
        emb = enc.encode_ids_device(jnp.asarray(qids))  # 1 async put
        hits = index.search(emb, k=10)[0]               # 1 packed fetch
        _context = "\n".join(docs[int(kk)][:200] for (kk, _s) in hits)
        lat.append((time.perf_counter() - t0) * 1000)
        # full measured loop (BASELINE.json north star): embed→index→RERANK
        scores = ce.score_pairs([(q, docs[int(kk)][:800]) for (kk, _s) in hits])
        _best = hits[int(np.argmax(scores))]
        lat_rr.append((time.perf_counter() - t0) * 1000)
    extras["rag_query_p50_ms"] = round(statistics.median(lat), 2)
    extras["rag_query_rerank_p50_ms"] = round(statistics.median(lat_rr), 2)

    # device-side per-query latency: chained encode+search inside one jit
    index._flush()
    qids_dev = jnp.asarray(qids)

    @_partial(jax.jit, static_argnames=("length",))
    def rag_chain(params, vectors, norms, valid, bits, ids0, length):
        def body(ids, _):
            emb = encode(params, cfg, ids.astype(jnp.int32), ids != 0)
            s, si = _search_kernel(vectors, norms, valid, bits, emb, k=10, metric="cos")
            bump = (si[0, 0] % 2).astype(ids.dtype)
            return ids ^ bump, s[0]
        _, outs = jax.lax.scan(body, ids0, None, length=length)
        return outs

    args = (enc.params, index._vectors, index._norms_sq, index._valid, index._key_bits)
    # a single query step is ~0.1 ms: chain 256 steps so the K/2K difference
    # rises above the host clock's jitter
    per_q = _chain_rate(lambda length: np.asarray(rag_chain(*args, qids_dev, length)), 256)
    extras["rag_query_device_ms"] = round(per_q * 1e3, 3)

    # device-side FULL loop: embed → top-10 search → cross-encoder rerank, all
    # inside one jit (the BASELINE.json metric is the embed+index+RERANK loop,
    # question_answering.py:97-160 + rerankers.py:159 in the reference)
    doc_toks_dev = jnp.asarray(ids_all.astype(np.int32))  # [N_DOCS, L] resident
    ce_cfg = ce.cfg
    ce_params = ce.params
    Lq = int(qids.shape[1])

    @_partial(jax.jit, static_argnames=("length",))
    def rag_rerank_chain(params, vectors, norms, valid, bits, doc_toks, ids0, length):
        def body(ids, _):
            emb = encode(params, cfg, ids.astype(jnp.int32), ids != 0)
            _s, si = _search_kernel(vectors, norms, valid, bits, emb, k=10, metric="cos")
            dtoks = doc_toks[si[0]]                     # [10, L]
            dtoks = dtoks.at[:, 0].set(2)               # doc CLS -> [SEP]
            qrep = jnp.broadcast_to(ids0.astype(jnp.int32), (10, Lq))
            pair = jnp.concatenate([qrep, dtoks], axis=1)
            scores = rerank_score(ce_params, ce_cfg, pair, pair != 0)  # [10]
            bump = (jnp.argmax(scores) % 2).astype(ids.dtype)
            return ids ^ bump, scores[0]
        _, outs = jax.lax.scan(body, ids0, None, length=length)
        return outs

    rr_args = (*args, doc_toks_dev)
    per_rr = _chain_rate(
        lambda length: np.asarray(rag_rerank_chain(*rr_args, qids_dev, length)),
        64,
        reps=9,
    )
    extras["rag_query_rerank_device_ms"] = round(per_rr * 1e3, 3)
    return rate, extras


def _timed(f) -> float:
    t0 = time.perf_counter()
    f()
    return time.perf_counter() - t0


def _chain_rate(run_chain, k: int, reps: int = 5) -> float:
    """Per-step device time of a K-chained jit: median(t_2K) - median(t_K)
    over K extra steps — the fetch and the dispatch overhead cancel. A 2K
    chain that does not take longer than a K chain is a failed measurement
    and raises rather than fabricating a rate."""
    run_chain(k)       # compile K
    run_chain(2 * k)   # compile 2K
    t1 = statistics.median(_timed(lambda: run_chain(k)) for _ in range(reps))
    t2 = statistics.median(_timed(lambda: run_chain(2 * k)) for _ in range(reps))
    if t2 <= t1:
        raise RuntimeError(f"chain timing failed: t_2K={t2:.6f}s <= t_K={t1:.6f}s at K={k}")
    return (t2 - t1) / k


def bench_knn_1m() -> dict:
    """configs[2]: 1M × 384 HBM-resident index — build rate + query p50.

    Build data is generated ON DEVICE (jax.random per chunk) and ingested via
    ``add_batch_device``: this measures the framework's scatter/bookkeeping
    machinery and real HBM writes, exactly like the production path where the
    encoder output feeds the index without a host hop."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.ops.knn import BruteForceKnnIndex, _search_kernel

    n, d, chunk = 1_000_000, 384, 8192
    index = BruteForceKnnIndex(dimension=d, capacity=n)
    key = jax.random.PRNGKey(0)

    def dev_block(i):
        return jax.random.normal(jax.random.fold_in(key, i), (chunk, d), jnp.float32)

    # warmup scatter+search shapes
    index.add_batch_device(range(chunk), dev_block(0))
    index._flush()
    q_host = np.asarray(dev_block(1)[:16])
    index.search(q_host, k=10)
    t0 = time.perf_counter()
    inserted = 0
    for i in range(chunk, n, chunk):
        index.add_batch_device(range(i, i + chunk), dev_block(i))
        index._flush()
        inserted += chunk
    index.search(q_host, k=10)  # sync the build pipeline before stopping the clock
    build_s = time.perf_counter() - t0
    lat = []
    for _ in range(20):
        t0 = time.perf_counter()
        index.search(q_host, k=10)
        lat.append((time.perf_counter() - t0) * 1000)

    # device-side p50: chained searches, K vs 2K differencing
    from functools import partial as _partial

    K = 16
    q_dev = jnp.asarray(q_host)

    @_partial(jax.jit, static_argnames=("length",))
    def chain(vectors, norms, valid, bits, q0, length):
        def body(q, _):
            s, si = _search_kernel(vectors, norms, valid, bits, q, k=10, metric="cos")
            bump = (si[:, :1] % 2).astype(q.dtype) * 1e-6
            return q + bump, s[0, 0]
        _, outs = jax.lax.scan(body, q0, None, length=length)
        return outs

    args = (index._vectors, index._norms_sq, index._valid, index._key_bits)
    per_q = _chain_rate(lambda length: np.asarray(chain(*args, q_dev, length)), K)
    return {
        "knn1m_build_rows_per_s": round(inserted / build_s, 0),
        "knn1m_query16_p50_ms": round(statistics.median(lat), 2),
        "knn1m_query16_device_ms": round(per_q * 1e3, 2),
    }


def bench_engine() -> dict:
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from benchmarks.engine_bench import run as engine_run

    # best-of-2: the first run pays page-cache/allocator warmup
    static = max((engine_run(1_000_000) for _ in range(2)), key=lambda r: r["value"])
    incr = max((engine_run(200_000, 10) for _ in range(2)), key=lambda r: r["value"])
    out = {
        "engine_static_rows_per_s": static["value"],
        "engine_incremental_rows_per_s": incr["value"],
        "engine_incremental_pct_of_static": round(
            100 * incr["value"] / static["value"], 1
        ),
    }
    # VERDICT r3 #3: the jitted-relational-kernel bet, measured
    from benchmarks.jax_kernel_bench import run as jax_kernel_run

    jk = jax_kernel_run(1_000_000)
    out["jax_kernel_rows_per_s"] = jk["jax_kernel_rows_per_s"]
    out["numpy_kernel_rows_per_s"] = jk["numpy_groupby_rows_per_s"]
    out["jax_probe_rows_per_s"] = jk.get("jax_cpu_probe_rows_per_s")
    out["numpy_probe_rows_per_s"] = jk["numpy_probe_rows_per_s"]
    return out


def bench_exchange() -> dict:
    """Host vs device exchange plane (VERDICT r4 #1): a multi-device
    collective, run as a subprocess on the 8-device virtual CPU mesh (this
    process holds the chip) — a count of host work, not a device number."""
    import json as _json
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["PYTHONPATH"] = here + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(here, "benchmarks", "exchange_bench.py")],
        capture_output=True,
        text=True,
        timeout=600,
        env=env,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"exchange_bench failed: {(proc.stderr or proc.stdout)[-2000:]}")
    return _json.loads(proc.stdout.strip().splitlines()[-1])


def bench_scaling() -> dict:
    """1/2/4/8-worker scaling curve, thread + process planes (VERDICT r4 #3).
    Subprocess-driven; see benchmarks/scaling_bench.py for the 1-core caveat."""
    import json as _json
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = here + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(here, "benchmarks", "scaling_bench.py")],
        capture_output=True,
        text=True,
        timeout=900,
        env=env,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"scaling_bench failed: {(proc.stderr or proc.stdout)[-2000:]}")
    data = _json.loads(proc.stdout.strip().splitlines()[-1])
    return {
        "scaling_times_s": data["scaling_times_s"],
        "scaling_efficiency": data["speedup_vs_1w"],
        "scaling_note": data["note"],
    }


def bench_torch_batched_baseline(docs: list[str]) -> float:
    """Honest baseline: batched torch CPU, same architecture, batch=BATCH."""
    import torch

    torch.manual_seed(0)
    blocks, embed = _torch_model()
    rng = np.random.default_rng(0)
    n = BATCHED_BASELINE_DOCS
    batches = [
        torch.tensor(rng.integers(3, 32768, size=(BATCH, SEQ_LEN)), dtype=torch.long)
        for _ in range(n // BATCH)
    ]
    with torch.no_grad():
        blocks(embed(batches[0]))  # warmup
        t0 = time.perf_counter()
        for b in batches:
            z = blocks(embed(b)).mean(dim=1)
            z = z / z.norm(dim=-1, keepdim=True)
        elapsed = time.perf_counter() - t0
    return n / elapsed


def bench_torch_per_row_baseline() -> float:
    """Reference pattern: per-row model.encode on torch CPU, same architecture."""
    import torch

    torch.manual_seed(0)
    blocks, embed = _torch_model()
    rng = np.random.default_rng(0)
    rows = [
        torch.tensor(rng.integers(3, 32768, size=(1, SEQ_LEN)), dtype=torch.long)
        for _ in range(PER_ROW_BASELINE_ROWS)
    ]
    with torch.no_grad():
        blocks(embed(rows[0]))  # warmup
        t0 = time.perf_counter()
        for r in rows:
            z = blocks(embed(r)).mean(dim=1)
            z = z / z.norm(dim=-1, keepdim=True)
        elapsed = time.perf_counter() - t0
    return PER_ROW_BASELINE_ROWS / elapsed


def _torch_model():
    import torch

    class Block(torch.nn.Module):
        def __init__(self, d, h, f):
            super().__init__()
            self.attn = torch.nn.MultiheadAttention(d, h, batch_first=True)
            self.ln1 = torch.nn.LayerNorm(d)
            self.ln2 = torch.nn.LayerNorm(d)
            self.ff = torch.nn.Sequential(
                torch.nn.Linear(d, f), torch.nn.GELU(), torch.nn.Linear(f, d)
            )

        def forward(self, x):
            h = self.ln1(x)
            x = x + self.attn(h, h, h, need_weights=False)[0]
            return x + self.ff(self.ln2(x))

    d, heads, ff, layers, vocab = 384, 6, 1536, 6, 32768
    embed = torch.nn.Embedding(vocab, d)
    blocks = torch.nn.Sequential(*[Block(d, heads, ff) for _ in range(layers)])
    return blocks, embed


def main() -> None:
    docs = synth_docs(N_DOCS)
    tpu_rate, extras = bench_tpu(docs)
    batched_rate = bench_torch_batched_baseline(docs)
    per_row_rate = bench_torch_per_row_baseline()
    out = {
        "metric": "embed+index docs/sec, single chip (MiniLM-class encoder, 128 tok)",
        "value": round(tpu_rate, 2),
        "unit": "docs/s",
        "vs_baseline": round(tpu_rate / batched_rate, 2),
        "baseline": "batched torch CPU, same arch, batch=256",
        "baseline_docs_per_s": round(batched_rate, 1),
        "vs_per_row_baseline": round(tpu_rate / per_row_rate, 2),
    }
    out.update(extras)
    out.update(bench_engine())
    out.update(bench_exchange())
    out.update(bench_scaling())
    out.update(bench_knn_1m())
    # r6 tentpole: cross-tick microbatching of device UDF streams
    from benchmarks.streaming_bench import run as streaming_run

    sb = streaming_run(2048, reps=3)
    out["stream64_docs_per_s_microbatch"] = sb["stream64_docs_per_s_microbatch"]
    out["stream64_docs_per_s_per_tick"] = sb["stream64_docs_per_s_per_tick"]
    out["stream64_device_batch512_docs_per_s"] = sb["device_docs_per_s_batch512"]
    out["stream64_microbatch_pct_of_batch512"] = sb["microbatch_pct_of_batch512"]
    out["stream64_byte_identical"] = sb["byte_identical_outputs"]
    out["stream64_chain_qps_microbatch"] = sb["chain_embed_knn_rerank_qps_microbatch"]
    out["stream64_chain_qps_per_tick"] = sb["chain_embed_knn_rerank_qps_per_tick"]
    print(json.dumps(out))


if __name__ == "__main__":
    main()
