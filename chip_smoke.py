"""chip_smoke.py — does the system still start on the chip?

``python chip_smoke.py`` drives the Adaptive-RAG serving path once, through the
entry points a user calls, at the full width of the default models
(``SentenceTransformerEmbedder()`` d=384, 6 heads, 6 layers, d_ff 1536, vocab
32,768; ``CrossEncoderReranker()`` the same at 4 layers), with weights made
from a seed, and checks what comes out:

- ``SentenceTransformerEmbedder`` → ``DocumentStore(BruteForceKnnFactory)`` →
  ``DocumentStoreServer`` ``/v1/retrieve``, and a second REST route
  ``rest_connector`` → ``query_as_of_now`` → ``CrossEncoderReranker`` →
  ``pw.run()``; every query is a document's own text, so top-1 must be it;
- each labelled kernel of the path was launched, the encoder executable at the
  (512, 128) ingest shape holds the Mosaic custom call, and embeddings agree
  with a float32 XLA forward computed on the same device;
- both native kernels build from the committed C sources and load;
- one relational leg (join + groupby above the ``auto`` thresholds) equals its
  numpy-only output, and its JAX kernels sit on the XLA CPU device;
- with four or more devices: ``dryrun_multichip(4)`` and the same server under
  ``pw.run(n_workers=4)`` answering as one worker does.

It measures nothing: counts, shapes and compile seconds only. One process uses
the chip. There is no CPU mode — off a TPU it exits 2 before building
anything. The last line of stdout is the verdict, one JSON object with
exactly the keys ``ok`` and ``device`` (platform, kind, count as JAX reports
them); the full report — every phase's counts, shapes and compile seconds — is
the ``chip_smoke: report`` line before it and ``chiprun_out/chip_smoke.json``.
A failed phase prints its traceback, ``"ok": false``, and exits 1.
"""

from __future__ import annotations

import json
import os
import socket
import sys
import threading
import time
import traceback
import urllib.request

import numpy as np

import pathway_tpu as pw
from pathway_tpu.internals.parse_graph import G

SEED = 0
#: corpus stages, in arrival order: (documents, words each). 8,192 documents of
#: 120 words tokenize to 121 ids -> L=128 and fill sixteen 512-row launches
#: (the Pallas gate's envelope); 300 words -> L=512 compiles the XLA attention
#: path; 10 words -> L=16 is the short-query shape
STAGES = ((8192, 120), (256, 300), (16, 10))
K = 3
#: queried documents per stage, per route
N_RETRIEVE = (16, 4, 4)
N_RERANK = (8, 2, 2)

#: bf16 carries 8 significand bits: 2**-8 on components of a unit vector
TOL_VS_F32 = 2.0 ** -8
#: the Pallas kernel against XLA's attention at the same bf16 width: the two
#: round different intermediates, so they differ by a fraction of that — a
#: quarter is allowed (BASELINE.md §encoder-mfu states 2e-4 for the pair)
TOL_VS_XLA_BF16 = 2.0 ** -10
#: reranker logits are a 384-term dot of such a vector with an O(d**-0.5) head
TOL_RERANK = 2.0 ** -6

LABELS = ("encoder.encode_ids", "knn.scatter", "knn.search", "reranker.score")
OUT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "chiprun_out")


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


# ------------------------------------------------------------------- corpus


def corpus(stages=STAGES, seed: int = SEED) -> list[list[str]]:
    """One list of documents per stage, from a seed: random words of a
    5,000-word vocabulary, no punctuation (one token per word)."""
    rng = np.random.default_rng(seed)
    vocab = [f"word{i}" for i in range(5000)]
    return [
        [" ".join(rng.choice(vocab, size=words)) for _ in range(n)]
        for n, words in stages
    ]


class StagedDocs(pw.io.python.ConnectorSubject):
    """Live document source: emits one stage of the corpus each time the
    client releases it. Stages arrive in separate ticks, so the microbatcher's
    512-row launches are cut from documents of one length class (a static
    table is hash-ordered: every launch would pad to the longest class)."""

    def __init__(self, stages: list[list[str]]):
        super().__init__()
        self.stages = stages
        self.release = [threading.Event() for _ in stages]
        self.stop = threading.Event()

    def run(self) -> None:
        for docs, released in zip(self.stages, self.release):
            while not released.wait(0.05):
                if self.stop.is_set():
                    return
            self.next_batch([{"data": d} for d in docs])

    def on_stop(self) -> None:
        self.stop.set()


# ------------------------------------------------------------------- serving


def _free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _post(port: int, route: str, payload: dict):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{route}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    return json.loads(urllib.request.urlopen(req, timeout=300).read())


def picks(stages: list[list[str]], per_stage) -> list[str]:
    return [d for docs, n in zip(stages, per_stage) for d in docs[:n]]


def serve(
    stages: list[list[str]],
    embedder,
    reranker,
    *,
    n_workers: int | None = None,
    n_retrieve=N_RETRIEVE,
    n_rerank=N_RERANK,
    timeout_s: float = 900.0,
) -> dict:
    """Build the serving graph, run it, feed the corpus stage by stage —
    each stage is released once ``/v1/statistics`` counts the one before and
    ``/v1/retrieve`` finds its first and last document, so every launch is cut
    from one stage — post each picked document's own text to both routes,
    stop the run. Returns ``{"retrieve": {query: reply}, "rerank": {query:
    reply}}``."""
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.servers import DocumentStoreServer

    G.clear()
    subject = StagedDocs(stages)
    docs = pw.io.python.read(subject, schema=pw.schema_from_types(data=str))
    store = DocumentStore(
        docs,
        retriever_factory=BruteForceKnnFactory(
            embedder=embedder, reserved_space=sum(len(s) for s in stages)
        ),
    )
    store_port, rerank_port = _free_port(), _free_port()
    DocumentStoreServer("127.0.0.1", store_port, store)

    # the rerank route of benchmarks/serving_bench.py: embed -> KNN -> rerank
    queries, respond = pw.io.http.rest_connector(
        host="127.0.0.1", port=rerank_port, schema=pw.schema_from_types(query=str)
    )
    picked = store.index.query_as_of_now(queries.query, number_of_matches=K).select(
        q=pw.left.query,
        top=pw.apply(lambda ts: ts[0] if ts else "", pw.right.text),
    )
    # rerank as a top-level column so the batched UDF rides the microbatcher
    scored = picked.select(picked.top, score=reranker(picked.top, picked.q))
    respond(
        scored.select(
            result=pw.apply(
                lambda t, s: {"top": t, "score": float(s)}, scored.top, scored.score
            )
        )
    )

    out: dict = {}
    failure: list[BaseException] = []
    deadline = time.monotonic() + timeout_s

    def wait_until(what: str, ready) -> None:
        while True:
            if subject.stop.is_set():
                raise RuntimeError(f"the run stopped before {what}")
            if time.monotonic() > deadline:
                raise TimeoutError(f"timed out before {what}")
            try:
                if ready():
                    return
            except OSError:
                pass  # the server is not up yet
            time.sleep(0.1)

    def indexed(doc: str) -> bool:
        reply = _post(store_port, "/v1/retrieve", {"query": doc, "k": K})
        return bool(reply) and reply[0]["text"] == doc

    def client() -> None:
        try:
            seen = 0
            for docs_, released in zip(stages, subject.release):
                released.set()
                seen += len(docs_)
                wait_until(
                    f"/v1/statistics counted {seen} documents",
                    lambda: _post(store_port, "/v1/statistics", {}).get("file_count") == seen,
                )
                # counted is parsed; the embeddings may still sit in the
                # microbatcher, where the next stage would join their launch
                wait_until(
                    "the stage was indexed",
                    lambda: indexed(docs_[0]) and indexed(docs_[-1]),
                )
            out["retrieve"] = {
                q: _post(store_port, "/v1/retrieve", {"query": q, "k": K})
                for q in picks(stages, n_retrieve)
            }
            out["rerank"] = {
                q: _post(rerank_port, "/", {"query": q}) for q in picks(stages, n_rerank)
            }
        except BaseException as e:  # noqa: BLE001 — re-raised by the caller below
            failure.append(e)
        finally:
            rt = pw.internals.run.current_runtime()
            if rt is not None:
                rt.request_stop()

    th = threading.Thread(target=client, daemon=True)
    th.start()
    try:
        pw.run(monitoring_level="none", n_workers=n_workers)
    finally:
        subject.stop.set()
    th.join(timeout=60)
    if failure:
        raise failure[0]
    if th.is_alive() or "rerank" not in out:
        raise RuntimeError("the client did not finish")
    return out


def check_answers(answers: dict) -> dict:
    """Every query was a document's own text: top-1 must be that document at
    cosine 1, and every rerank score finite."""
    for q, reply in answers["retrieve"].items():
        if not reply or reply[0]["text"] != q:
            raise AssertionError(f"/v1/retrieve: top-1 is not the query document {q[:40]!r}")
        dists = [r["dist"] for r in reply]
        if not np.isfinite(dists).all() or abs(dists[0] + 1.0) > 1e-2:
            raise AssertionError(f"/v1/retrieve: distances {dists} for {q[:40]!r}")
    for q, reply in answers["rerank"].items():
        if reply["top"] != q:
            raise AssertionError(f"rerank route: top is not the query document {q[:40]!r}")
        if not np.isfinite(reply["score"]):
            raise AssertionError(f"rerank route: score {reply['score']} for {q[:40]!r}")
    return {"retrieve": len(answers["retrieve"]), "rerank": len(answers["rerank"])}


def same_answers(a: dict, b: dict) -> dict:
    """Two runs of the same queries: the same top-1 document at the same
    distance and rerank score, to the width of the arithmetic. Ranks past the
    first sit in a dense cluster of near-equal cosines and are compared as
    information only."""
    topk_identical = True
    for q, ra in a["retrieve"].items():
        rb = b["retrieve"][q]
        if ra[0]["text"] != rb[0]["text"] or abs(ra[0]["dist"] - rb[0]["dist"]) > TOL_VS_F32:
            raise AssertionError(f"/v1/retrieve answers differ for {q[:40]!r}")
        topk_identical &= [r["text"] for r in ra] == [r["text"] for r in rb]
    for q, ra in a["rerank"].items():
        rb = b["rerank"][q]
        if ra["top"] != rb["top"] or abs(ra["score"] - rb["score"]) > TOL_RERANK:
            raise AssertionError(f"rerank answers differ for {q[:40]!r}: {ra} vs {rb}")
    return {"top1_equal": True, "topk_identical": topk_identical}


# -------------------------------------------------------------------- checks


class CompileLog:
    """Per kernel label: compile requests that went through the persistent
    cache, how many it answered, how many it had to write (JAX's own
    monitoring events, attributed to the ``traced_jit`` label dispatching on
    the thread)."""

    EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "writes",
    }

    def __init__(self) -> None:
        from jax import monitoring

        self.by_label: dict[str, dict[str, int]] = {}
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        kind = self.EVENTS.get(event)
        if kind is None:
            return
        from pathway_tpu.observability import device

        label = device.current_label() or "(unlabelled)"
        row = self.by_label.setdefault(label, {"requests": 0, "hits": 0, "writes": 0})
        row[kind] += 1

    def summary(self) -> dict:
        rows = dict(sorted(self.by_label.items()))
        labelled = [r for name, r in rows.items() if name in LABELS]
        return {
            "by_label": rows,
            "labelled_requests": sum(r["requests"] for r in labelled),
            "labelled_backend_compiles": sum(r["requests"] - r["hits"] for r in labelled),
        }


def check_native() -> dict:
    from pathway_tpu import native
    from pathway_tpu.internals import keys
    from pathway_tpu.ops import encoder

    built = {name: os.path.basename(native.load(name).__file__) for name in ("pwhash", "pwtok")}
    if keys._pwhash_native is None or encoder._pwtok_native is None:
        raise AssertionError("a native kernel built here but the package runs its Python fallback")
    return built


def check_launched() -> dict:
    from pathway_tpu.observability import device

    view = device.status_summary()["callables"]
    missing = [name for name in LABELS if not view.get(name, {}).get("calls")]
    if missing:
        raise AssertionError(f"kernels never launched: {missing}")
    return {
        name: {k: view[name][k] for k in ("calls", "shapes", "compiles", "compile_s")}
        for name in view
        if "." in name
    }


def check_mosaic(embedder) -> dict:
    """The ingest launch really was (512, 128), and the executable for that
    shape holds the Mosaic custom call: the kernel compiled, nothing stood in
    for it."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.ops import encoder as E

    enc = embedder._encoder
    shapes = sorted({key[2][0] for key in E.encode_ids_jit._seen})
    if (512, 128) not in shapes:
        raise AssertionError(f"no (512, 128) ingest launch; encoder shapes: {shapes}")
    if not any(shape[1] == 512 for shape in shapes):
        raise AssertionError(f"no L=512 launch (XLA attention path); encoder shapes: {shapes}")
    t0 = time.perf_counter()
    text = (
        E._encode_ids_jit.lower(
            enc.params, enc.cfg, jax.ShapeDtypeStruct((512, 128), jnp.int16)
        )
        .compile()
        .as_text()
    )
    calls = text.count("tpu_custom_call")
    if calls < enc.cfg.n_layers:
        raise AssertionError(
            f"{calls} Mosaic custom calls in the (512, 128) encoder executable, "
            f"expected one per layer ({enc.cfg.n_layers})"
        )
    return {
        "encoder_shapes": [list(s) for s in shapes],
        "mosaic_custom_calls_512x128": calls,
        "lower_compile_s": round(time.perf_counter() - t0, 2),
    }


def check_parity(stages: list[list[str]], embedder, reranker) -> dict:
    """The bf16 path (Pallas attention where the gate takes it) against a
    float32 forward with XLA attention and full-precision matmuls, on the same
    device: a full (512, 128) ingest launch (its first 64 rows compared), the
    (8, 16) and (1, 16) query shapes, and (8, 256) reranker pairs."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.ops import encoder as E
    from pathway_tpu.ops import reranker as R

    enc = embedder._encoder
    f32 = enc.cfg._replace(dtype=jnp.float32, pallas_attention=False)
    xla = enc.cfg._replace(pallas_attention=False)
    out: dict = {}

    def worst(a, b) -> float:
        return float(np.max(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))))

    cases = {
        "512x128": (stages[0][:512], 64),
        "8x16": (stages[2][:8], 8),
        "1x16": (stages[2][:1], 1),
    }
    for name, (texts, rows) in cases.items():
        ids, _mask = enc.tokenizer(texts)
        if "x".join(map(str, ids.shape)) != name:
            raise AssertionError(f"parity case {name} tokenized to {ids.shape}")
        got = np.asarray(E.encode_ids_jit(enc.params, enc.cfg, ids))[:rows]
        head = ids[:rows]
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(E._encode_ids_jit(enc.params, f32, head))
        same_width = np.asarray(E._encode_ids_jit(enc.params, xla, head))
        row = {
            "vs_f32": worst(got, ref),
            "vs_xla_bf16": worst(got, same_width),
            "xla_bf16_vs_f32": worst(same_width, ref),
        }
        out[name] = {k: float(f"{v:.3g}") for k, v in row.items()}
        if not np.isfinite(got).all() or row["vs_f32"] > TOL_VS_F32:
            raise AssertionError(f"encoder {name}: {row} exceeds {TOL_VS_F32} against float32")
        if row["vs_xla_bf16"] > TOL_VS_XLA_BF16:
            raise AssertionError(
                f"encoder {name}: {row} exceeds {TOL_VS_XLA_BF16} against XLA attention in bf16"
            )

    ce = reranker._model
    pairs = [(d, d) for d in stages[0][:8]]
    got = ce.score_pairs(pairs)
    ce_f32 = ce.cfg._replace(dtype=jnp.float32, pallas_attention=False)
    toks = [ce.tokenizer._tok(d) for d, _ in pairs]
    ids = np.zeros((8, 256), np.int32)
    for i, t in enumerate(toks):
        seq = [1] + t + [2] + t
        ids[i, : len(seq)] = seq
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(R._score_jit(ce.params, ce_f32, ids, ids != 0))
    out["rerank_8x256"] = {"vs_f32": float(f"{worst(got, ref):.3g}")}
    if not np.isfinite(got).all() or worst(got, ref) > TOL_RERANK:
        raise AssertionError(f"reranker (8, 256): {out['rerank_8x256']} exceeds {TOL_RERANK}")
    return out


def relational_leg(ticks: int = 4, rows_per_tick: int = 81_920, n_right: int = 196_608) -> dict:
    """filter -> map -> filter -> join -> groupby, with the left side streamed
    over several ticks so every tick sits above the ``auto`` thresholds (fused
    chain: 65,536-row blocks; join probe: 131,072 state rows and 32,768
    probes): the output with the JAX host kernels equals the output with
    numpy only, and the kernels sit on the XLA CPU device."""
    from pathway_tpu.debug import _capture
    from pathway_tpu.engine import jax_kernels
    from pathway_tpu.observability import device

    host = jax_kernels.host_device()
    if host.platform != "cpu":
        raise AssertionError(f"relational kernels would run on {host}")

    def launches() -> dict:
        # join_probe's wrapper is module-global and counts calls; a fused
        # chain's dies with its graph, so it is seen by the compiles JAX's
        # monitoring attributed to its label (process-cumulative)
        st = device.stats()
        with st.lock:
            seen = {k: v[0] for k, v in st.compiles.items() if k.startswith("engine.")}
        view = device.status_summary()["callables"]
        seen.update({k: v["calls"] for k, v in view.items() if k == "engine.join_probe"})
        return seen

    def run() -> dict:
        G.clear()
        rng = np.random.default_rng(SEED)
        n_left = ticks * rows_per_tick
        left = pw.debug.table_from_rows(
            pw.schema_from_types(k=int, v=int),
            [
                (k, v, 2 * (i // rows_per_tick), 1)
                for i, (k, v) in enumerate(
                    zip(
                        rng.integers(0, n_right, n_left).tolist(),
                        rng.integers(0, 100, n_left).tolist(),
                    )
                )
            ],
            is_stream=True,
        )
        right = pw.debug.table_from_rows(
            pw.schema_from_types(k=int, w=int),
            list(zip(range(n_right), rng.integers(0, 100, n_right).tolist())),
        )
        f = left.filter(left.v > 4)
        f = f.select(f.k, v=f.v * 3 + 1)
        f = f.filter(f.v != 100)
        j = f.join(right, f.k == right.k).select(k=f.k, v=f.v, w=right.w)
        g = j.groupby(j.k).reduce(j.k, s=pw.reducers.sum(j.v * j.w), c=pw.reducers.count())
        rows = dict(_capture(g).rows)
        G.clear()
        return rows

    before = launches()
    with_jax = run()
    after = launches()
    launched = {k: after[k] - before.get(k, 0) for k in after if after[k] > before.get(k, 0)}
    if "engine.join_probe" not in launched or not any(
        k.startswith("engine.fused_chain/") for k in launched
    ):
        raise AssertionError(f"relational leg stayed under the auto thresholds: {launched}")
    saved = {k: os.environ.get(k) for k in ("PATHWAY_ENGINE_JAX", "PATHWAY_FUSE_JAX")}
    os.environ.update(PATHWAY_ENGINE_JAX="0", PATHWAY_FUSE_JAX="off")
    try:
        numpy_only = run()
    finally:
        for k, v in saved.items():
            if v is None:
                del os.environ[k]
            else:
                os.environ[k] = v
    if launches() != after:
        raise AssertionError("the numpy-only run launched a JAX kernel")
    if with_jax != numpy_only:
        raise AssertionError("relational output differs between the JAX host kernels and numpy")
    return {"groups": len(with_jax), "host_device": str(host), "launches": launched}


def memory_by_device() -> dict:
    import jax

    out = {}
    for d in jax.devices():
        ms = d.memory_stats() or {}
        out[str(d.id)] = {
            k: ms.get(k) for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
        }
    return out


def multichip_leg(stages, embedder, reranker, one_worker: dict) -> dict:
    """Four chips: the sharding dry run (tp+dp train step, sharded KNN under
    ``shard_map``, the ``all_to_all`` exchange, the forced device plane), then
    the same server under ``pw.run(n_workers=4)``."""
    import __graft_entry__

    t0 = time.perf_counter()
    __graft_entry__.dryrun_multichip(4)
    dryrun_s = round(time.perf_counter() - t0, 1)
    say(f"dryrun_multichip(4) passed in {dryrun_s}s")
    before = memory_by_device()
    answers = serve(stages, embedder, reranker, n_workers=4)
    check_answers(answers)
    return {
        "dryrun_multichip_4": "passed",
        "dryrun_s": dryrun_s,
        "four_workers": same_answers(one_worker, answers),
        "memory_before_four_workers": before,
        "memory_after_four_workers": memory_by_device(),
    }


# ---------------------------------------------------------------------- main


def main() -> int:
    import jax

    from pathway_tpu.internals.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    say(
        f"platform={dev.platform} device_kind={dev.device_kind!r} devices={device['count']} "
        f"jax={jax.__version__} compile_cache={cache_dir}"
    )
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU and has no CPU mode; JAX found platform {dev.platform!r}",
            file=sys.stderr,
        )
        return 2

    report: dict = {"ok": False, "device": device, "jax": jax.__version__, "compile_cache": cache_dir}
    try:
        run_phases(report)
        report["ok"] = True
    except Exception:  # noqa: BLE001 — reported as the verdict, exit code 1
        traceback.print_exc()
        report["error"] = traceback.format_exc(limit=-3)
    report["claim"] = None
    line = json.dumps(report)
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "chip_smoke.json"), "w") as f:
        f.write(line + "\n")
    say(f"report {line}")
    sys.stderr.flush()
    print(json.dumps({"ok": report["ok"], "device": device}), flush=True)
    return 0 if report["ok"] else 1


def run_phases(result: dict) -> None:
    """Every phase in order, each filling its key of ``result``; the first
    failure raises."""
    from pathway_tpu.observability import device as device_stats
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
    from pathway_tpu.xpacks.llm.rerankers import CrossEncoderReranker

    t_start = time.perf_counter()
    compiles = CompileLog()
    n_devices = result["device"]["count"]

    def phase(name: str, fn, *args):
        t0 = time.perf_counter()
        result[name] = fn(*args)
        say(f"{name} ok in {time.perf_counter() - t0:.1f}s: {json.dumps(result[name])[:600]}")
        return result[name]

    phase("native", check_native)
    stages = corpus()
    embedder = SentenceTransformerEmbedder()
    reranker = CrossEncoderReranker()
    answers: dict = {}

    def served() -> dict:
        answers.update(serve(stages, embedder, reranker))
        return check_answers(answers)

    phase("answers", served)
    phase("kernels", check_launched)
    phase("mosaic", check_mosaic, embedder)
    phase("parity", check_parity, stages, embedder, reranker)
    phase("relational", relational_leg)
    phase("memory", memory_by_device)
    if n_devices >= 4:
        phase("multichip", multichip_leg, stages, embedder, reranker, answers)
    else:
        result["multichip"] = f"skipped: {n_devices} device(s)"
        say(f"multichip {result['multichip']}")
    result["compile"] = dict(
        compiles.summary(),
        process_compile_s=device_stats.status_summary()["process_compile_s"],
    )
    result["wall_s"] = round(time.perf_counter() - t_start, 1)


if __name__ == "__main__":
    sys.exit(main())
