"""``python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one process, one cell, once. Load, warm up, measure for
``--seconds``, compare with the reference, print one last line, exit.

Everything a cell needs is found by name: the cell's entry in
``BENCHMARK.json`` names its configuration (``configs/<config>.json``) and
its traffic (``traffic/<traffic>.json``); ``workloads/<cell>.json`` holds
what belongs to the pair (the comparison's limits, the shapes to warm); the
configuration names its served pipeline (``pipelines/<name>.py``); the
traffic names its generator (``generators/<kind>.py``) and its comparison
(``comparisons/<kind>.py``); each metric is ``metrics/<metric>.py``. No chip
is a failure, never a CPU fallback.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()  # process start, as near as Python lets us see it

import argparse
import gc
import importlib
import importlib.util
import json
import os
import queue
import shutil
import subprocess
import sys
import threading
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, "out")


def say(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


def load_cell(name: str, entry: dict | None = None) -> types.SimpleNamespace:
    """The cell ``name`` as ``BENCHMARK.json`` has it (``entry`` stands in for
    a cell whose files are here and whose entry is not yet: the tests')."""
    bench = load_json(ROOT, "BENCHMARK.json")
    entry = entry or next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")

    def wanted(group: str) -> list[dict]:
        return [m for m in bench[group] if name in m.get("workloads", [name])]

    return types.SimpleNamespace(
        name=name, chips=entry["chips"],
        config=load_json(HERE, "configs", entry["config"] + ".json"),
        traffic=load_json(HERE, "traffic", entry["traffic"] + ".json"),
        cell=load_json(HERE, "workloads", name + ".json"),
        end_to_end=wanted("end_to_end"), per_layer=wanted("per_layer"),
    )


def load_metric(name: str):
    """``metrics/<name>.py``; names hold dots, so it is loaded by path."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class CompileCount:
    """Executables the process had to fetch or build: every compile request
    that went through the persistent cache, hit or miss."""

    def __init__(self) -> None:
        from jax import monitoring

        self.requests = self.hits = 0
        monitoring.register_event_listener(self._on)

    def _on(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


class Generator:
    """The load generator's process and its two channels: commands down its
    stdin, and up its stdout document blocks (to the docs connector's queue)
    and replies."""

    def __init__(self, plan: dict, doc_blocks: "queue.Queue"):
        env = {k: v for k, v in os.environ.items() if not k.startswith(("JAX_", "XLA_", "TPU_"))}
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "chipbench.loadgen"], cwd=ROOT, env=env,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        )
        self.replies: "queue.Queue[dict | None]" = queue.Queue()
        self.doc_blocks = doc_blocks
        self.reader = threading.Thread(target=self._read, daemon=True)
        self.reader.start()
        self._send(plan)

    def _read(self) -> None:
        for line in self.proc.stdout:
            msg = json.loads(line)
            if msg["t"] == "docs":
                self.doc_blocks.put(msg["texts"])
            else:
                self.replies.put(msg)
        self.replies.put(None)

    def _send(self, obj: dict) -> None:
        self.proc.stdin.write(json.dumps(obj) + "\n")
        self.proc.stdin.flush()

    def call(self, timeout: float = 600.0, **cmd) -> dict:
        self._send(cmd)
        reply = self.replies.get(timeout=timeout)
        if reply is None:
            raise RuntimeError(f"the load generator died during {cmd['cmd']!r} (exit {self.proc.wait()})")
        return reply

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self._send({"cmd": "quit"})
            except OSError:
                pass
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        self.proc.wait()
        self.proc.stdin.close()
        self.reader.join(timeout=10)
        self.proc.stdout.close()


def build_models(config: dict, seed: int):
    """The embedder (and the reranker, where the configuration has one) as a
    user builds them: BERT block at the published widths, bf16 compute,
    parameters from the seed."""
    import jax.numpy as jnp

    from chipbench import weights
    from pathway_tpu.ops.encoder import EncoderConfig
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    def enc_cfg(c: dict) -> EncoderConfig:
        return EncoderConfig(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
            n_layers=c["num_hidden_layers"], d_ff=c["intermediate_size"],
            max_len=c["max_position_embeddings"], dtype=getattr(jnp, config["compute_dtype"]),
            arch="bert", ln_eps=c["layer_norm_eps"],
        )

    eparams = weights.make_params(config, seed, 0)
    embedder = SentenceTransformerEmbedder(model=enc_cfg(config), params=eparams)
    reranker = rparams = None
    if "reranker" in config:
        from pathway_tpu.ops.reranker import JaxCrossEncoder
        from pathway_tpu.xpacks.llm.rerankers import CrossEncoderReranker

        rparams = weights.make_params(config["reranker"], seed, 1, head=True)
        ce = JaxCrossEncoder(enc_cfg(config["reranker"]))
        ce.params = rparams
        reranker = CrossEncoderReranker(model=ce)
    return embedder, eparams, reranker, rparams


def warm_shapes(warm: dict, backend, embedder, reranker) -> None:
    """Direct calls that put every executable the window can ask for into
    the process: query-embedding buckets, search at every query count (the
    index does not bucket it), reranker buckets, scatter buckets."""
    import numpy as np

    for batch, words in warm.get("embed", []):
        embedder.func([" ".join(["w1"] * words)] * batch)
    if "search" in warm:
        dim = backend.index.dimension
        ok = lambda _meta: True  # noqa: E731
        for k in warm["search"]["k"]:
            for q in range(1, warm["search"]["q_max"] + 1):
                backend.search([np.ones(dim, np.float32)] * q, [k] * q, [ok] * q)
    for batch, words in warm.get("rerank", []):
        reranker.func([" ".join(["w1"] * words)] * batch, ["w2 w3 w4"] * batch)
    if warm.get("scatter"):
        from chipbench.archive import KEY0

        ix = backend.index
        live = [(k, s) for k, s in ix._key_to_slot.items() if not KEY0 <= k < KEY0 + (1 << 32)]
        for m in warm["scatter"]:
            part = live[: min(m, len(live))]
            if len(part) == m:  # upsert rows with their own vectors: a no-op that compiles the shape
                ix.add_batch([k for k, _ in part], np.asarray(ix._vectors[np.asarray([s for _, s in part])]))
                ix._flush()


def scaled(ns: types.SimpleNamespace, scale: dict | None) -> None:
    """A rehearsal or a test cuts the scale (archive, capacity, set-up
    documents, warm range); a run on the chip never does."""
    for key, value in (scale or {}).items():
        where, _, field = key.partition(".")
        getattr(ns, where)[field] = value


def run_cell(name: str, seed: int, seconds: float, trace: bool, *, scale: dict | None = None,
             cell: types.SimpleNamespace | None = None) -> dict:
    """Everything after the look for a chip. Returns the result object."""
    import jax

    from pathway_tpu.internals.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    compiles = CompileCount()
    cell = cell or load_cell(name)
    scaled(cell, scale)
    config, traffic = cell.config, cell.traffic
    devices = jax.devices()[: cell.chips]

    import pathway_tpu as pw
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.observability import device as prof

    from chipbench import archive as archive_mod
    from chipbench import corpus, reference, weights

    embedder, eparams, reranker, rparams = build_models(config, seed)
    blocks = config["live_documents"] // corpus.BLOCK
    setup_texts = corpus.docs(seed, 0, blocks, config["documents"])
    arch = None
    if config["archive_rows"]:
        base = reference.embed_texts(eparams, setup_texts, config, config["archive_base_precision"])
        arch = archive_mod.Archive(
            weights.seed_key(seed, 7), base, config["archive_rows"], config["archive_block_rows"],
            config["archive"], config["archive_noise"],
        )
    say(f"models and archive base ready at {time.monotonic() - _T0:.1f} s")

    # the deployment's admission budget: the server refuses (429) what would
    # exceed it, so no tick ever searches for more queries than were warmed
    os.environ["PATHWAY_SERVE_MAX_INFLIGHT"] = str(config["serve_max_inflight"])
    G.clear()
    doc_blocks: "queue.Queue" = queue.Queue()
    built: list = []
    ctx = types.SimpleNamespace(
        config=config, doc_blocks=doc_blocks, reranker=reranker,
        retriever_factory=archive_mod.knn_factory(embedder, config["reserved_space"], arch, built),
    )
    routes = importlib.import_module("chipbench.pipelines." + config["pipeline"]).build(ctx)
    say(f"graph built at {time.monotonic() - _T0:.1f} s")
    gen = Generator(
        {"seed": seed, "routes": routes, "traffic": traffic, "documents": config["documents"],
         "preload_blocks": blocks}, doc_blocks,
    )
    comparison = importlib.import_module("chipbench.comparisons." + traffic["comparison"])
    state: dict = {}
    failure: list[BaseException] = []
    trace_dir = os.path.join(OUT, "trace")

    def control() -> None:
        try:
            pre = gen.call(cmd="preload", blocks=blocks)
            say(f"{pre['documents']} set-up documents indexed in {pre['seconds']:.1f} s; "
                f"index holds {len(built[0].index)} rows")
            warm_shapes(cell.cell.get("warm", {}), built[0], embedder, reranker)
            say(f"shapes warmed at {time.monotonic() - _T0:.1f} s")
            if traffic.get("warm_seconds"):
                gen.call(cmd="window", seconds=traffic["warm_seconds"], stream=9)
            stats = prof.stats()
            counters = lambda: {  # noqa: E731
                "pad": {k: list(v) for k, v in stats.pad.items()},
                "calls": {w.label: w.calls for w in list(prof._wrappers)},
            }
            state["before"] = counters()
            c0 = compiles.requests
            state["setup_s"] = time.monotonic() - _T0
            if trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                state["marker_ns"] = time.monotonic_ns()
                with jax.profiler.TraceAnnotation("chipbench.marker"):
                    time.sleep(0.001)
            state["window"] = gen.call(cmd="window", seconds=seconds, stream=2)
            if trace:
                jax.profiler.stop_trace()
            state["compiles_in_window"] = compiles.requests - c0
            state["after"] = counters()
            state["memory_peak_bytes"] = max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
            )
            state["live_rows"] = len(built[0].index)
            from pathway_tpu.ops import knn

            from pathway_tpu.ops import encoder as enc_mod

            say(f"search shapes (queries, k) this process ran: "
                f"{sorted({(key[4][0][0], dict(key[5:])['k']) for key in knn._search_kernel._seen})}; "
                f"encoder shapes: {sorted({key[2][0] for key in enc_mod.encode_ids_jit._seen})}")
            state["sample"] = comparison.collect(gen, cell, seed, state["window"])
        except BaseException as e:  # noqa: BLE001 - re-raised on the main thread
            failure.append(e)
        finally:
            rt = pw.internals.run.current_runtime()
            if rt is not None:
                rt.request_stop()

    th = threading.Thread(target=control, daemon=True)
    th.start()
    try:
        pw.run(monitoring_level="none")
    finally:
        doc_blocks.put(None)
        th.join(timeout=120)
        gen.close()
    if failure:
        raise failure[0]
    if th.is_alive() or "sample" not in state:
        raise RuntimeError("the control thread did not finish")

    # free the program's state before the reference touches the chip
    for backend in built:
        ix = backend.index
        ix._vectors = ix._norms_sq = ix._valid = ix._key_bits = None
    built.clear()
    G.clear()
    gc.collect()

    win = state["window"]
    t_ref = time.monotonic()
    numbers = comparison.numbers(cell, seed, state["sample"], win, eparams, rparams, arch, setup_texts)
    from chipbench import check

    correct, compared = check.verdict(numbers, cell.cell["limits"])
    # a query refused over the in-flight budget is late, not wrong: its client
    # sends it again and its answer is compared like any other; one that never
    # came, or a document never acknowledged, is not correct
    correct = correct and win["lost"] == 0
    say(f"reference and comparison took {time.monotonic() - t_ref:.1f} s; "
        f"{state['compiles_in_window']} compilations inside the window")

    kind = devices[0].device_kind
    ctxm = types.SimpleNamespace(
        cell=cell, config=config, traffic=traffic, seed=seed, seconds=seconds, window=win,
        setup_s=state["setup_s"], before=state["before"], after=state["after"],
        live_rows=state["live_rows"], device_kind=kind, trace=None,
    )
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": state["memory_peak_bytes"]}
    result: dict = {"correct": bool(correct), "attempted": win["attempted"], "failed": win["failed"]}
    breakdown = None
    if trace:
        from chipbench import reduce_trace

        events = reduce_trace.load(trace_dir)
        marker = reduce_trace.find_marker(events, "chipbench.marker")
        off = (marker - state["marker_ns"]) if marker is not None else None
        w0 = win["start_ns"] + off if off is not None else None
        window = (w0, w0 + int(win["end_s"] * 1e9)) if w0 is not None else None
        ctxm.trace = reduce_trace.reduce(events, window)
        say(f"trace: {len(events)} events, lines {sorted({(p, l) for p, l, *_ in events if p.startswith('/device:')})}, "
            f"launches {ctxm.trace['launches']}, top ops {ctxm.trace['ops'][:8]}")
        ctxm.trace["offset_ns"] = off
        if window is not None:
            os.makedirs(OUT, exist_ok=True)
            with open(os.path.join(OUT, "last_trace_head.json"), "w", encoding="utf-8") as f:
                json.dump({"window": window, "events": reduce_trace.head(events, window)}, f)
        device["busy_s"], device["window_s"] = ctxm.trace["busy_s"], ctxm.trace["window_s"]
        flights = comparison.in_flight(win, off) if off is not None else []
        breakdown = {
            "device_ops": [[k, v] for k, v in sorted(ctxm.trace["seconds"].items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": reduce_trace.name_gaps(ctxm.trace["gaps"], flights, *comparison.GAP_NAMES),
        }
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        value = load_metric(m["name"]).read(ctxm)
        if value is not None:  # a reader that finds nothing to read returns nothing
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared  # every number compared beside its limit: the last key
    for n, c in compared.items():
        say(f"compared {n} = {c['value']:.6g} (limit {c['limit']:.6g})")
    say(f"correct = {result['correct']} (failed {win['failed']} of {win['attempted']}, lost {win['lost']}, "
        f"refused and sent again {win.get('refusals', 0)} times)")
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    import jax

    from chipbench import flops

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        say(f"needs {cell.chips} TPU chip(s); JAX found {len(devices)} x {devices[0].platform}")
        return 2
    flops.peaks(devices[0].device_kind)  # an unknown device is an error before any work
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace), cell=cell)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
