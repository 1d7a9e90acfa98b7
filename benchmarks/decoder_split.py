"""Where the device time of a decoder launch goes: the operations inside
``jit__decoder_prefill`` at one prompt length and ``jit__decoder_step`` at one
row count, for the language model of a benchmark configuration at its
published size, grouped by what an operation is (its HLO opcode and output
shape: forty layers give forty of each).

    chiprun -- python3 benchmarks/decoder_split.py --config adaptive-rag-granite-4h-micro --length 1350 --rows 4

Prints per executable the launch's device time and its operation groups in
order of cost. A chip tool, not a benchmark: it refuses to run off a TPU.
"""

from __future__ import annotations

import argparse
import os
import re
import shutil
import sys
import tempfile

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import reduce_trace, run  # noqa: E402
from chipbench.pipelines.answer_llm import build_chat  # noqa: E402


def split(events: list, module: str, top: int) -> None:
    launches = sorted((s, s + d) for _p, line, n, s, d in events
                      if line == reduce_trace.MODULES and n.startswith(module))
    groups: dict[str, list] = {}
    for _p, line, n, s, d in events:
        if line == reduce_trace.OPS and any(a <= s < b for a, b in launches):
            name, _, rest = n.partition(" = ")
            shape = rest.split("{")[0].split(" ")[0]
            ent = groups.setdefault(re.sub(r"[.\d]+$", "", name.lstrip("%")) + " " + shape, [0, 0, n[:160]])
            ent[0] += d
            ent[1] += 1
    n_l = max(1, len(launches))
    print(f"{module}: {len(launches)} launches, {sum(b - a for a, b in launches) / n_l / 1e6:.3f} ms a launch, "
          f"ops {sum(v[0] for v in groups.values()) / n_l / 1e6:.3f} ms in {len(groups)} groups", flush=True)
    for key, (dur, count, head) in sorted(groups.items(), key=lambda kv: -kv[1][0])[:top]:
        print(f"  {dur / n_l / 1e6:8.3f} ms x{count / n_l:5.1f}  {key}   | {head}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="adaptive-rag-granite-4h-micro")
    ap.add_argument("--length", type=int, default=1350)
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--launches", type=int, default=5)
    ap.add_argument("--top", type=int, default=28)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("decoder_split: no TPU here; device times come only from the chip", file=sys.stderr)
        return 2
    from pathway_tpu.internals.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    config = run.load_json(run.HERE, "configs", args.config + ".json")
    m = build_chat(config, jax.random.PRNGKey(0)).model
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, m.cfg.vocab_size, size=args.length).astype(np.int32) for _ in range(args.rows)]
    cache = m.new_cache()
    for slot, p in enumerate(prompts):
        out, _l, cache, _L = m.run_prefill(cache, [slot], [p])
    slots, pos = list(range(args.rows)), [args.length] * args.rows
    out, _l, cache, _R = m.run_step(cache, slots, [5] * args.rows, pos)
    np.asarray(out)
    trace_dir = tempfile.mkdtemp(prefix="decoder_split_")
    jax.profiler.start_trace(trace_dir)
    for i in range(args.launches):
        out, _l, cache, _L = m.run_prefill(cache, [0], [prompts[0]])
        np.asarray(out)
        out, _l, cache, _R = m.run_step(cache, slots, [5] * args.rows, [p + 1 + i for p in pos])
        np.asarray(out)
    jax.profiler.stop_trace()
    events = [e for e in reduce_trace.load(trace_dir) if e[0].startswith("/device:")]
    shutil.rmtree(trace_dir, ignore_errors=True)
    split(events, "jit__decoder_prefill", args.top)
    split(events, "jit__decoder_step", args.top)
    return 0


if __name__ == "__main__":
    sys.exit(main())
