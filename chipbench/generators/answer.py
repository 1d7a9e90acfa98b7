"""Open-loop Poisson arrivals of distinct short questions to the answer route.

As ``poisson`` (the same quantile gaps and lengths for every seed in another
order, each request timed from the moment it was due to the last byte of its
answer, a refused request sent again after ``retry_refused_s``), but the
question goes in the field the traffic names (``field``: ``/v2/answer`` takes
``prompt``) beside the constant ``body`` (``return_context_docs``), and a kept
body is the whole answer object. ``payload.k`` is the retrieval the answer
rests on; it is not posted (the server's ``search_topk`` decides it) and is
what the comparison and ``chipbench.control`` read.
"""

from __future__ import annotations

import asyncio
import time

from chipbench import corpus


async def window(gen, cmd: dict) -> dict:
    tr, seed, seconds = gen.plan["traffic"], gen.seed, cmd["seconds"]
    n = max(1, round(tr["rate"] * seconds))
    pool = corpus.docs(seed, 0, gen.plan["preload_blocks"], gen.plan["documents"])
    stream = cmd.get("stream", 2)  # the warm-up burst draws other questions than the window
    texts = corpus.queries(seed, n, tr["queries"], pool, stream)
    due = corpus.arrival_offsets(seed, n, tr["rate"], stream)
    keep = set(corpus.sample(seed, n, tr["sample"], stream))
    keep.add(max(range(n), key=lambda i: len(texts[i])))
    sent, done, status, refusals, bodies = [0.0] * n, [0.0] * n, [0] * n, [0] * n, {}
    retry_s = tr.get("retry_refused_s")
    start = time.monotonic() + 0.05

    async def one(i: int) -> None:
        delay = start + due[i] - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        sent[i] = time.monotonic() - start
        while True:
            status[i], body = await gen.client.post(
                tr["route"], {tr["field"]: texts[i], **tr["body"]}, keep_body=i in keep
            )
            if status[i] != 429 or not retry_s:
                break
            refusals[i] += 1
            await asyncio.sleep(retry_s)
        done[i] = time.monotonic() - start
        if i in keep:
            bodies[i] = body

    tasks = [asyncio.ensure_future(one(i)) for i in range(n)]
    _finished, pending = await asyncio.wait(tasks, timeout=float(due[-1]) + 0.05 + 60.0)
    for t in pending:  # never answered within a minute of the close
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    end = time.monotonic() - start
    ok = [s == 200 for s in status]
    return {
        "start_ns": int(start * 1e9), "close_s": float(due[-1]), "end_s": end,
        "attempted": n, "failed": n - sum(ok),
        "lost": sum(1 for s in status if s != 200 and (retry_s or s != 429)),
        "refusals": sum(refusals),
        "due_s": due.tolist(),
        "latency_ms": [(d - u) * 1e3 if o else None for d, u, o in zip(done, due.tolist(), ok)],
        "late_ms": [(s - u) * 1e3 for s, u in zip(sent, due.tolist())],
        "sample": [[i, texts[i], bodies.get(i)] for i in sorted(keep) if ok[i]],
    }
