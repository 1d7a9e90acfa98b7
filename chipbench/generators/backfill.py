"""A connector backfill: documents offered through the docs channel as fast
as the store takes them, with at most ``outstanding`` unacknowledged; no
queries in the window.

The acknowledged count is read from ``/v1/statistics`` every ``poll_s``
(four times a second), and with it the newest document offered is asked
for, as a freshness monitor would. After ``seconds`` no more is offered; the window
closes when the count has reached what was offered and ``/v1/retrieve`` finds
the last document, so the drain is inside the window.
"""

from __future__ import annotations

import asyncio
import time

from chipbench import corpus


async def window(gen, cmd: dict) -> dict:
    tr, seconds = gen.plan["traffic"], cmd["seconds"]
    first = gen.plan["preload_blocks"]
    before = gen.offered
    start = time.monotonic()
    block, last = first, ""
    acked = before
    while time.monotonic() - start < seconds:
        while gen.offered - acked + corpus.BLOCK <= tr["outstanding"]:
            last = gen.offer_block(block)[-1]
            block += 1
        await asyncio.sleep(tr["poll_s"])
        acked = max(acked, await gen.acknowledged())
        # the monitor also asks for the newest document: the index lands its
        # staged rows when it is next searched, so this bounds what one
        # scatter carries to a poll's worth of documents
        await gen.indexed(last)
    offered_s = time.monotonic() - start
    acked = await gen.drained(last, 0.02)
    end = time.monotonic() - start
    return {
        "start_ns": int(start * 1e9), "close_s": offered_s, "end_s": end,
        "attempted": gen.offered - before, "failed": gen.offered - acked, "lost": gen.offered - acked,
        "documents": acked - before, "first_block": first, "blocks": block - first,
    }
