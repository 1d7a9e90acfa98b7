"""The load generator: a process of its own that never imports JAX (one
process holds the chip). It makes its texts from the seed, stamps every event
with the time it was due, and sends on its schedule whether or not the server
keeps up.

It is a small command server on its standard input, one JSON object a line;
every reply is one JSON line on standard output:

- ``{"cmd": "preload", "blocks": n}``: offer document blocks [0, n) through
  the docs channel (``{"t": "docs", "texts": [...]}`` lines that the parent
  feeds to the store's connector) and wait until the store has acknowledged
  and indexed them
- ``{"cmd": "requests", "items": [[route, payload], ...], "parallel": p}``:
  send these, ``p`` at a time, and return every body
- ``{"cmd": "window", ...}``: run the traffic's window: the generator kind
  named in the traffic file (``chipbench/generators/<kind>.py``)
- ``{"cmd": "quit"}``

Times are ``time.monotonic_ns()``, one clock for every process of the host.
"""

from __future__ import annotations

import asyncio
import importlib
import json
import sys
import time

import aiohttp

from chipbench import corpus


class Client:
    """Keep-alive HTTP client for the store's routes on localhost."""

    def __init__(self, routes: dict):
        self.routes = routes  # name -> [port, path]
        self.session = aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0),
            timeout=aiohttp.ClientTimeout(total=120),
        )

    async def post(self, route: str, payload: dict, keep_body: bool = True):
        """(status, body or None). A refused connection or a time-out is
        status 0: the request failed, it is not retried."""
        port, path = self.routes[route]
        try:
            async with self.session.post(f"http://127.0.0.1:{port}{path}", json=payload) as r:
                raw = await r.read()
                return r.status, (json.loads(raw) if keep_body and r.status == 200 else None)
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError):
            return 0, None

    async def close(self) -> None:
        await self.session.close()


class Gen:
    def __init__(self, plan: dict):
        self.plan = plan
        self.seed = plan["seed"]
        self.client = Client(plan["routes"])
        self.offered = 0  # documents sent down the docs channel

    def emit(self, obj: dict) -> None:
        sys.stdout.write(json.dumps(obj, separators=(",", ":")) + "\n")
        sys.stdout.flush()

    def offer_block(self, block: int) -> list[str]:
        texts = corpus.doc_block(self.seed, block, self.plan["documents"])
        self.emit({"t": "docs", "texts": texts})
        self.offered += len(texts)
        return texts

    async def acknowledged(self) -> int:
        status, body = await self.client.post("statistics", {})
        return int(body.get("file_count") or 0) if status == 200 and body else -1

    async def indexed(self, text: str) -> bool:
        status, body = await self.client.post("retrieve", {"query": text, "k": 1})
        return status == 200 and bool(body) and body[0]["text"] == text

    async def drained(self, last_text: str, poll_s: float, timeout_s: float = 300.0) -> int:
        """Wait until the store has acknowledged every offered document and
        ``/v1/retrieve`` finds the last one; returns the acknowledged count."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            n = await self.acknowledged()
            if n >= self.offered and await self.indexed(last_text):
                return n
            await asyncio.sleep(poll_s)
        raise TimeoutError(f"store acknowledged fewer than the {self.offered} documents offered")

    async def preload(self, blocks: int) -> dict:
        t0 = time.monotonic()
        while await self.acknowledged() < 0:  # the server is not up yet
            await asyncio.sleep(0.1)
        last = ""
        for b in range(blocks):
            last = self.offer_block(b)[-1]
        n = await self.drained(last, 0.05) if blocks else 0
        return {"t": "preloaded", "documents": n, "seconds": time.monotonic() - t0}

    async def requests(self, items: list, parallel: int) -> dict:
        out: list = [None] * len(items)
        todo = iter(enumerate(items))

        async def worker():
            for i, (route, payload) in todo:
                out[i] = await self.client.post(route, payload)

        await asyncio.gather(*(worker() for _ in range(max(1, parallel))))
        return {"t": "replies", "replies": out}

    async def window(self, cmd: dict) -> dict:
        kind = importlib.import_module(f"chipbench.generators.{self.plan['traffic']['generator']}")
        res = await kind.window(self, cmd)
        res["t"] = "window"
        return res


async def serve() -> None:
    loop = asyncio.get_running_loop()
    reader = asyncio.StreamReader(limit=1 << 26)  # a command may carry hundreds of texts
    await loop.connect_read_pipe(lambda: asyncio.StreamReaderProtocol(reader), sys.stdin)
    gen = Gen(json.loads(await reader.readline()))
    try:
        while True:
            line = await reader.readline()
            if not line:
                return  # the parent is gone
            cmd = json.loads(line)
            if cmd["cmd"] == "quit":
                return
            if cmd["cmd"] == "preload":
                gen.emit(await gen.preload(cmd["blocks"]))
            elif cmd["cmd"] == "requests":
                gen.emit(await gen.requests(cmd["items"], cmd.get("parallel", 1)))
            elif cmd["cmd"] == "window":
                gen.emit(await gen.window(cmd))
            else:
                raise ValueError(f"unknown command {cmd['cmd']!r}")
    finally:
        await gen.client.close()


if __name__ == "__main__":
    asyncio.run(serve())
