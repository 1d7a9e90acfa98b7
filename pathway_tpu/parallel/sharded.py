"""Multi-worker sharded execution of the engine graph.

The reference's worker model (SURVEY §2.9, ``worker-architecture.md``): every
worker builds the IDENTICAL dataflow; records are exchanged between workers by
key shard before stateful operators; progress (the tick frontier) advances in
lockstep. This module is the block-engine version:

- ``ShardedRuntime(n_workers)`` builds one engine graph per worker from the same
  logical outputs (node indices align across workers by construction).
- At routing time, a consumer's :meth:`Node.exchange_key` decides placement:
  ``None`` → stay on the producing worker (stateless op); a key function →
  split the block by ``shard_of_keys`` and deliver each piece to its owner —
  numeric blocks may instead ride the on-device all_to_all plane
  (``parallel/device_plane.py``, ``PATHWAY_DEVICE_EXCHANGE``); ``SOLO`` →
  everything to worker 0 (serial operators: non-partitioned sources,
  unsharded sinks, sort's global order, non-shardable external indexes).
  Partitioned sources (``local_source`` nodes, e.g. Kafka) poll on their OWN
  worker with disjoint partition slices, and ``fs.write(sharded=True)`` sinks
  write per-worker shards with an ordered merge-commit — the r5 SOLO-pin
  kills (reference ``worker-architecture.md:36-47``). The temporal plane
  shards: temporal/asof-now joins by join key, session windows by instance,
  buffer/forget/freeze row state by row key with one shared watermark cell
  per logical node (``internals/time_ops._SharedWatermark``).
- Each tick runs sweep rounds: all workers sweep concurrently (threads), then
  meet at a barrier; the tick ends when a round does no work anywhere. The
  frontier phase runs the same way, so every worker passes timestamp t before
  any sees t+1 — the global consistency frontier.

Worker threads parallelize the host-side state machinery (hash joins, group
state); the FLOP-heavy work inside nodes is already batched XLA. The same
exchange contract carries to multi-process over ``jax.distributed`` (blocks
serialized between processes instead of handed between threads).
"""

from __future__ import annotations

import threading
from typing import Any

import numpy as np

from pathway_tpu import observability as _obs
from pathway_tpu.engine import fusion as _fusion
from pathway_tpu.engine.blocks import DeltaBatch
from pathway_tpu.engine.graph import BROADCAST, END_OF_STREAM, SOLO, Node, TickLoop, Worker
from pathway_tpu.internals.logical import BuildContext, LogicalNode
from pathway_tpu.parallel.mesh import shard_of_keys
from pathway_tpu.resilience import faults as _faults


class ExchangeLoop(TickLoop):
    """The tick loop of a runtime with several workers: a routed batch goes
    where its consumer's ``exchange_key`` says. The runtime supplies
    ``n_workers``, ``device_plane`` and ``_deliver(worker, ci, port, batch)``
    (this process's worker, or a peer link)."""

    #: versioned ownership table of the cluster's shard-map plane; None keeps
    #: the derived modulo rule
    shardmap = None

    def _route(self, worker: Worker, producer: Node, batches: list[DeltaBatch]) -> bool:
        routed = False
        consumers = worker.graph.edges.get(producer.node_index, ())
        for batch in batches:
            if batch is None or batch.is_empty:
                continue
            producer.stats_rows_out += len(batch)
            for ci, port in consumers:
                routed = True
                key_fn = worker.graph.nodes[ci].exchange_key(port)
                if key_fn is None or (self.n_workers == 1 and callable(key_fn)):
                    self._accept_local(worker, ci, port, batch)
                elif key_fn == SOLO:
                    self._deliver(0, ci, port, batch)
                elif key_fn == BROADCAST:
                    for w_idx in range(self.n_workers):
                        self._deliver(w_idx, ci, port, batch)
                else:
                    route_keys = np.asarray(key_fn(batch), dtype=np.uint64)
                    if (
                        self.device_plane is not None
                        and self.device_plane.should_stage(batch)
                    ):
                        # numeric fast lane: the block rides the mesh at the
                        # next flush instead of host-splitting here
                        self.device_plane.stage(
                            ci, port, worker.index, route_keys, batch
                        )
                        continue
                    shards = shard_of_keys(
                        route_keys, self.n_workers, shard_map=self.shardmap
                    )
                    for w_idx in np.unique(shards):
                        piece = batch.take(np.flatnonzero(shards == w_idx))
                        self._deliver(int(w_idx), ci, port, piece)
        return routed


class ShardedRuntime(ExchangeLoop):
    """Drives W aligned engine graphs tick by tick with key-shard exchange.

    API-compatible with ``engine.runtime.Runtime`` where the single-worker
    code paths touch it (connectors, persistence hooks are worker-0 concerns).
    """

    def __init__(
        self,
        n_workers: int = 2,
        monitoring_level: Any = None,
        autocommit_duration_ms: int | None = 20,
    ):
        if n_workers < 1:
            raise ValueError("need at least one worker")
        super().__init__()
        self.n_workers = n_workers
        self.autocommit_duration_ms = autocommit_duration_ms
        self.monitoring_level = monitoring_level
        self.connectors: list[Any] = []
        self.persistence: Any = None
        self.workers: list[Worker] = []
        self._stop_requested = False
        self.streaming = False  # set after build (see engine.runtime.Runtime)
        # arrival-driven tick scheduling (REST serving plane wakeups)
        from pathway_tpu.engine.runtime import TickWakeup

        self.wakeup = TickWakeup()
        # on-device all_to_all exchange for numeric blocks (None = host-only;
        # see parallel/device_plane.py and PATHWAY_DEVICE_EXCHANGE)
        from pathway_tpu.parallel.device_plane import make_device_plane

        self.device_plane = make_device_plane(n_workers)

    def register_connector(self, driver) -> None:
        self.connectors.append(driver)

    def request_stop(self) -> None:
        self._stop_requested = True

    # ---------------------------------------------------------------- build
    def _build(self, outputs: list[LogicalNode]) -> None:
        # peers build first, worker 0 LAST: node factories may capture the built
        # node into shared holders (connector subjects, rest holders) — the last
        # build must be the one whose sources actually receive events and poll
        self.workers = [None] * self.n_workers  # type: ignore[list-item]
        for w in list(range(1, self.n_workers)) + [0]:
            ctx = BuildContext(
                runtime=self if w == 0 else None,
                worker_index=w,
                n_workers=self.n_workers,
                register=self.register_connector,
                shared_runtime=self,
            )
            for out in outputs:
                ctx.resolve(out)
            if w == 0:
                ctx.finish()
                self._ctx0 = ctx
            # exchange-aware plan: fusing across an exchange would move rows
            # off the worker the routing places them on
            plan = _fusion.build_plan(ctx.graph, exchange_aware=True)
            self.workers[w] = Worker(w, ctx.graph, plan, threading.Lock())
        self._workers = self.workers
        sizes = {len(w.graph.nodes) for w in self.workers}
        assert len(sizes) == 1, "worker graphs misaligned"

    # ---------------------------------------------------------------- routing
    def _deliver(self, worker: int, ci: int, port: int, batch: DeltaBatch) -> None:
        self.workers[worker].deliver(ci, port, batch)

    # ---------------------------------------------------------------- ticking
    def _pollers(self, worker: Worker) -> list[Node]:
        # non-partitioned sources live on worker 0 only — peers' copies never
        # poll (polling them would duplicate every input row per worker);
        # partitioned sources (``local_source``) poll on their OWN worker,
        # each subject owning a disjoint partition slice (r5: the SOLO-pin
        # kill, reference worker-architecture.md:36-47)
        if worker.index == 0:
            return worker.plan.pollers
        return [n for n in worker.plan.pollers if getattr(n, "local_source", False)]

    def _round(self, time: int) -> bool:
        """All workers sweep concurrently, then the device plane flushes its
        staged blocks through one collective per group — the exchange lands
        as new pending work, picked up by the next round."""
        any_work = self._sweep_all(time)
        if self.device_plane is not None and self.device_plane.flush(
            self._deliver, time
        ):
            any_work = True
        return any_work

    # ---------------------------------------------------------------- run loop
    def run(self, outputs: list[LogicalNode]):
        import time as _time

        from pathway_tpu import flow as _flow

        _faults.install_from_env()  # fault plan resets per run (as in Runtime)
        _obs.install_from_env(self)
        _flow.install_from_env(self)  # before build: gates attach to inputs
        try:
            self.tracer = _obs.current()
            return self._run_inner(outputs)
        except BaseException as e:
            _obs.device.on_run_error(e, self)  # flight-recorder post-mortem
            raise
        finally:
            self.tracer = None
            _obs.shutdown()
            _flow.shutdown()

    def _run_inner(self, outputs: list[LogicalNode]):
        import time as _time

        self._build(outputs)
        self.streaming = bool(self.connectors)
        if self.persistence is not None:
            self.persistence.on_graph_built(self._ctx0)
            self.on_tick_done.append(self.persistence.on_tick_done)

        from pathway_tpu import flow as _flow

        plane = _flow.current()
        if plane is not None:
            self.on_tick_done.append(lambda t: plane.on_tick_complete(self, t))
        for driver in self.connectors:
            driver.start()
        if not self.connectors:
            self.run_tick(0)
            self.close()
            return self
        tick = 0
        period = (self.autocommit_duration_ms or 20) / 1000.0
        all_virtual = all(getattr(d, "virtual", False) for d in self.connectors)
        try:
            while not self._stop_requested:
                t0 = _time.perf_counter()
                self.run_tick(tick)
                tick += 1
                from pathway_tpu.engine.runtime import check_connector_failures

                check_connector_failures(self.connectors)
                if all(d.is_finished() for d in self.connectors):
                    self.run_tick(tick)
                    break
                if not all_virtual:
                    elapsed = _time.perf_counter() - t0
                    if elapsed < period:
                        self.wakeup.wait(period - elapsed)
        finally:
            for driver in self.connectors:
                driver.stop()
        # re-check: a subject may error between the in-loop check and the
        # is_finished break (see engine.runtime.Runtime.run)
        from pathway_tpu.engine.runtime import check_connector_failures

        check_connector_failures(self.connectors)
        self.close()
        return self

    def close(self) -> None:
        self.run_tick(END_OF_STREAM)
        for w in self.workers:
            for node in w.graph.nodes:
                node.on_end()
        if self.persistence is not None:
            self.persistence.on_close()

    # Runtime API used by debug capture
    @property
    def scheduler(self):
        return self
