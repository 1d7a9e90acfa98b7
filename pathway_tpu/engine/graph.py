"""Engine dataflow graph and the tick scheduler.

Role of the reference's worker main loop (``src/engine/dataflow.rs:6202-6255``:
``loop { probers; flushers; pollers; worker.step_or_park }``): a topologically-ordered
DAG of engine nodes processes **delta blocks** tick by tick. Each logical timestamp is
one tick; within a tick the scheduler sweeps nodes in topo order until quiescent, then
advances the frontier (notifying temporal operators: buffers, forget, windows), then
sweeps again — so all downstream consequences of a timestamp are drained before the
next timestamp starts, giving the reference's "every output reflects a known prefix of
inputs" consistency model.
"""

from __future__ import annotations

import heapq
import threading
import time as _time
from typing import Any, Callable

import numpy as np

from pathway_tpu.engine.blocks import DeltaBatch, concat_batches
from pathway_tpu import observability as _obs
from pathway_tpu.internals.trace import run_annotated as _run_annotated
from pathway_tpu.observability import audit as _audit
from pathway_tpu.observability import device as _device_prof
from pathway_tpu.observability import engine_phases as _phases
from pathway_tpu.observability import requests as _requests
from pathway_tpu.observability import spans as _spans
from pathway_tpu.resilience import faults as _faults

END_OF_STREAM = np.iinfo(np.int64).max  # frontier value after all input closed


SOLO = "solo"  # exchange marker: route every row to worker 0 (serial operator)

BROADCAST = "broadcast"  # exchange marker: deliver every row to EVERY worker
# (replicated consumers, e.g. index queries fanned out over doc shards)


class Node:
    """Engine operator. Subclasses implement ``process`` and optionally
    ``on_frontier``.

    ``exchange_key(port)`` declares how a multi-worker runtime must partition
    this node's input rows (the reference's exchange-by-shard contract,
    ``src/engine/dataflow/shard.rs``): ``None`` = no co-location requirement
    (stateless; process rows where they are produced), a callable
    ``batch -> uint64[n]`` = co-locate rows by that key's shard, ``SOLO`` =
    the operator is serial (global watermark / external index / output order) and
    runs entirely on worker 0."""

    name: str = "node"

    #: attribute names that constitute this node's operator state; empty =
    #: stateless. The operator-persistence layer (``persistence/snapshots.py``,
    #: reference ``src/persistence/operator_snapshot.rs:21-342``) pickles these
    #: at snapshot ticks and restores them on restart, making recovery
    #: O(state) instead of O(history).
    snapshot_attrs: tuple[str, ...] = ()

    def snapshot_state(self) -> dict | None:
        """Operator state for persistence, or None when stateless."""
        if not self.snapshot_attrs:
            return None
        return {a: getattr(self, a) for a in self.snapshot_attrs}

    def restore_state(self, state: dict) -> None:
        for a, v in state.items():
            setattr(self, a, v)

    #: True when this node's state keys live on the worker the shard map says
    #: owns them (keyed-exchange discipline) — an O(moved-state) migration may
    #: then read only the old shards whose ranges overlap the new worker's.
    #: Nodes whose state placement follows something OTHER than key ownership
    #: (e.g. a partitioned source's per-partition slice) set this False and a
    #: migration reads every old shard for them instead.
    migrate_aligned: bool = True

    def migrate_mode(self) -> str | None:
        """How an O(moved-state) rescale may move this node's persisted shard:
        ``"keyed"`` — state is key-addressed; merge overlapping old shards via
        :meth:`migrate_restore`. ``"solo"`` — the node runs serially on global
        worker 0 under every shape, so its single shard restores positionally.
        ``None`` — neither holds; the whole restore must fall back to
        reshard-by-replay."""
        if type(self).migrate_restore is not Node.migrate_restore:
            return "keyed"
        if self.exchange_key(0) == SOLO:
            return "solo"
        return None

    def migrate_restore(self, shards: list[dict], keep) -> dict | None:
        """Merge old per-worker snapshot states into THIS worker's state for an
        O(moved-state) rescale (``PATHWAY_SHARDMAP_MIGRATION``).

        ``shards`` are the ``snapshot_state()`` dicts of every old worker whose
        owned key ranges overlap this worker's new ranges; ``keep`` maps a
        ``uint64`` key array to a boolean mask of keys this worker owns under
        the NEW shard map. Returns a state dict for :meth:`restore_state`, or
        ``None`` when the merged state is empty.

        The default (this method not overridden) means the node does NOT
        support keyed migration — the restore falls back to reshard-by-replay
        for the whole pipeline (``persistence/snapshots.py``)."""
        raise NotImplementedError

    def exchange_key(self, port: int):
        # stateful nodes keyed by row key need co-location by row key; stateless
        # subclasses override with None, specially-keyed ones with their key fn
        return lambda batch: batch.keys

    def __init__(self, n_inputs: int = 1):
        self.n_inputs = n_inputs
        self.node_index: int = -1  # set by EngineGraph
        self._buffers: list[list[DeltaBatch]] = [[] for _ in range(n_inputs)]
        self.stats_rows_in = 0
        self.stats_rows_out = 0
        self.stats_time_ns = 0
        # per-operator probes (reference: Prober / OperatorStats{latency,lag},
        # src/engine/dataflow.rs:678-806, graph.rs:497-527): queue latency =
        # wall time a pending input set waited before this node drained it;
        # last processed logical time feeds the lag computation in monitoring
        self.stats_latency_ms = 0.0  # last drain
        self.stats_latency_ewma_ms = 0.0
        self.stats_last_time = -1
        self._pending_since: int | None = None

    # -- scheduler interface --
    def accept(self, port: int, batch: DeltaBatch) -> None:
        if not batch.is_empty:
            if self._pending_since is None:
                self._pending_since = _time.perf_counter_ns()
            self._buffers[port].append(batch)

    def has_pending(self) -> bool:
        return any(self._buffers)

    def drain(self) -> list[DeltaBatch | None]:
        if self._pending_since is not None:
            lat = (_time.perf_counter_ns() - self._pending_since) / 1e6
            self.stats_latency_ms = lat
            self.stats_latency_ewma_ms = (
                lat
                if self.stats_latency_ewma_ms == 0.0
                else 0.8 * self.stats_latency_ewma_ms + 0.2 * lat
            )
            self._pending_since = None
        out: list[DeltaBatch | None] = []
        for port in range(self.n_inputs):
            out.append(concat_batches(self._buffers[port]))
            self._buffers[port] = []
        for b in out:
            if (
                b is not None
                and b.time is not None
                and b.time != END_OF_STREAM  # the close tick is not a logical time
                and b.time > self.stats_last_time
            ):
                self.stats_last_time = b.time
        return out

    # -- operator interface --
    def poll(self, time: int) -> list[DeltaBatch]:
        """Called at tick start; source nodes emit their pending input here."""
        return []

    def has_queued_input(self) -> bool:
        """Does this source hold input that a later ``poll`` will emit? A
        source that cannot say answers True (``Runtime.input_queued``)."""
        return True

    def process(self, inputs: list[DeltaBatch | None], time: int) -> list[DeltaBatch]:
        """Consume one round of input batches, return emissions (all at ``time``)."""
        return []

    def on_frontier(self, time: int) -> list[DeltaBatch]:
        """Called when the frontier passes ``time`` (end of tick). May emit."""
        return []

    def on_tick_complete(self, time: int) -> None:
        """Called once per tick AFTER the frontier loop settles — everything
        emitted at ``time`` has been routed. Side effects only (sinks,
        callbacks); emissions are not possible here."""

    def on_end(self) -> None:
        """Stream closed — release resources, fire final callbacks."""


class EngineGraph:
    def __init__(self) -> None:
        self.nodes: list[Node] = []
        # edges[i] = list of (consumer_index, port)
        self.edges: dict[int, list[tuple[int, int]]] = {}

    def add_node(self, node: Node, inputs: list[Node]) -> Node:
        node.node_index = len(self.nodes)
        self.nodes.append(node)
        assert len(inputs) == node.n_inputs, f"{node.name}: wrong input arity"
        for port, src in enumerate(inputs):
            assert src.node_index >= 0 and src.node_index < node.node_index, (
                f"{node.name}: inputs must be added before consumers (topo order)"
            )
            self.edges.setdefault(src.node_index, []).append((node.node_index, port))
        return node


class Worker:
    """One engine graph as the tick loop drives it: its sweep plan
    (``engine/fusion.py``), the dirty step positions and the active sweep's
    heap. ``lock`` is None where one thread owns the graph (``Scheduler``);
    the multi-worker runtimes give every worker one, and it guards the
    accepts, marks and drains that cross threads (sibling workers, the peer
    links' readers)."""

    __slots__ = ("index", "graph", "plan", "lock", "dirty", "heap", "span_attrs")

    def __init__(self, index: int, graph: EngineGraph, plan, lock=None):
        self.index = index
        self.graph = graph
        self.plan = plan
        self.lock = lock
        #: dirty step positions (guarded by ``lock`` where there is one)
        self.dirty: set[int] = set()
        #: the active sweep's heap — only the sweeping thread touches it
        self.heap: list[int] | None = None
        self.span_attrs = {} if lock is None else {"pathway.worker": index}

    def mark(self, node_index: int) -> None:
        """Mark the step that owns ``node_index`` dirty. The caller holds
        ``lock`` where there is one."""
        self.dirty.add(self.plan.pos_of[node_index])

    def deliver(self, node_index: int, port: int, batch: DeltaBatch) -> None:
        """Accept a batch that another thread routed here."""
        with self.lock:
            self.graph.nodes[node_index].accept(port, batch)
            self.dirty.add(self.plan.pos_of[node_index])

    def take_dirty(self) -> list[int] | None:
        """The dirty positions in topological order, cleared; None when the
        worker is quiescent (an O(1) check)."""
        if self.lock is None:
            return self._pop_dirty()
        with self.lock:
            return self._pop_dirty()

    def _pop_dirty(self) -> list[int] | None:
        dirty = self.dirty
        if not dirty:
            return None
        heap = sorted(dirty)
        dirty.clear()
        return heap

    def take_inputs(self, node: Node) -> list[DeltaBatch | None] | None:
        """Drain ``node``'s pending input, or None when it has none."""
        lock = self.lock
        if lock is None:
            return node.drain() if node.has_pending() else None
        with lock:
            return node.drain() if node.has_pending() else None


class TickLoop:
    """The tick loop, once, for the three runtimes: poll, sweep the dirty
    steps to quiescence, frontier rounds, ``on_tick_complete``,
    ``on_tick_done``.

    The sweep is PLAN-driven (``engine/fusion.py``): fused chains execute as
    single steps and idle nodes are never visited — routing marks the
    consumer's step dirty, and a sweep drains the dirty set in topological
    order (edges only point forward, so one drain reaches quiescence). The
    poll/frontier/complete loops visit only nodes that override those hooks.

    A runtime supplies what truly differs, by overriding: where a routed
    batch goes (``_route``), which sources a worker polls (``_pollers``),
    when a round of sweeps is over (``_round`` / ``_settle``) and when a
    frontier round is (``_frontier_round``). The defaults are the
    single-process answers: accept and mark, every source, a loop."""

    #: a short-lived inner graph (iterate's body): keeps its ticks out of the
    #: span ring and the request plane
    transient = False
    #: the process id a fault plan's ``corrupt_polled`` entries are keyed by
    pid = 0

    def __init__(self) -> None:
        self.current_time = 0
        self.on_tick_done: list[Callable[[int], None]] = []
        # live tracing (observability plane): None when PATHWAY_TRACE=off and
        # no profiler session is on; ``_tr`` is the tracer during a sampled
        # tick — the hot loops below pay exactly one is-not-None test per guard
        self.tracer = None
        self._tr = None
        # request-scoped tracing (observability/requests.py): the installed
        # plane while a request is in flight this tick, else None — sweep
        # steps pay one is-None test
        self._rp = None
        #: the workers this loop drives, in index order (set at build)
        self._workers: list[Worker] = []

    # ---------------------------------------------------------------- routing
    def _accept_local(self, worker: Worker, ci: int, port: int, batch: DeltaBatch) -> None:
        """Same-worker accept from the worker's own thread: a mid-sweep mark
        goes straight onto the active heap (all edges point forward, so the
        marked step is still ahead of the cursor and runs in this sweep)."""
        worker.graph.nodes[ci].accept(port, batch)
        pos = worker.plan.pos_of[ci]
        heap = worker.heap
        if heap is not None:
            heapq.heappush(heap, pos)
        elif worker.lock is None:
            worker.dirty.add(pos)
        else:
            with worker.lock:
                worker.dirty.add(pos)

    def _route(self, worker: Worker, producer: Node, batches: list[DeltaBatch]) -> bool:
        """Hand ``producer``'s emissions to its consumers; True if any row
        went anywhere."""
        routed = False
        consumers = worker.graph.edges.get(producer.node_index, ())
        for batch in batches:
            if batch is None or batch.is_empty:
                continue
            producer.stats_rows_out += len(batch)
            for ci, port in consumers:
                self._accept_local(worker, ci, port, batch)
                routed = True
        return routed

    # ------------------------------------------------------------------ sweep
    def _run_node(self, worker: Worker, node: Node, time: int, aud) -> bool:
        """One node step: drain, process, span, route."""
        inputs = worker.take_inputs(node)
        if inputs is None:
            return False
        rows_in = sum(len(b) for b in inputs if b is not None)
        node.stats_rows_in += rows_in
        tr, rp = self._tr, self._rp
        tok = (
            _spans.step_begin(tr, rp, f"sweep/{node.name}")
            if tr is not None or rp is not None
            else None
        )
        t0 = _time.perf_counter_ns()
        out = _run_annotated(node, node.process, inputs, time)
        node.stats_time_ns += _time.perf_counter_ns() - t0
        if tok is not None:
            _spans.step_end(
                tok, time, rows_in, sum(len(b) for b in out if b is not None),
                {"pathway.operator.id": node.node_index, **worker.span_attrs},
            )
        if aud is not None:
            # audit plane: per-edge cardinality/selectivity counters (node
            # instances are per-worker; the read side sums by position)
            aud.note_edge(node, inputs, out)
        self._route(worker, node, out)
        return True

    def _run_chain(self, worker: Worker, chain, time: int, aud) -> bool:
        """One fused-chain step: drain, hand off member to member, route the
        tail. The span is per CHAIN."""
        tr, rp = self._tr, self._rp
        tok = (
            _spans.step_begin(tr, rp, f"sweep/chain{{{chain.label}}}")
            if tr is not None or rp is not None
            else None
        )
        t0 = _time.perf_counter_ns()
        ptok = _phases.start()
        try:
            out, processed, rows_in, rows_out = chain.execute(time, worker.lock, aud)
        finally:
            _phases.stop(ptok, "fused")
        if not processed:
            if tok is not None:
                _spans.step_drop(tok)
            return False
        chain.tail.stats_time_ns += _time.perf_counter_ns() - t0
        if tok is not None:
            _spans.step_end(
                tok, time, rows_in, rows_out,
                {
                    "pathway.operator.id": chain.operator_ids(),
                    **worker.span_attrs,
                    "pathway.chain.nodes": len(chain.members),
                },
            )
        self._route(worker, chain.tail, out)
        return True

    def _sweep(self, worker: Worker, time: int) -> bool:
        """Drain ``worker``'s dirty steps in topo order; True if any step did
        work."""
        heap = worker.take_dirty()
        if heap is None:
            return False
        aud = _audit.current()
        # edge cardinality recording rides the audit plane's deterministic
        # tick sample — unsampled ticks pay only this flag read
        if aud is not None and not aud.edge_sampled:
            aud = None
        worker.heap = heap
        any_work = False
        by_pos = worker.plan.by_pos
        last = -1
        try:
            while heap:
                pos = heapq.heappop(heap)
                if pos == last:
                    continue  # duplicate marks collapse (ascending pops)
                last = pos
                step = by_pos[pos]
                if step.chain is not None:
                    if self._run_chain(worker, step.chain, time, aud):
                        any_work = True
                elif self._run_node(worker, step.node, time, aud):
                    any_work = True
        finally:
            worker.heap = None
        return any_work

    def _sweep_all(self, time: int) -> bool:
        """One sweep of every worker, concurrently where there are several.
        A worker's exception (e.g. terminate_on_error aborting a batch) is
        re-raised here, so the run fails loudly instead of dropping that
        worker's batch."""
        workers = self._workers
        if len(workers) == 1:
            return self._sweep(workers[0], time)
        results: list[Any] = [False] * len(workers)

        def target(i: int, w: Worker) -> None:
            try:
                results[i] = self._sweep(w, time)
            except BaseException as e:  # noqa: BLE001 — transported to caller
                results[i] = e

        threads = [
            threading.Thread(target=target, args=(i, w)) for i, w in enumerate(workers)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for r in results:
            if isinstance(r, BaseException):
                raise r
        return any(results)

    # ----------------------------------------------------------------- rounds
    def _pollers(self, worker: Worker) -> list[Node]:
        """The sources ``worker`` polls this tick."""
        return worker.plan.pollers

    def _round(self, time: int) -> bool:
        """One round of sweeps; True if it did any work."""
        return self._sweep_all(time)

    def _settle(self, time: int) -> bool:
        """Rounds until nothing is pending anywhere; True if any did work."""
        worked = False
        while self._round(time):
            worked = True
        return worked

    def _frontier_round(self, time: int) -> bool:
        """Notify the frontier nodes in topo order (only nodes that override
        ``on_frontier`` are visited); True if an emission re-entered the
        tick, which then settles and goes round again."""
        tr = self._tr
        progressed = False
        for worker in self._workers:
            for node in worker.plan.frontier_nodes:
                tok = tr.begin(f"frontier/{node.name}") if tr is not None else None
                out = _run_annotated(node, node.on_frontier, time)
                if tok is not None:
                    tr.end(
                        tok,
                        {"pathway.rows_out": sum(len(b) for b in out), **worker.span_attrs},
                        keep=bool(out),
                    )
                if self._route(worker, node, out):
                    progressed = True
        return progressed

    # ------------------------------------------------------------------- tick
    def run_tick(self, time: int) -> None:
        """Process everything pending at logical ``time`` to quiescence, then
        advance the frontier past it."""
        self.current_time = time
        # device plane: steps an armed jax.profiler window, stamps the flight
        # recorder's tick ring (two global reads when profiling is off)
        _device_prof.tick_hook(time)
        # span plane: one flag read brings a tracer up for the ticks of a
        # profiler session
        tracer = None
        if not self.transient:
            tracer = self.tracer = _obs.tick_tracer(self.tracer)
        tick_tok = tracer.begin_tick(time) if tracer is not None else None
        tr = self._tr = tracer if tick_tok is not None else None
        # request plane: active for this tick only while a request is in
        # flight (one global read + one flag read)
        rp = None if self.transient else _requests.current()
        if rp is not None and (not rp.hot or time == END_OF_STREAM):
            rp = None
        self._rp = rp
        if rp is not None:
            rp.note_tick(time)
        aud = _audit.current()
        if aud is not None:
            aud.begin_tick(time)
        worked = False
        for worker in self._workers:
            for node in self._pollers(worker):
                tok = tr.begin(f"tick/poll/{node.name}") if tr is not None else None
                polled = _run_annotated(node, node.poll, time)
                if polled:
                    worked = True
                    # fault plan (flip_diff/drop_retract) corrupts BEFORE the
                    # audit monitors observe — the tripwire sees exactly what
                    # the engine will
                    polled = _faults.corrupt_polled(self.pid, time, polled)
                    if aud is not None:
                        aud.observe_input(node, polled, time)
                if tok is not None:
                    tr.end(
                        tok,
                        {"pathway.rows": sum(len(b) for b in polled), **worker.span_attrs},
                        keep=bool(polled),
                    )
                self._route(worker, node, polled)
        if self._settle(time):
            worked = True
        # frontier phase: emissions re-enter the same tick
        while self._frontier_round(time):
            worked = True
            self._settle(time)
        if tr is not None and not worked:
            tr = None  # an idle tick: its wait is its whole record
        tok = tr.begin("tick/complete") if tr is not None else None
        for worker in self._workers:
            for node in worker.plan.tick_complete_nodes:
                _run_annotated(node, node.on_tick_complete, time)
        if tok is not None:
            tr.end(tok)
            tok = tr.begin("tick/done")
        for cb in self.on_tick_done:
            cb(time)
        if tok is not None:
            tr.end(tok)
        if tick_tok is not None:
            self._tr = None
            tracer.end_tick(time, tick_tok, worked)


class Scheduler(TickLoop):
    """The single-process runtime's loop: one worker, no lock, no thread."""

    def __init__(self, graph: EngineGraph, transient: bool = False):
        super().__init__()
        self.graph = graph
        self.transient = transient
        from pathway_tpu.engine import fusion as _fusion

        # transient = a short-lived inner graph rebuilt per use (iterate's
        # fixed-point runner): chain fusion still applies, but the jitted
        # segment tier is disabled — a fresh jax.jit per rebuild would
        # re-trace its kernel every tick
        self.plan = _fusion.build_plan(graph, exchange_aware=False, transient=transient)
        self._workers = [Worker(0, graph, self.plan)]

    def close(self) -> None:
        """Input exhausted: flush temporal buffers and fire end callbacks."""
        self.run_tick(END_OF_STREAM)
        for node in self.graph.nodes:
            node.on_end()
