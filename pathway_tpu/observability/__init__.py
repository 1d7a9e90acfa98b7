"""Live telemetry plane: streaming spans, watermarks, latency histograms,
cluster aggregation.

The offline exports (``internals/telemetry.py``) write one OTLP document at
run END; this package streams the same planes *while the pipeline runs* —
the observability the ROADMAP's live-RAG serving target actually needs:

- ``spans``    — Dapper-style head-sampled tick/operator/device/cluster spans,
  ring-buffered for ``/trace?since=`` and appended to a rotating OTLP-JSON
  file (``PATHWAY_TRACE=on``, ``PATHWAY_TRACE_SAMPLE``,
  ``PATHWAY_TRACE_LIVE_FILE``); also on, ring only, for exactly the ticks of
  a JAX profiler session (:func:`tick_tracer`);
- ``metrics``  — per-input watermarks, per-sink end-to-end latency histograms
  (log-2 buckets → Prometheus histograms on ``/metrics``), backlog gauges;
- ``aggregate``— peers ship summaries to the coordinator on the heartbeat
  plane; process 0's ``/status`` shows every process.

Lifecycle: each runtime ``run()`` calls :func:`install_from_env` (next to the
fault-plan install) and :func:`shutdown` in its run wrapper; ``current()`` is
the hot-path accessor — **None when tracing is off**, so engine loops pay one
``is None`` test. A tracer that records also has its ``on_gc`` in
``gc.callbacks`` (the collector's passes), for as long as it is installed and
no longer. The last recording's ring stays readable after the run
(:func:`last_recording`).
"""

from __future__ import annotations

import gc
import secrets

from pathway_tpu.observability import (
    aggregate,
    alerts,
    audit,
    bottleneck,
    device,
    engine_phases,
    health,
    lineage,
    metrics,
    requests,
    spans,
    timeline,
)
from pathway_tpu.observability.metrics import (
    BUCKET_BOUNDS_S,
    Histogram,
    backlog_gauges,
    input_watermarks,
    run_metrics,
)
from pathway_tpu.observability.spans import (
    RotatingTraceSink,
    SpanBuffer,
    Tracer,
    derive_trace_id,
)

_tracer: Tracer | None = None
#: the ring of the last tracer retired, readable until the next one retires
_last_buffer: SpanBuffer | None = None


def current() -> Tracer | None:
    """The installed live tracer, or None when tracing is off."""
    return _tracer


def last_recording() -> SpanBuffer | None:
    """The ring of the live tracer, else of the last one retired (the
    readers of a benchmark or a test run after ``pw.run()`` returns)."""
    return _tracer.buffer if _tracer is not None else _last_buffer


def begin(name: str) -> tuple | None:
    """Open a span on the live tracer (None when off or the tick is
    unsampled): the one-line guard of the sites outside the engine loop."""
    t = _tracer
    return t.begin(name) if t is not None else None


def end(tok: tuple, attrs: dict | None = None) -> None:
    tok[0].end(tok, attrs)


def _bring_up(tracer: Tracer) -> Tracer:
    global _tracer
    _tracer = tracer
    gc.callbacks.append(tracer.on_gc)
    return tracer


def _retire(emit_root: bool) -> None:
    global _tracer, _last_buffer
    tracer, _tracer = _tracer, None
    if tracer is None:
        return
    if tracer.on_gc in gc.callbacks:
        gc.callbacks.remove(tracer.on_gc)
    _last_buffer = tracer.buffer
    try:
        tracer.close(emit_root=emit_root)
    except Exception:
        pass


def tick_tracer(tracer: Tracer | None) -> Tracer | None:
    """Once per tick, from every runtime's ``run_tick``: the tracer this tick
    records on. With ``PATHWAY_TRACE`` off this is the ONE place that brings
    a tracer up (ring only, no sink, every tick sampled) — at the first tick
    that finds a JAX profiler session — and retires it at the first tick that
    finds the session gone; without a session it costs one flag read."""
    on = spans.profiler_session_active()
    if tracer is None:
        if not on:
            return None
        if _tracer is None:
            from pathway_tpu.internals.config import get_pathway_config

            cfg = get_pathway_config()
            _bring_up(Tracer(
                trace_id=run_trace_id(),
                process_id=cfg.process_id,
                buffer=SpanBuffer(max_spans=cfg.trace_buffer_spans),
                session=True,
            ))
        tracer = _tracer
    elif tracer.session and not on:
        _retire(emit_root=False)
        return None
    tracer.annotate = on
    return tracer


def run_trace_id() -> str:
    """The trace id this run's spans carry: derived deterministically from
    ``PATHWAY_RUN_ID`` when set (cluster processes share it → one stitched
    trace), else random per process."""
    from pathway_tpu.internals.config import get_pathway_config

    run_id = get_pathway_config().run_id
    if run_id:
        return derive_trace_id(run_id)
    return secrets.token_hex(16)


def install_from_env(runtime=None) -> Tracer | None:
    """Install the run's live telemetry (called by every runtime's ``run``,
    next to ``faults.install_from_env``): reset the per-run metrics state,
    and build a tracer when ``PATHWAY_TRACE`` is on. Idempotent per run —
    a previous run's tracer is closed first."""
    from pathway_tpu.internals.config import get_pathway_config

    metrics.reset()
    spans.reanchor()
    # device profiling plane (compile/pad/memory accounting, flight recorder,
    # profiler windows) — on by default, independent of PATHWAY_TRACE
    device.install_from_env(runtime)
    # data-plane audit (invariant monitors, cardinality gauges, shadow audits,
    # row lineage) — on by default, independent of the other planes
    audit.install_from_env(runtime)
    # request-scoped tracing (per-request flight paths, tail-based sampling) —
    # on by default; off installs no plane, hot loops pay one is-None test
    requests.install_from_env(runtime)
    # host-side per-phase tick attribution (PATHWAY_ENGINE_PHASES=on):
    # consolidate/rehash/probe/realloc/kernel/exchange breakdown, read by
    # engine_bench — totals persist across runs until reset() so one bench
    # process can aggregate several pipelines
    engine_phases.install_from_env()
    # pod health & SLO plane (door state machine, canaries, burn-rate alerts,
    # incident bundles) — on by default; off installs nothing
    health.install_from_env(runtime)
    # pod timeline plane (tick-granularity history rings, segment spill,
    # bottleneck attribution) — on by default; off constructs no plane. After
    # health so the recorder can sample canary/alert state from step one.
    timeline.install_from_env(runtime)
    _retire(emit_root=False)
    cfg = get_pathway_config()
    if cfg.trace_mode == "off":
        return None
    sink = None
    path = cfg.trace_live_file
    if path:
        if cfg.processes > 1:
            path = f"{path}.p{cfg.process_id}"
        sink = RotatingTraceSink(path, rotate_bytes=cfg.trace_rotate_mb * 1024 * 1024)
    return _bring_up(Tracer(
        trace_id=run_trace_id(),
        process_id=cfg.process_id,
        sample=cfg.trace_sample,
        buffer=SpanBuffer(max_spans=cfg.trace_buffer_spans, sink=sink),
    ))


def shutdown() -> None:
    """Close the live tracer (flush + root span + file sink; its ring stays
    readable). Never raises — runs in ``finally`` blocks next to
    connector/server teardown."""
    timeline.shutdown()
    health.shutdown()
    device.shutdown()
    audit.shutdown()
    requests.shutdown()
    _retire(emit_root=True)


__all__ = [
    "BUCKET_BOUNDS_S",
    "Histogram",
    "RotatingTraceSink",
    "SpanBuffer",
    "Tracer",
    "aggregate",
    "alerts",
    "audit",
    "backlog_gauges",
    "begin",
    "bottleneck",
    "current",
    "derive_trace_id",
    "device",
    "end",
    "engine_phases",
    "health",
    "lineage",
    "input_watermarks",
    "install_from_env",
    "last_recording",
    "metrics",
    "requests",
    "run_metrics",
    "run_trace_id",
    "shutdown",
    "spans",
    "tick_tracer",
    "timeline",
]
