"""Device-side profiling & cost-attribution plane.

The r8 observability plane made the *host* side of the dataflow legible
(spans, watermarks, sink-latency histograms); this module lights up the
*device* side — the compile stalls and padding waste that are the dominant
silent tax of the microbatch/bucket discipline (SURVEY §3.4, §7.1.5), and the
per-step compile/memory telemetry MegaScale-style production systems treat as
table stakes. Four pillars, on by default (``PATHWAY_PROFILE=on``) at
negligible cost:

- **compile telemetry** — :func:`traced_jit` wraps every jit entry point
  (encoder/reranker/knn/engine kernels/device exchange) and counts calls,
  cold-shape calls (first sight of an argument shape set = a fresh XLA
  compile-cache entry on this process) and their wall time; where
  ``jax.monitoring`` is available a process-wide listener attributes the
  PRECISE ``backend_compile`` durations to the dispatching callable. A
  recompile-storm detector flags callables whose shape set keeps growing
  (``PATHWAY_PROFILE_SHAPE_WARN``) on ``/status``.
- **padding & waste accounting** — the microbatch dispatcher and the
  encoder/reranker length-bucketing report real vs padded rows and tokens per
  UDF (``pathway_pad_rows_total{kind=real|pad}``, waste-ratio gauges). FLOPs
  and peaks are the benchmark's to count (``chipbench/flops.py``).
- **memory + time attribution** — components (KNN index shards, encoder /
  reranker params, microbatch buffers) register weakly and are summed into
  ``pathway_device_bytes{component=...}``; ``jax`` backend memory stats ride
  along when the platform exposes them (TPU/GPU — CPU returns none and the
  gauge degrades gracefully). Tracing adds no device sync: while a tracer
  records, every traced call is a ``device/launch`` span (label, cold) — the
  host's cost to hand the arguments to the executable — and :func:`put` and
  :func:`fetch` are the host↔device crossings of the kernels' call sites, each
  a ``device/put`` / ``device/fetch`` span carrying its bytes; the device's
  own time is the device trace's, joined on the clock.
- **flight recorder** — bounded rings of recent ticks and device events
  (compiles, storms, launches, faults), dumped as a post-mortem JSON to
  ``PATHWAY_FLIGHT_DIR`` on ``terminate_on_error`` aborts,
  ``OtherWorkerError`` and supervised restarts. ``PATHWAY_PROFILE_DIR``
  additionally captures a ``jax.profiler`` trace for the first
  ``PATHWAY_PROFILE_TICKS`` ticks; further windows are triggerable live via
  ``/profile?ticks=N`` or ``pathway_tpu profile``.

Graceful degradation is a hard requirement: every probe must no-op cleanly —
zero warnings, zero crashes — when ``jax.profiler`` / device memory stats /
``jax.monitoring`` are unavailable (CPU-only CI runs with
``JAX_PLATFORMS=cpu``).
"""

from __future__ import annotations

import json
import os
import threading
import time as _time
import weakref
from collections import deque
from typing import Any, Callable

import numpy as np

from pathway_tpu.internals.config import get_pathway_config

__all__ = [
    "DeviceStats",
    "flight_dump",
    "flight_note",
    "install_from_env",
    "fetch",
    "on_run_error",
    "put",
    "register_memory",
    "request_profile",
    "stats",
    "status_summary",
    "tick_hook",
    "traced_jit",
]


# --------------------------------------------------------------------- labels
# Thread-local label stack: the jax.monitoring compile listener attributes
# backend_compile durations to whichever traced callable (or microbatch UDF
# scope) is dispatching on this thread.

_tls = threading.local()


def push_label(label: str) -> None:
    stack = getattr(_tls, "labels", None)
    if stack is None:
        stack = _tls.labels = []
    stack.append(label)


def pop_label() -> None:
    stack = getattr(_tls, "labels", None)
    if stack:
        stack.pop()


def current_label() -> str | None:
    stack = getattr(_tls, "labels", None)
    return stack[-1] if stack else None


def thread_cold_s() -> float:
    """This thread's cumulative traced cold-call seconds — the microbatch
    dispatcher subtracts the delta across a launch so an inner traced jit's
    compile is not double-counted into the per-process compile-seconds."""
    return getattr(_tls, "cold_s", 0.0)


# ---------------------------------------------------------------- jax helpers

_jax = None  # resolved lazily; False = import failed (stay degraded forever)


def _jax_mod():
    global _jax
    if _jax is None:
        try:
            import jax as j

            _jax = j
        except Exception:  # pragma: no cover - jax is baked into the image
            _jax = False
    return _jax or None


def _block(out: Any) -> None:
    """Wait for device completion (a device failure raises here, in the
    launch that caused it)."""
    j = _jax_mod()
    if j is not None:
        j.block_until_ready(out)


_obs = None


def _observability():
    """``pathway_tpu.observability`` (which imports this module: resolved at
    the first call)."""
    global _obs
    if _obs is None:
        from pathway_tpu import observability

        _obs = observability
    return _obs


_listener_registered = False


def _ensure_listener() -> None:
    """Register the process-wide ``jax.monitoring`` compile-duration listener
    once. Listeners cannot be individually unregistered, so the callback reads
    the CURRENT stats singleton at fire time."""
    global _listener_registered
    if _listener_registered:
        return
    _listener_registered = True  # one attempt, even on failure
    j = _jax_mod()
    if j is None:
        return
    try:
        from jax import monitoring as _mon

        def _on_duration(key: str, dur: float, **kw: Any) -> None:
            if "backend_compile" not in key:
                return
            st = _stats
            label = current_label() or "(unattributed)"
            with st.lock:
                ent = st.compiles.setdefault(label, [0, 0.0])
                ent[0] += 1
                ent[1] += dur
            st.listener_active = True
            flight_note("compile", callable=label, seconds=round(dur, 4))

        _mon.register_event_duration_secs_listener(_on_duration)
    except Exception:
        pass


# ----------------------------------------------------------------- core state


class DeviceStats:
    """Per-process device profiling state.

    Compile/shape tracking is process-cumulative (the XLA compile cache it
    mirrors is, too); pad accounting resets per run via :meth:`reset_run` so
    ``/metrics`` describes the current run.
    """

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.mode = "on"
        self.enabled = True
        self.shape_warn = 12
        # label -> [compiles, compile_seconds] from the jax.monitoring
        # listener (process-cumulative; falls back to cold-call counts when
        # the listener never fired)
        self.compiles: dict[str, list] = {}
        self.listener_active = False
        # microbatch-dispatcher scope: label -> [cold_calls, cold_s, {buckets}]
        self.dispatch: dict[str, list] = {}
        self._seen_shapes: set = set()
        #: per-process cumulative compile-seconds (cold-call wall time of
        #: dispatcher launches + traced jits; the ISSUE-5 satellite counter)
        self.process_compile_s = 0.0
        self.reset_run()

    # -- run lifecycle -------------------------------------------------------
    def reconfigure(self, cfg) -> None:
        try:
            self.mode = cfg.profile
        except ValueError:
            self.mode = "on"
        self.enabled = self.mode != "off"
        self.shape_warn = cfg.profile_shape_warn

    def reset_run(self) -> None:
        with self.lock:
            # label -> [real_rows, pad_rows, real_tokens, pad_tokens]
            self.pad: dict[str, list] = {}

    # -- compile / shape telemetry -------------------------------------------
    def first_shape(self, label: str, bucket: Any) -> bool:
        """True exactly once per (label, shape) on this process — the dispatch
        that populates a fresh XLA compile-cache entry."""
        key = (label, bucket)
        if key in self._seen_shapes:
            return False
        self._seen_shapes.add(key)
        return True

    def note_cold(
        self, label: str, wall_s: float, bucket: Any = None, inner_s: float = 0.0
    ) -> None:
        """One cold (first-shape) dispatcher launch. ``inner_s`` is the cold
        wall time already booked by traced jits INSIDE the launch (the
        dispatcher's wall contains their compiles) — subtracted so the
        per-process compile-seconds counter counts each compile once."""
        own_s = max(0.0, wall_s - inner_s)
        with self.lock:
            ent = self.dispatch.setdefault(label, [0, 0.0, set()])
            ent[0] += 1
            ent[1] += wall_s
            if bucket is not None:
                ent[2].add(bucket)
            self.process_compile_s += own_s
        if bucket is not None and len(self.dispatch[label][2]) == self.shape_warn:
            flight_note("recompile_storm", callable=label, shapes=self.shape_warn)
            _storm_alert(label, self.shape_warn)

    # -- padding --------------------------------------------------------------
    def note_pad_rows(self, label: str, real: int, pad: int) -> None:
        with self.lock:
            ent = self.pad.setdefault(label, [0, 0, 0, 0])
            ent[0] += real
            ent[1] += pad

    def note_pad_tokens(self, label: str, real: int, pad: int) -> None:
        with self.lock:
            ent = self.pad.setdefault(label, [0, 0, 0, 0])
            ent[2] += real
            ent[3] += pad


_stats = DeviceStats()


def stats() -> DeviceStats:
    return _stats


def put(x: Any, label: str, dtype: Any = None):
    """``jnp.asarray(x, dtype)``: a host array crossing to the device — a
    ``device/put`` span with its bytes while a tracer records."""
    tok = _observability().begin("device/put")
    out = _jax_mod().numpy.asarray(x, dtype)
    if tok is not None:
        tok[0].end(tok, {"pathway.label": label, "pathway.bytes": int(out.nbytes)})
    return out


def fetch(x: Any, label: str):
    """``np.asarray(x)``: the host blocked until the device has the value,
    then the copy down — a ``device/fetch`` span with its bytes while a tracer
    records. Its duration is the sync; counting these spans counts syncs."""
    tok = _observability().begin("device/fetch")
    out = np.asarray(x)
    if tok is not None:
        tok[0].end(tok, {"pathway.label": label, "pathway.bytes": int(out.nbytes)})
    return out


# ------------------------------------------------------------------ traced_jit


class _TracedJit:
    """Wrapper around a jitted callable: shape-set / cold-call accounting on
    every call, and a ``device/launch`` span around the call while a tracer
    records. Off mode costs one attribute read + ``is``-test."""

    def __init__(self, label: str, fn: Callable):
        self.label = label
        self.fn = fn
        self.__name__ = getattr(fn, "__name__", label)
        self._seen: set = set()
        # guards the cold-shape decision only — the warm path stays lock-free
        # (set membership reads are safe under the GIL; the counters are
        # monitoring-grade and tolerate lost increments)
        self._cold_lock = threading.Lock()
        self.calls = 0
        self.cold_calls = 0
        self.cold_s = 0.0
        self.storm = False
        _wrappers.add(self)

    # shape signature: positional args' array shapes/dtypes, hashable
    # non-arrays verbatim, containers structurally. Params dicts contribute
    # their leaf count only — their leaf shapes are fixed per model object and
    # walking a full pytree per call is not negligible.
    @staticmethod
    def _sig(x: Any) -> Any:
        shape = getattr(x, "shape", None)
        if shape is not None:
            return (tuple(shape), str(getattr(x, "dtype", "")))
        if isinstance(x, dict):
            return ("dict", len(x))
        if isinstance(x, (list, tuple)):
            return tuple(_TracedJit._sig(v) for v in x)
        try:
            hash(x)
        except TypeError:
            return type(x).__name__
        return x

    def shape_key(self, args: tuple, kwargs: dict) -> tuple:
        key = tuple(self._sig(a) for a in args)
        if kwargs:
            key += tuple((k, self._sig(v)) for k, v in sorted(kwargs.items()))
        return key

    def __call__(self, *args: Any, **kwargs: Any):
        st = _stats
        if not st.enabled:
            return self.fn(*args, **kwargs)
        self.calls += 1
        key = self.shape_key(args, kwargs)
        cold = key not in self._seen
        if cold:
            # double-checked under the lock: two worker threads racing the
            # same fresh shape must measure (and count) the compile once
            with self._cold_lock:
                cold = key not in self._seen
                if cold:
                    self._seen.add(key)
        push_label(self.label)
        try:
            if cold:
                _ensure_listener()
                t0 = _time.perf_counter()
                out = self._launch(args, kwargs, True)
                _block(out)
                dt = _time.perf_counter() - t0
                self.cold_calls += 1
                self.cold_s += dt
                _tls.cold_s = getattr(_tls, "cold_s", 0.0) + dt
                with st.lock:
                    st.process_compile_s += dt
                if len(self._seen) >= st.shape_warn and not self.storm:
                    self.storm = True
                    flight_note(
                        "recompile_storm",
                        callable=self.label,
                        shapes=len(self._seen),
                    )
                    _storm_alert(self.label, len(self._seen))
                return out
            return self._launch(args, kwargs, False)
        except Exception as e:
            from pathway_tpu.internals.errors import mark_device_error

            mark_device_error(e, self.label)
            raise
        finally:
            pop_label()

    def _launch(self, args: tuple, kwargs: dict, cold: bool):
        """The call into the executable: a ``device/launch`` span (label,
        cold) while a tracer records — the host's hand-over of the
        arguments, a compile on a cold call, the enqueue; never a wait for
        the device."""
        tok = _observability().begin("device/launch")
        out = self.fn(*args, **kwargs)
        if tok is not None:
            tok[0].end(tok, {"pathway.label": self.label, "pathway.cold": cold})
        return out


_wrappers: "weakref.WeakSet[_TracedJit]" = weakref.WeakSet()


def traced_jit(label: str, fn: Callable) -> Callable:
    """Wrap an (already-jitted) callable with compile/shape telemetry. Every
    device kernel is built through here, so this is also where the compile
    cache is placed — before anything compiles."""
    from pathway_tpu.internals.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    return _TracedJit(label, fn)


# ------------------------------------------------------------- memory registry

# (component, weakref-to-owner, fn(owner) -> bytes); dead owners pruned on read
_memory_providers: list[tuple[str, "weakref.ref", Callable]] = []
_memory_lock = threading.Lock()


def register_memory(obj: Any, component: str, fn: Callable[[Any], int]) -> None:
    """Attribute ``obj``'s live device bytes to ``component`` while it lives
    (``pathway_device_bytes{component=...}``). Weakly referenced — no
    lifetime coupling, and unregistration is implicit."""
    try:
        ref = weakref.ref(obj)
    except TypeError:
        return
    with _memory_lock:
        if len(_memory_providers) > 4096:
            _memory_providers[:] = [
                (c, r, f) for c, r, f in _memory_providers if r() is not None
            ]
        _memory_providers.append((component, ref, fn))


def index_tier_stats() -> dict | None:
    """Aggregate tiered-index stats, or None when no tiered backend lives (or
    the indexing stack can't import on this image). The ONE guarded accessor
    behind both the /metrics lines here and the /status block in
    ``internals.monitoring`` — keep the two surfaces from diverging."""
    try:
        from pathway_tpu.stdlib.indexing.tiered import tier_stats
    except ImportError:
        return None  # indexing stack absent on this image
    return tier_stats()


def memory_components() -> dict[str, int]:
    """component -> summed live bytes across registered owners."""
    out: dict[str, int] = {}
    with _memory_lock:
        providers = list(_memory_providers)
    live: list[tuple[str, "weakref.ref", Callable]] = []
    for component, ref, fn in providers:
        obj = ref()
        if obj is None:
            continue
        live.append((component, ref, fn))
        try:
            out[component] = out.get(component, 0) + int(fn(obj))
        except Exception:
            continue
    if len(live) != len(providers):
        with _memory_lock:
            _memory_providers[:] = live
    return out


def backend_memory() -> dict[str, int] | None:
    """Allocator stats from the jax backend (bytes in use / peak), or None
    where the platform exposes none (CPU)."""
    j = _jax_mod()
    if j is None:
        return None
    try:
        in_use = 0
        peak = 0
        limit = 0
        seen = False
        for d in j.local_devices():
            ms = getattr(d, "memory_stats", None)
            ms = ms() if callable(ms) else None
            if not ms:
                continue
            seen = True
            in_use += ms.get("bytes_in_use", 0)
            peak += ms.get("peak_bytes_in_use", 0)
            limit += ms.get("bytes_limit", 0)
        if not seen:
            return None
        out = {"bytes_in_use": in_use, "peak_bytes_in_use": peak}
        if limit:
            out["bytes_limit"] = limit
        return out
    except Exception:
        return None


# ------------------------------------------------------------- flight recorder


class FlightRecorder:
    """Bounded rings of recent ticks and device events for post-mortems."""

    def __init__(self, max_events: int = 1024):
        self._lock = threading.Lock()
        self.events: deque = deque(maxlen=max_events)
        self.ticks: deque = deque(maxlen=max(64, max_events // 4))

    def note(self, kind: str, **attrs: Any) -> None:
        rec = {"t_ns": _time.time_ns(), "kind": kind}
        if attrs:
            rec.update(attrs)
        with self._lock:
            self.events.append(rec)

    def note_tick(self, tick: int) -> None:
        with self._lock:
            self.ticks.append((tick, _time.time_ns()))

    def snapshot(self) -> dict[str, list]:
        with self._lock:
            return {
                "events": list(self.events),
                "ticks": [{"tick": t, "t_ns": ns} for t, ns in self.ticks],
            }


_recorder = FlightRecorder()


def flight_note(kind: str, **attrs: Any) -> None:
    rec = _recorder
    if rec is not None and _stats.enabled:
        rec.note(kind, **attrs)


def flight_snapshot() -> dict[str, list]:
    """The flight-recorder rings (recent device events + ticks) — read by the
    health plane's incident bundles and ``flight_dump``."""
    return _recorder.snapshot()


def _storm_alert(label: str, shapes: int) -> None:
    """r10's recompile-storm tripwire unified into the alert registry: the
    same condition that flags ``/status`` now fires through ``/alerts``,
    Prometheus and the notification sinks like every other detector. No-op
    when the health plane is off."""
    from pathway_tpu.observability import alerts as _alerts

    registry = _alerts.current()
    if registry is not None:
        registry.fire(
            "recompile_storm",
            fingerprint=label,
            severity="warn",
            summary=(
                f"callable {label!r} compiled {shapes} distinct shapes — "
                "bucketing is not closing the shape set"
            ),
            auto=False,
        )


def flight_dump(
    reason: str, error: BaseException | None = None, extra: dict | None = None
) -> str | None:
    """Write the post-mortem JSON to ``PATHWAY_FLIGHT_DIR`` (no-op when the
    knob is unset). Returns the file path, or None. Never raises."""
    try:
        cfg = get_pathway_config()
        out_dir = cfg.flight_dir
        if not out_dir:
            return None
        os.makedirs(out_dir, exist_ok=True)
        doc: dict[str, Any] = {
            "reason": reason,
            "process_id": cfg.process_id,
            "time_unix": round(_time.time(), 3),
            "extra": extra,
            "device": status_summary(None),
        }
        # request-trace plane: which user queries were mid-flight (and how
        # far each got) when this process died — the post-mortem names them
        try:
            from pathway_tpu.observability import requests as _requests

            rp = _requests.current()
            if rp is not None:
                doc["requests"] = rp.inflight_table()
        except Exception:
            pass
        if error is not None:
            doc["error"] = {
                "type": type(error).__name__,
                "message": str(error),
                # OtherWorkerError carries the failed peer + its last tick
                "process_id": getattr(error, "process_id", None),
                "tick": getattr(error, "tick", None),
                "peer_reason": getattr(error, "reason", None),
            }
        doc.update(_recorder.snapshot())
        path = os.path.join(
            out_dir, f"flight_p{cfg.process_id}_{_time.time_ns()}.json"
        )
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(doc, fh, default=str)
        return path
    except Exception:
        return None


def on_run_error(error: BaseException, runtime: Any = None) -> None:
    """Run-loop failure hook (``terminate_on_error`` aborts, dead-peer
    ``OtherWorkerError``): record the failure and write the post-mortem."""
    try:
        from pathway_tpu.internals.errors import OtherWorkerError

        is_peer = isinstance(error, OtherWorkerError)
    except Exception:
        is_peer = False
    flight_note(
        "run_error",
        error=type(error).__name__,
        message=str(error)[:500],
        peer=getattr(error, "process_id", None),
        tick=getattr(error, "tick", None),
    )
    flight_dump("other_worker_error" if is_peer else "run_error", error=error)


# ------------------------------------------------------- jax.profiler windows


class _ProfileWindow:
    __slots__ = ("path", "remaining", "active")

    def __init__(self, path: str, ticks: int):
        self.path = path
        self.remaining = max(1, int(ticks))
        self.active = False


_profile_window: _ProfileWindow | None = None
_profile_lock = threading.Lock()


def request_profile(ticks: int | None = None, path: str | None = None) -> dict:
    """Arm a ``jax.profiler`` capture window for the next N ticks (served by
    ``/profile?ticks=N`` and the ``pathway_tpu profile`` CLI)."""
    global _profile_window
    cfg = get_pathway_config()
    path = path or cfg.profile_dir
    if not path:
        return {
            "ok": False,
            "error": "no capture directory (set PATHWAY_PROFILE_DIR or pass dir=)",
        }
    if _jax_mod() is None or not hasattr(_jax_mod(), "profiler"):
        return {"ok": False, "error": "jax.profiler unavailable"}
    with _profile_lock:
        if _profile_window is not None:
            return {"ok": False, "error": "a capture window is already active"}
        _profile_window = _ProfileWindow(path, ticks or cfg.profile_ticks)
        return {"ok": True, "dir": path, "ticks": _profile_window.remaining}


def _profile_state() -> dict | None:
    w = _profile_window
    if w is None:
        return None
    return {"dir": w.path, "ticks_remaining": w.remaining, "active": w.active}


def _step_profile(tick: int) -> None:
    global _profile_window
    with _profile_lock:
        w = _profile_window
        if w is None:
            return
        j = _jax_mod()
        if j is None:
            _profile_window = None
            return
        if not w.active:
            try:
                os.makedirs(w.path, exist_ok=True)
                j.profiler.start_trace(w.path)
                w.active = True
                flight_note("profile_start", dir=w.path, tick=tick, ticks=w.remaining)
            except Exception:
                _profile_window = None
                return
        w.remaining -= 1
        if w.remaining <= 0:
            _stop_profile_locked(tick)


def _stop_profile_locked(tick: int | None = None) -> None:
    global _profile_window
    w = _profile_window
    _profile_window = None
    if w is None or not w.active:
        return
    j = _jax_mod()
    try:
        if j is not None:
            j.profiler.stop_trace()
        flight_note("profile_stop", dir=w.path, tick=tick)
    except Exception:
        pass


def tick_hook(tick: int) -> None:
    """Once per engine tick from every runtime's run loop: steps an armed
    profiler window and stamps the flight recorder's tick ring. Off mode is
    two global reads."""
    if _profile_window is not None:
        _step_profile(tick)
    st = _stats
    if st.enabled:
        _recorder.note_tick(tick)


# ------------------------------------------------------------- run lifecycle


def install_from_env(runtime: Any = None) -> None:
    """Per-run (re)initialization, called from ``observability.
    install_from_env`` next to the tracer/fault installs."""
    global _recorder
    cfg = get_pathway_config()
    _stats.reconfigure(cfg)
    _stats.reset_run()
    _recorder = FlightRecorder(cfg.flight_events)
    if not _stats.enabled:
        return
    _ensure_listener()
    if cfg.profile_dir:
        request_profile(cfg.profile_ticks, cfg.profile_dir)


def shutdown() -> None:
    """Run teardown: close any live profiler capture. Never raises."""
    try:
        with _profile_lock:
            _stop_profile_locked()
    except Exception:
        pass


# ------------------------------------------------------------------ summaries


def _callables_view() -> dict[str, dict]:
    st = _stats
    # label -> [calls, cold_calls, cold_s, shapes] — SUMMED across wrappers
    # sharing a label (e.g. the two device-exchange jit variants): each
    # wrapper owns its own jit cache, so shape-set sizes add, and summing
    # keeps the exported counters monotonic regardless of WeakSet iteration
    # order
    acc: dict[str, list] = {}
    for w in list(_wrappers):
        if not w.calls and not w.cold_calls:
            continue  # registered but never dispatched — noise on /status
        ent = acc.setdefault(w.label, [0, 0, 0.0, 0, False])
        ent[0] += w.calls
        ent[1] += w.cold_calls
        ent[2] += w.cold_s
        ent[3] += len(w._seen)
        ent[4] = ent[4] or w.storm
    with st.lock:
        compiles = {k: list(v) for k, v in st.compiles.items()}
        dispatch = {k: (v[0], v[1], len(v[2])) for k, v in st.dispatch.items()}
        listener = st.listener_active
    out: dict[str, dict] = {}

    def _compiled(label: str, cold: int, cold_s: float) -> tuple[int, float]:
        c = compiles.get(label)
        if c:
            return c[0], c[1]
        if listener:
            # the listener is live and never fired for this label: its calls
            # genuinely compiled nothing themselves (e.g. a pure-Python UDF
            # whose inner traced jit got the attribution) — don't re-count
            # the cold wall as compiles
            return 0, 0.0
        return cold, cold_s  # no jax.monitoring: cold calls are the proxy

    for label, (calls, cold, cold_s, shapes, storm) in acc.items():
        n, s = _compiled(label, cold, cold_s)
        out[label] = {
            "calls": calls,
            "cold_calls": cold,
            "cold_s": round(cold_s, 4),
            "compiles": n,
            "compile_s": round(s, 4),
            "shapes": shapes,
            "storm": storm or shapes >= st.shape_warn,
        }
    for label, (cold, cold_s, shapes) in dispatch.items():
        n, s = _compiled(label, cold, cold_s)
        out[label] = {
            "calls": None,
            "cold_calls": cold,
            "cold_s": round(cold_s, 4),
            "compiles": n,
            "compile_s": round(s, 4),
            "shapes": shapes,
            "storm": shapes >= st.shape_warn,
        }
    return dict(sorted(out.items()))


def _pad_view() -> dict[str, dict]:
    st = _stats
    out: dict[str, dict] = {}
    with st.lock:
        items = [(k, list(v)) for k, v in st.pad.items()]
    for label, (rr, pr, rt, pt) in sorted(items):
        row = {"real_rows": rr, "pad_rows": pr}
        if rr + pr:
            row["row_waste_ratio"] = round(pr / (rr + pr), 4)
        if rt + pt:
            row["real_tokens"] = rt
            row["pad_tokens"] = pt
            row["token_waste_ratio"] = round(pt / (rt + pt), 4)
        out[label] = row
    return out


def _microbatch_buffer_bytes(runtime: Any) -> int:
    """Rough live bytes held in cross-tick microbatch buffers (status-time
    walk; array cells report nbytes, scalars a nominal 8)."""
    if runtime is None:
        return 0
    from pathway_tpu.observability.metrics import iter_graphs

    total = 0
    try:
        for g in iter_graphs(getattr(runtime, "scheduler", None)):
            for node in g.nodes:
                if node.name != "microbatch_select":
                    continue
                for entry in list(getattr(node, "waiting", {}).values()):
                    for cell in entry[3]:
                        if cell[0] != "args":
                            continue
                        for v in cell[1]:
                            total += getattr(v, "nbytes", 8)
    except Exception:
        return total
    return total


def status_summary(runtime: Any = None) -> dict[str, Any]:
    """The ``/status`` ``device`` section (also embedded in flight dumps)."""
    st = _stats
    if not st.enabled:
        return {"enabled": False, "mode": "off"}
    callables = _callables_view()
    with st.lock:
        compile_s = st.process_compile_s
    mem = memory_components()
    mb = _microbatch_buffer_bytes(runtime)
    if mb:
        mem["microbatch_buffers"] = mem.get("microbatch_buffers", 0) + mb
    out: dict[str, Any] = {
        "enabled": True,
        "mode": st.mode,
        "process_compile_s": round(compile_s, 4),
        "callables": callables,
        "pad": _pad_view(),
        "memory": {"components": mem, "backend": backend_memory()},
        "profiler": _profile_state(),
        "flight": {
            "events": len(_recorder.events),
            "dir": get_pathway_config().flight_dir,
        },
    }
    storms = [label for label, c in callables.items() if c["storm"]]
    if storms:
        out["warnings"] = [
            f"recompile storm: {label} has {callables[label]['shapes']} compiled "
            f"shapes (>= PATHWAY_PROFILE_SHAPE_WARN={st.shape_warn}) — "
            "unbucketed input shapes defeat the compile cache"
            for label in storms
        ]
    return out


def heartbeat_summary() -> dict[str, Any] | None:
    """Compact device block riding cluster heartbeats (peer → coordinator)."""
    st = _stats
    if not st.enabled:
        return None
    callables = _callables_view()
    with st.lock:
        pad = [sum(v[0] for v in st.pad.values()), sum(v[1] for v in st.pad.values())]
        compile_s = st.process_compile_s
    return {
        "compiles": sum(c["compiles"] for c in callables.values()),
        "compile_s": round(
            sum(c["compile_s"] for c in callables.values()), 4
        ),
        "process_compile_s": round(compile_s, 4),
        "shapes_max": max((c["shapes"] for c in callables.values()), default=0),
        "storm": any(c["storm"] for c in callables.values()),
        "pad_rows": pad,
        "device_bytes": sum(memory_components().values()),
    }


def merge_heartbeat_summaries(blocks: list[dict]) -> dict[str, Any] | None:
    """Cluster rollup of peers' heartbeat device blocks (coordinator
    ``/status``)."""
    blocks = [b for b in blocks if b]
    if not blocks:
        return None
    return {
        "compiles": sum(b.get("compiles") or 0 for b in blocks),
        "compile_s": round(sum(b.get("compile_s") or 0.0 for b in blocks), 4),
        "shapes_max": max(b.get("shapes_max") or 0 for b in blocks),
        "storm": any(b.get("storm") for b in blocks),
        "pad_rows": [
            sum((b.get("pad_rows") or [0, 0])[0] for b in blocks),
            sum((b.get("pad_rows") or [0, 0])[1] for b in blocks),
        ],
        "device_bytes": sum(b.get("device_bytes") or 0 for b in blocks),
    }


# ------------------------------------------------------------------ /metrics


def prometheus_lines(runtime: Any = None) -> list[str]:
    """Device-plane Prometheus exposition lines (appended by
    ``internals.monitoring.prometheus_text``)."""
    st = _stats
    if not st.enabled:
        return []
    from pathway_tpu.internals.monitoring import escape_label_value as esc

    lines: list[str] = []
    callables = _callables_view()
    if callables:
        lines.append("# HELP pathway_jit_compiles_total XLA compiles per traced callable")
        lines.append("# TYPE pathway_jit_compiles_total counter")
        for label, c in callables.items():
            lines.append(
                f'pathway_jit_compiles_total{{callable="{esc(label)}"}} {c["compiles"]}'
            )
        lines.append("# HELP pathway_jit_compile_seconds_total Compile seconds per traced callable")
        lines.append("# TYPE pathway_jit_compile_seconds_total counter")
        for label, c in callables.items():
            lines.append(
                f'pathway_jit_compile_seconds_total{{callable="{esc(label)}"}} {c["compile_s"]}'
            )
        lines.append("# HELP pathway_jit_shape_set_size Compile-cache shape-set cardinality per traced callable")
        lines.append("# TYPE pathway_jit_shape_set_size gauge")
        for label, c in callables.items():
            lines.append(
                f'pathway_jit_shape_set_size{{callable="{esc(label)}"}} {c["shapes"]}'
            )
    pad = _pad_view()
    if pad:
        lines.append("# HELP pathway_pad_rows_total Real vs padding rows launched per UDF")
        lines.append("# TYPE pathway_pad_rows_total counter")
        for label, row in pad.items():
            lines.append(
                f'pathway_pad_rows_total{{udf="{esc(label)}",kind="real"}} {row["real_rows"]}'
            )
            lines.append(
                f'pathway_pad_rows_total{{udf="{esc(label)}",kind="pad"}} {row["pad_rows"]}'
            )
        tok = {k: v for k, v in pad.items() if "real_tokens" in v}
        if tok:
            lines.append("# HELP pathway_pad_tokens_total Real vs padding tokens launched per UDF")
            lines.append("# TYPE pathway_pad_tokens_total counter")
            for label, row in tok.items():
                lines.append(
                    f'pathway_pad_tokens_total{{udf="{esc(label)}",kind="real"}} {row["real_tokens"]}'
                )
                lines.append(
                    f'pathway_pad_tokens_total{{udf="{esc(label)}",kind="pad"}} {row["pad_tokens"]}'
                )
        lines.append("# HELP pathway_pad_waste_ratio Fraction of launched rows that were padding")
        lines.append("# TYPE pathway_pad_waste_ratio gauge")
        for label, row in pad.items():
            ratio = row.get("row_waste_ratio")
            if ratio is not None:
                lines.append(
                    f'pathway_pad_waste_ratio{{udf="{esc(label)}"}} {ratio}'
                )
    mem = memory_components()
    mb = _microbatch_buffer_bytes(runtime)
    if mb:
        mem["microbatch_buffers"] = mem.get("microbatch_buffers", 0) + mb
    backend = backend_memory()
    if backend:
        for k, v in backend.items():
            mem[f"backend.{k}"] = v
    # family header always present (a scrape with no live components is a
    # valid empty family, not a missing metric)
    lines.append("# HELP pathway_device_bytes Live device bytes attributed per component")
    lines.append("# TYPE pathway_device_bytes gauge")
    for component, n in sorted(mem.items()):
        lines.append(
            f'pathway_device_bytes{{component="{esc(component)}"}} {n}'
        )
    # ---- tiered-index plane (hot HBM shard over host IVF cold tier) ---------
    # hot/cold device bytes already ride pathway_device_bytes via the
    # knn_hot/knn_cold memory components; these add serving-quality gauges
    ts = index_tier_stats()
    if ts is not None:
        lines.append("# HELP pathway_index_hot_hit_ratio Fraction of emitted KNN hits served from the HBM hot shard")
        lines.append("# TYPE pathway_index_hot_hit_ratio gauge")
        lines.append(f"pathway_index_hot_hit_ratio {ts['hot_hit_ratio'] or 0.0}")
        lines.append("# HELP pathway_index_promotions_total Rows promoted cold->hot by the tiered-index maintenance pass")
        lines.append("# TYPE pathway_index_promotions_total counter")
        lines.append(f"pathway_index_promotions_total {ts['promotions_total']}")
        lines.append("# HELP pathway_index_demotions_total Rows demoted hot->cold by the tiered-index maintenance pass")
        lines.append("# TYPE pathway_index_demotions_total counter")
        lines.append(f"pathway_index_demotions_total {ts['demotions_total']}")
        lines.append("# HELP pathway_index_tier_rows Resident rows per tier of the tiered KNN index")
        lines.append("# TYPE pathway_index_tier_rows gauge")
        lines.append(f'pathway_index_tier_rows{{tier="hot"}} {ts["hot_rows"]}')
        lines.append(f'pathway_index_tier_rows{{tier="cold"}} {ts["cold_rows"]}')
    return lines
