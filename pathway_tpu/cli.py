"""``pathway_tpu`` command line — spawn / spawn-from-env / replay.

Role of the reference CLI (``python/pathway/cli.py:53-113,167,253``): ``spawn``
forks N processes of a user program with the ``PATHWAY_THREADS / PATHWAY_PROCESSES /
PATHWAY_PROCESS_ID / PATHWAY_FIRST_PORT`` env contract consumed by
``parallel/cluster.py``; ``replay`` re-runs a program against a recorded
persistence log. Usage::

    python -m pathway_tpu spawn --threads 2 --processes 2 python script.py
    python -m pathway_tpu replay --record-path ./rec --mode speedrun python script.py
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys

import click

from pathway_tpu.internals.chips import child_chip_env
from pathway_tpu.internals.config import get_pathway_config


def _spawn_processes(env_base: dict[str, str], processes: int, args: tuple[str, ...]) -> int:
    """Fork one subprocess per process id, each with a chip of its own;
    forward SIGINT/SIGTERM; return the first non-zero exit code (killing the
    rest), else 0."""
    if not args:
        raise click.UsageError("no program given (e.g. `spawn -t 2 python script.py`)")
    try:
        envs = [
            dict(
                env_base,
                PATHWAY_PROCESS_ID=str(pid),
                **child_chip_env(env_base, pid, processes),
            )
            for pid in range(processes)
        ]
    except ValueError as e:  # more processes than chips: nothing spawned yet
        raise click.UsageError(str(e)) from e
    procs = [subprocess.Popen(list(args), env=env) for env in envs]

    def forward(signum, frame):
        for p in procs:
            if p.poll() is None:
                p.send_signal(signum)

    old_int = signal.signal(signal.SIGINT, forward)
    old_term = signal.signal(signal.SIGTERM, forward)
    try:
        code = 0
        for p in procs:
            rc = p.wait()
            if rc != 0 and code == 0:
                code = rc
                for q in procs:
                    if q.poll() is None:
                        q.terminate()
        return code
    finally:
        signal.signal(signal.SIGINT, old_int)
        signal.signal(signal.SIGTERM, old_term)


@click.group()
def cli() -> None:
    """pathway_tpu — TPU-native streaming dataflow framework."""


@cli.command(context_settings={"ignore_unknown_options": True})
@click.option("-t", "--threads", type=int, default=1, help="workers per process")
@click.option("-n", "--processes", type=int, default=1, help="number of processes")
@click.option("--first-port", type=int, default=None, help="base TCP port for the cluster plane")
@click.option("--record", is_flag=True, default=False, help="record inputs for later replay")
@click.option("--record-path", type=str, default="./record", help="where recorded inputs live")
@click.option(
    "--trace",
    type=click.Choice(["off", "on"]),
    default=None,
    help="live span pipeline (PATHWAY_TRACE)",
)
@click.option(
    "--trace-sample", type=float, default=None, help="tick head-sampling rate (0, 1]"
)
@click.option(
    "--trace-file",
    type=str,
    default=None,
    help="rotating OTLP-JSON live sink (per-process .pN suffix)",
)
@click.argument("program", nargs=-1, type=click.UNPROCESSED)
def spawn(threads, processes, first_port, record, record_path, trace, trace_sample, trace_file, program):
    """Run PROGRAM across THREADS×PROCESSES workers on this host."""
    import uuid

    env = dict(os.environ)
    env["PATHWAY_THREADS"] = str(threads)
    env["PATHWAY_PROCESSES"] = str(processes)
    env["PATHWAY_FIRST_PORT"] = str(
        first_port if first_port is not None else get_pathway_config().first_port
    )
    # one run id per launch: every process derives the SAME trace id from it,
    # so per-process tick spans (live + offline exports) stitch into one trace
    env.setdefault("PATHWAY_RUN_ID", uuid.uuid4().hex)
    if trace is not None:
        env["PATHWAY_TRACE"] = trace
    if trace_sample is not None:
        env["PATHWAY_TRACE_SAMPLE"] = str(trace_sample)
    if trace_file is not None:
        env["PATHWAY_TRACE_LIVE_FILE"] = trace_file
    if record:
        env["PATHWAY_PERSISTENT_STORAGE"] = record_path
        env["PATHWAY_RECORD"] = "1"
    sys.exit(_spawn_processes(env, processes, program))


@cli.command(context_settings={"ignore_unknown_options": True})
@click.argument("program", nargs=-1, type=click.UNPROCESSED)
def spawn_from_env(program):
    """Like spawn, but topology comes from the current PATHWAY_* environment."""
    import uuid

    cfg = get_pathway_config()
    env = cfg.spawn_env(0)
    env.setdefault("PATHWAY_RUN_ID", uuid.uuid4().hex)  # shared trace id
    sys.exit(_spawn_processes(env, cfg.processes, program))


@cli.command(context_settings={"ignore_unknown_options": True})
@click.option("-t", "--threads", type=int, default=None, help="workers per process")
@click.option("-n", "--processes", type=int, default=None, help="number of processes")
@click.option("--first-port", type=int, default=None, help="base TCP port for the cluster plane")
@click.option("--max-restarts", type=int, default=None, help="restart budget on failure")
@click.option("--backoff", type=float, default=None, help="base restart backoff seconds")
@click.option("--log-dir", type=str, default=None, help="capture child output per attempt")
@click.argument("program", nargs=-1, type=click.UNPROCESSED)
def supervise(threads, processes, first_port, max_restarts, backoff, log_dir, program):
    """Run PROGRAM under the resilience Supervisor: spawn the cluster, detect
    a failed process, relaunch from the last committed checkpoint epoch with
    bounded exponential backoff (``resilience.Supervisor``)."""
    from pathway_tpu.resilience import Supervisor, SupervisorGaveUp

    if not program:
        raise click.UsageError("no program given (e.g. `supervise -n 2 python script.py`)")
    try:
        result = Supervisor(
            list(program),
            threads=threads,
            processes=processes,
            first_port=first_port,
            max_restarts=max_restarts,
            backoff_s=backoff,
            log_dir=log_dir,
        ).run()
    except SupervisorGaveUp as e:
        raise click.ClickException(str(e)) from e
    if result.restarts:
        click.echo(f"pathway_tpu supervise: recovered after {result.restarts} restart(s)")
    sys.exit(0)


@cli.command()
@click.option("--to", "target", type=int, required=True, help="target process count")
@click.option(
    "--storage",
    type=str,
    default=None,
    help="persistence root holding the cluster's membership table "
    "(default PATHWAY_PERSISTENT_STORAGE)",
)
@click.option("--host", type=str, default=None, help="instead of the storage path, hit a RUNNING coordinator's monitoring server")
@click.option(
    "--port",
    type=int,
    default=None,
    help="monitoring server port (default PATHWAY_MONITORING_HTTP_PORT, 20000)",
)
def scale(target, storage, host, port):
    """Request a live rescale of a running elastic cluster to TARGET
    processes (``PATHWAY_ELASTIC=manual`` or ``auto``). Default transport is
    the persistence backend: the request lands in ``elastic/scale_request``
    and the coordinator adopts it on its next tick-continuation barrier; the
    pod then quiesces to one final committed checkpoint epoch and its
    Supervisor relaunches it at the new shape, state resharded by key range.
    With ``--host``, the request goes to the coordinator's monitoring server
    ``/scale`` endpoint instead (no filesystem access needed)."""
    if target < 1:
        raise click.UsageError(f"--to must be >= 1, got {target}")
    if host is not None:
        import json as _json
        import urllib.request

        if port is None:
            port = int(os.environ.get("PATHWAY_MONITORING_HTTP_PORT", "20000"))
        url = f"http://{host}:{port}/scale?to={target}"
        try:
            body = urllib.request.urlopen(url, timeout=5).read().decode()
        except OSError as e:
            raise click.ClickException(
                f"cannot reach monitoring server at {host}:{port}: {e} "
                "(is the pipeline running with with_http_server=True?)"
            ) from e
        doc = _json.loads(body)
        click.echo(_json.dumps(doc, indent=2))
        if doc.get("ok") is False:
            raise click.ClickException(doc.get("error", "scale request failed"))
        return
    storage = storage or get_pathway_config().persistent_storage
    if not storage:
        raise click.UsageError(
            "no persistence root: pass --storage or set PATHWAY_PERSISTENT_STORAGE "
            "(or use --host to reach a running coordinator)"
        )
    from pathway_tpu.elastic import write_scale_request
    from pathway_tpu.persistence.backends import FileBackend

    req = write_scale_request(FileBackend(storage), target, source="cli")
    click.echo(
        f"scale request to {target} process(es) recorded at {storage!r} "
        f"(requested_unix {req['requested_unix']:.3f}); the coordinator "
        "adopts it within a tick"
    )


@cli.command()
@click.option("--host", type=str, default="127.0.0.1", help="monitoring server host")
@click.option(
    "--port",
    type=int,
    default=None,
    help="monitoring server port (default PATHWAY_MONITORING_HTTP_PORT, 20000)",
)
@click.option("--ticks", type=int, default=None, help="capture window length in ticks")
@click.option(
    "--dir",
    "out_dir",
    type=str,
    default=None,
    help="capture output directory (default PATHWAY_PROFILE_DIR of the target run)",
)
@click.option("--status", is_flag=True, default=False, help="report the current window instead of arming one")
def profile(host, port, ticks, out_dir, status):
    """Arm a live ``jax.profiler`` capture window on a RUNNING pipeline via
    its monitoring server's ``/profile`` endpoint (view the result in
    TensorBoard/XProf)."""
    import json as _json
    import urllib.parse
    import urllib.request

    if port is None:
        port = int(os.environ.get("PATHWAY_MONITORING_HTTP_PORT", "20000"))
    qs = {}
    if not status:
        qs["ticks"] = str(ticks if ticks is not None else get_pathway_config().profile_ticks)
        if out_dir:
            qs["dir"] = out_dir
    url = f"http://{host}:{port}/profile"
    if qs:
        url += "?" + urllib.parse.urlencode(qs)
    try:
        body = urllib.request.urlopen(url, timeout=5).read().decode()
    except OSError as e:
        raise click.ClickException(
            f"cannot reach monitoring server at {host}:{port}: {e} "
            "(is the pipeline running with with_http_server=True?)"
        ) from e
    doc = _json.loads(body)
    click.echo(_json.dumps(doc, indent=2))
    if doc.get("ok") is False:
        raise click.ClickException(doc.get("error", "profile request failed"))


@cli.command()
@click.option("--host", type=str, default="127.0.0.1", help="monitoring server host")
@click.option(
    "--port",
    type=int,
    default=None,
    help="monitoring server port (default PATHWAY_MONITORING_HTTP_PORT, 20000)",
)
@click.option("--sink", type=str, default=None, help="sink label, e.g. subscribe:7 (omit to list sinks)")
@click.option("--key", type=str, default=None, help="output row key (decimal or 0x-hex)")
def explain(host, port, sink, key):
    """Ask a RUNNING pipeline why a sink row exists: walk the operator graph
    backward through the lineage rings (``/explain`` endpoint) and print the
    contributing input rows, operator path, and originating trace span ids.
    Requires ``PATHWAY_AUDIT`` on (the default) and ``with_http_server=True``."""
    import json as _json
    import urllib.parse
    import urllib.request

    if port is None:
        port = int(os.environ.get("PATHWAY_MONITORING_HTTP_PORT", "20000"))
    qs = {}
    if sink:
        qs["sink"] = sink
    if key is not None:
        qs["key"] = key
    url = f"http://{host}:{port}/explain"
    if qs:
        url += "?" + urllib.parse.urlencode(qs)
    try:
        body = urllib.request.urlopen(url, timeout=5).read().decode()
    except OSError as e:
        raise click.ClickException(
            f"cannot reach monitoring server at {host}:{port}: {e} "
            "(is the pipeline running with with_http_server=True?)"
        ) from e
    doc = _json.loads(body)
    click.echo(_json.dumps(doc, indent=2))
    if doc.get("ok") is False:
        raise click.ClickException(doc.get("error", "explain request failed"))


@cli.command()
@click.argument("request_id", required=False)
@click.option("--host", type=str, default="127.0.0.1", help="monitoring server host")
@click.option(
    "--port",
    type=int,
    default=None,
    help="monitoring server port (default PATHWAY_MONITORING_HTTP_PORT, 20000)",
)
def trace(request_id, host, port):
    """Print one request's end-to-end flight path: per-stage latency
    decomposition + OTLP spans, from the request-trace plane's kept ring
    (``/request`` endpoint). Omit REQUEST_ID to list kept trace ids and the
    in-flight request table. The id is the ``X-Pathway-Request-Id`` response
    header the REST front door stamps on every admitted request."""
    import json as _json
    import urllib.parse
    import urllib.request

    if port is None:
        port = int(os.environ.get("PATHWAY_MONITORING_HTTP_PORT", "20000"))
    url = f"http://{host}:{port}/request"
    if request_id:
        url += "?" + urllib.parse.urlencode({"id": request_id})
    try:
        body = urllib.request.urlopen(url, timeout=5).read().decode()
    except OSError as e:
        raise click.ClickException(
            f"cannot reach monitoring server at {host}:{port}: {e} "
            "(is the pipeline running with with_http_server=True?)"
        ) from e
    doc = _json.loads(body)
    click.echo(_json.dumps(doc, indent=2))
    if doc.get("ok") is False:
        raise click.ClickException(doc.get("error", "trace request failed"))


def _fetch_json(host: str, port: int, path: str) -> dict:
    import json as _json
    import urllib.request

    url = f"http://{host}:{port}{path}"
    try:
        body = urllib.request.urlopen(url, timeout=5).read().decode()
    except OSError as e:
        raise click.ClickException(
            f"cannot reach monitoring server at {host}:{port}: {e} "
            "(is the pipeline running with with_http_server=True?)"
        ) from e
    return _json.loads(body)


def _spark(values: list, width: int = 24) -> str:
    """One-line unicode sparkline of a numeric series (newest right)."""
    blocks = "▁▂▃▄▅▆▇█"
    vals = [v for v in values if isinstance(v, (int, float))][-width:]
    if not vals:
        return "-" * 4
    lo, hi = min(vals), max(vals)
    span = (hi - lo) or 1.0
    return "".join(blocks[int((v - lo) / span * (len(blocks) - 1))] for v in vals)


def render_top(status: dict, tl: dict) -> str:
    """One ``pathway_tpu top`` frame from a /status + /timeline pair (pure —
    the live loop and the tests share it)."""

    def series(metric: str) -> list:
        return [p.get(metric) for p in tl.get("points") or () if p.get(metric) is not None]

    def last(metric: str, default=0):
        s = series(metric)
        return s[-1] if s else default

    lines = []
    procs = tl.get("procs") or []
    lines.append(
        f"pathway_tpu top — proc {tl.get('proc')} of {len(procs) or 1} "
        f"reporting ({', '.join(procs)})"
    )
    lines.append(
        f"  qps {last('serve_qps'):>8.1f} {_spark(series('serve_qps'))}   "
        f"tick_rate {last('tick_rate'):>7.1f} {_spark(series('tick_rate'))}"
    )
    lines.append(
        f"  backlog {last('backlog_rows'):>6} {_spark(series('backlog_rows'))}   "
        f"wm_lag_s {last('watermark_lag_s', 0.0):>7.2f} "
        f"{_spark(series('watermark_lag_s'))}"
    )
    lines.append(
        f"  pressure {last('flow_pressure', 0.0):>5.2f} "
        f"{_spark(series('flow_pressure'))}   "
        f"shed/s {last('serve_shed_per_s', 0.0):>6.1f}   "
        f"timeouts/s {last('serve_timeouts_per_s', 0.0):>5.1f}"
    )
    metrics = tl.get("metrics") or []
    stages = sorted(m for m in metrics if m.startswith("stage_p99_s:"))
    if stages:
        lines.append("  p99 by stage:")
        for m in stages[:8]:
            lines.append(
                f"    {m.split(':', 1)[1]:<28} {last(m, 0.0) * 1e3:>8.1f} ms "
                f"{_spark(series(m))}"
            )
    phases = sorted(m for m in metrics if m.startswith("phase_ms:"))
    if phases:
        split = ", ".join(
            f"{m.split(':', 1)[1]}={last(m, 0.0):.0f}ms" for m in phases[:8]
        )
        lines.append(f"  tick split: {split}")
    health = status.get("health") or {}
    doors = (health.get("doors") or {}) if isinstance(health, dict) else {}
    alerts = health.get("alerts") if isinstance(health, dict) else None
    active = (alerts or {}).get("active") if isinstance(alerts, dict) else None
    state = health.get("door") or health.get("state")
    lines.append(
        f"  doors: {doors or state or 'n/a'}   "
        f"alerts: {[a.get('alert') for a in active] if active else 'none'}"
    )
    top = (status.get("bottleneck") or {}).get("top")
    if top:
        lines.append(
            f"  bound by: {top.get('cause')} (score {top.get('score')}) — "
            f"{top.get('verdict')}"
        )
        lines.append(f"  knob: {top.get('knob')}")
    else:
        lines.append("  bound by: (no bottleneck — idle or warming up)")
    return "\n".join(lines)


@cli.command()
@click.option("--host", type=str, default="127.0.0.1", help="monitoring server host")
@click.option(
    "--port",
    type=int,
    default=None,
    help="monitoring server port (default PATHWAY_MONITORING_HTTP_PORT, 20000)",
)
@click.option("--proc", type=str, default=None, help="process id, or 'pod' for the merged rollup")
@click.option("--refresh", type=float, default=1.0, help="seconds between frames")
@click.option("--once", is_flag=True, default=False, help="print one frame and exit (no ANSI redraw)")
def top(host, port, proc, refresh, once):
    """Live terminal view of a RUNNING pipeline: qps, p99 by stage, watermark
    lag, backlog, pod pressure, per-phase tick split, doors/alerts and the
    current bottleneck verdict — read purely from the monitoring server's
    ``/timeline`` + ``/status`` endpoints."""
    import time as _t

    if port is None:
        port = int(os.environ.get("PATHWAY_MONITORING_HTTP_PORT", "20000"))
    qs = f"?proc={proc}" if proc else ""
    prev_lines = 0
    while True:
        status = _fetch_json(host, port, "/status")
        tl = _fetch_json(host, port, f"/timeline{qs}")
        if not tl.get("enabled", False):
            raise click.ClickException(
                "timeline plane is off on the target (PATHWAY_TIMELINE=off)"
            )
        frame = render_top(status, tl)
        if once:
            click.echo(frame)
            return
        if prev_lines:
            # redraw in place: cursor up + clear to end (LiveDashboard idiom)
            sys.stdout.write(f"\x1b[{prev_lines}F\x1b[J")
        sys.stdout.write(frame + "\n")
        sys.stdout.flush()
        prev_lines = frame.count("\n") + 1
        _t.sleep(max(0.1, refresh))


@cli.group()
def timeline() -> None:
    """Inspect spilled timeline segment directories."""


@timeline.command("diff")
@click.argument("dir_a", type=click.Path(exists=True, file_okay=False))
@click.argument("dir_b", type=click.Path(exists=True, file_okay=False))
@click.option(
    "--prefix",
    "prefixes",
    multiple=True,
    default=("phase_ms:", "stage_p99_s:"),
    show_default=True,
    help="metric prefixes to compare (repeatable)",
)
@click.option("--limit", type=int, default=12, help="rows to print")
def timeline_diff(dir_a, dir_b, prefixes, limit):
    """Cross-run comparison of two timeline segment directories: per-phase /
    per-stage mean cost in run A vs run B, worst regression first — names the
    PHASE that regressed, not just the number. Exits non-zero when B has no
    comparable series."""
    from pathway_tpu.observability.timeline import diff_summary, read_segments

    points_a = read_segments(dir_a)
    points_b = read_segments(dir_b)
    rows = diff_summary(points_a, points_b, prefixes=tuple(prefixes))
    if not rows:
        raise click.ClickException(
            f"no comparable series under {prefixes} in both directories "
            f"({len(points_a)} vs {len(points_b)} points read)"
        )
    click.echo(f"{'metric':<40} {'A':>12} {'B':>12} {'Δ%':>8}")
    for r in rows[: max(1, limit)]:
        click.echo(
            f"{r['metric']:<40} {r['a']:>12.4f} {r['b']:>12.4f} "
            f"{r['regression_pct']:>+8.1f}"
        )
    worst = rows[0]
    click.echo(
        f"worst regression: {worst['metric']} "
        f"({worst['regression_pct']:+.1f}% vs run A)"
    )


@cli.command(context_settings={"ignore_unknown_options": True})
@click.option("--record-path", type=str, default="./record", help="recorded persistence root")
@click.option(
    "--mode",
    type=click.Choice(["batch", "speedrun", "realtime"]),
    default="speedrun",
    help="replay pacing: batch/speedrun = as fast as possible, realtime = original pacing",
)
@click.option("-t", "--threads", type=int, default=1)
@click.option("-n", "--processes", type=int, default=1)
@click.option(
    "--continue-after-replay/--no-continue-after-replay",
    default=False,
    help="after replaying the recording, keep consuming live sources",
)
@click.argument("program", nargs=-1, type=click.UNPROCESSED)
def replay(record_path, mode, threads, processes, continue_after_replay, program):
    """Re-run PROGRAM against inputs recorded by `spawn --record`."""
    env = dict(os.environ)
    env["PATHWAY_THREADS"] = str(threads)
    env["PATHWAY_PROCESSES"] = str(processes)
    env["PATHWAY_REPLAY_STORAGE"] = record_path
    env["PATHWAY_PERSISTENT_STORAGE"] = record_path
    env["PATHWAY_REPLAY_MODE"] = mode
    env["PATHWAY_CONTINUE_AFTER_REPLAY"] = "1" if continue_after_replay else "0"
    sys.exit(_spawn_processes(env, processes, program))


def main() -> None:
    cli()


if __name__ == "__main__":
    main()
