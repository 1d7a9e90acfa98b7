"""CLI spawn / record / replay (reference: ``python/pathway/cli.py:53-113,167,253``,
``integration_tests/common/test_cli.py`` — multi-process spawn on loopback)."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

from conftest import free_port_base
from test_cluster import _PIPELINE

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cli(args, extra_env=None, timeout=120):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu", PATHWAY_BARRIER_TIMEOUT="45")
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "-m", "pathway_tpu", *args],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def test_spawn_multiprocess_matches_solo(tmp_path):
    script = tmp_path / "pipeline.py"
    script.write_text(_PIPELINE)
    solo = str(tmp_path / "solo")
    r = _cli(["spawn", sys.executable, str(script), solo])
    assert r.returncode == 0, r.stdout + r.stderr
    dist = str(tmp_path / "dist")
    r = _cli(
        ["spawn", "-t", "2", "-n", "2", "--first-port", str(free_port_base(2)),
         sys.executable, str(script), dist],
    )
    assert r.returncode == 0, r.stdout + r.stderr
    for suffix in (".groupby.csv", ".window.csv"):
        assert open(solo + suffix).read() == open(dist + suffix).read()


_RECORDABLE = textwrap.dedent(
    """
    import os
    import sys

    import pathway_tpu as pw

    class Subj(pw.io.python.ConnectorSubject):
        def run(self):
            n = int(os.environ.get("N_EVENTS", "6"))
            for i in range(n):
                self.next(k=i % 3, v=i)

    S = pw.schema_from_types(k=int, v=int)
    t = pw.io.python.read(Subj(), schema=S, name="events")
    g = t.groupby(t.k).reduce(t.k, s=pw.reducers.sum(t.v))
    pw.io.fs.write(g, sys.argv[1], format="csv")
    pw.run()
    """
)


def test_record_then_replay(tmp_path):
    script = tmp_path / "rec.py"
    script.write_text(_RECORDABLE)
    rec_root = str(tmp_path / "recording")
    out1 = str(tmp_path / "out1.csv")
    r = _cli(
        ["spawn", "--record", "--record-path", rec_root, sys.executable, str(script), out1],
        extra_env={"N_EVENTS": "6"},
    )
    assert r.returncode == 0, r.stdout + r.stderr
    # replay with the live source emitting MORE rows: the recording is the
    # whole input, so the extra live rows must be ignored
    out2 = str(tmp_path / "out2.csv")
    r = _cli(
        ["replay", "--record-path", rec_root, sys.executable, str(script), out2],
        extra_env={"N_EVENTS": "50"},
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert open(out1).read() == open(out2).read()
