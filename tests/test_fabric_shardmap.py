"""Zero-hop fabric serving under the shard-map plane (r19).

With ``PATHWAY_SHARDMAP=on`` every fabric door routes each request DIRECTLY
into its local ingest copy — the request's key is minted to be locally owned,
so N doors are N independent front ends and NOTHING is forwarded between
processes on the serve path. The test pins: byte-identical answers from all
three doors (and vs a single-process run), ``X-Pathway-Fabric: owner:p<pid>``
on every response (each door IS the owner), and a pod-wide serving rollup
with forwarded_out == forwarded_in == 0 — the structural zero-hop assertion
that complements ``test_fabric.py``'s shardmap-off run, which pins the SAME
pipeline at forwarded_out == 6.
"""

from __future__ import annotations

import json
import textwrap

from conftest import free_port_base
from tests.test_fabric import _free_port, _run_cluster

_ECHO_SCRIPT = textwrap.dedent(
    """
    import json, os, socket, sys, threading, time, urllib.request
    import pathway_tpu as pw

    port = int(sys.argv[1])

    ws = pw.io.http.PathwayWebserver(host="127.0.0.1", port=port)
    queries, respond = pw.io.http.rest_connector(
        webserver=ws, route="/v1/echo", schema=pw.schema_from_types(text=str)
    )
    reply = queries.select(
        result=pw.apply(
            lambda t: {"upper": t.upper(), "len": len(t)}, queries.text
        )
    )
    respond(reply)

    pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
    n_proc = int(os.environ.get("PATHWAY_PROCESSES", "1"))
    stride = int(os.environ.get("PATHWAY_FABRIC_PORT_STRIDE", "1"))
    fabric_on = os.environ.get("PATHWAY_FABRIC") == "on"
    mon_base = int(os.environ.get("PATHWAY_MONITORING_HTTP_PORT", "0"))

    def wait_ready(p, timeout=60.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                socket.create_connection(("127.0.0.1", p), timeout=0.5).close()
                return
            except OSError:
                time.sleep(0.05)
        raise TimeoutError(p)

    if pid == 0:
        def client():
            doors = (
                [port + i * stride for i in range(n_proc)]
                if fabric_on
                else [port]
            )
            for p in doors:
                wait_ready(p)
            time.sleep(1.0)
            out = {"answers": {}, "fabric_headers": {}, "rids": {}}
            qs = ["alpha one", "beta two", "gamma three"]
            for p in doors:
                bodies, fhs, rids = [], [], []
                for q in qs:
                    req = urllib.request.Request(
                        f"http://127.0.0.1:{p}/v1/echo",
                        data=json.dumps({"text": q}).encode(),
                        headers={"Content-Type": "application/json"},
                    )
                    r = urllib.request.urlopen(req, timeout=90)
                    bodies.append(r.read().decode())
                    fhs.append(r.headers.get("X-Pathway-Fabric"))
                    rids.append(r.headers.get("X-Pathway-Request-Id"))
                out["answers"][str(p)] = bodies
                out["fabric_headers"][str(p)] = fhs
                out["rids"][str(p)] = rids
            if fabric_on and mon_base:
                time.sleep(1.6)  # two heartbeats: the serving rollup lands
                out["status"] = json.loads(urllib.request.urlopen(
                    f"http://127.0.0.1:{mon_base}/status", timeout=30
                ).read())
            print("RESULT:" + json.dumps(out), flush=True)
            rt = pw.internals.run.current_runtime()
            if rt is not None:
                rt.request_stop()

        threading.Thread(target=client, daemon=True).start()

    pw.run(monitoring_level="none", with_http_server=bool(mon_base))
    print("DONE", flush=True)
    """
)


def test_shardmap_zero_hop_three_doors_byte_identity(tmp_path):
    """ISSUE 16 acceptance: under the shard map every door answers locally —
    byte-identical bodies, owner-stamped headers, zero forwards pod-wide."""
    script = tmp_path / "echo.py"
    script.write_text(_ECHO_SCRIPT)
    block = free_port_base(4 + 9)
    mon_base = block
    fabric = _run_cluster(
        script,
        _free_port(),
        3,
        {
            "PATHWAY_FABRIC": "on",
            "PATHWAY_SHARDMAP": "on",
            "PATHWAY_ELASTIC": "manual",
            "PATHWAY_MONITORING_HTTP_PORT": str(mon_base),
        },
        first_port=block + 4,
    )
    single = _run_cluster(
        script,
        _free_port(),
        1,
        {
            "PATHWAY_FABRIC": "off",
            "PATHWAY_SHARDMAP": "off",
            "PATHWAY_MONITORING_HTTP_PORT": "0",
        },
    )

    # byte identity: every door agrees with every other AND with the
    # single-process shardmap-off run — placement changed, answers did not
    doors = sorted(fabric["answers"], key=int)
    assert len(doors) == 3
    reference = single["answers"][str(list(single["answers"])[0])]
    for door in doors:
        assert fabric["answers"][door] == reference, (
            f"door {door} diverged from the single-process answers"
        )

    # zero-hop: every response is answered by the door it arrived at — the
    # door IS the owner of the key it minted for the request
    for i, door in enumerate(doors):
        assert fabric["fabric_headers"][door] == [f"owner:p{i}"] * 3, (
            fabric["fabric_headers"]
        )

    # request ids stay unique pod-wide (pid-salted mint)
    all_rids = [r for rids in fabric["rids"].values() for r in rids]
    assert len(set(all_rids)) == len(all_rids)

    # structural zero-hop, pod-wide: all nine requests answered where they
    # landed; NOTHING crossed the fabric on the serve path (the shardmap-off
    # twin of this pipeline shape pins forwarded_out == 6 in test_fabric.py)
    cluster = fabric["status"]["serving"]["cluster"]
    assert cluster["n_reporting"] == 3
    route = cluster["routes"]["/v1/echo"]
    assert route["requests"] == 9
    assert route["responses"] == 9
    assert route["forwarded_out"] == 0
    assert route["forwarded_in"] == 0

    # the fabric advertises the shard-map plane it is routing by
    assert fabric["status"]["fabric"]["enabled"] is True
    assert fabric["status"]["fabric"]["shardmap_version"] == 0
