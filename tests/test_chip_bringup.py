"""What the chip bring-up changed, pinned on the CPU: a device error is never
retried row by row, the attention gate and the forced device plane raise, the
compile cache is placed from outside or at one fixed path, the native build is
keyed on source content, the launchers give each child its own chip, and
``chip_smoke.py``'s pipeline answers correctly at a small size while its
``main()`` refuses anything but a TPU."""

from __future__ import annotations

import hashlib
import importlib
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.internals import chips, compile_cache, errors
from pathway_tpu.internals.errors import ERROR
from pathway_tpu.internals.udfs import UDF
from utils import keyed_rows_of

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------- device errors vs rows


class _BatchedUdf(UDF):
    is_batched = True

    def __init__(self, fn):
        self.launches: list[int] = []

        def batch_fn(xs):
            self.launches.append(len(xs))
            return [fn(x) for x in xs]

        super().__init__(_fn=batch_fn, return_type=int)


class KS(pw.Schema):
    k: int = pw.column_definition(primary_key=True)
    x: int


def _select(udf):
    t = pw.debug.table_from_rows(
        KS, [(i, 10 + i, i // 4, 1) for i in range(8)], is_stream=True
    )
    return keyed_rows_of(t.select(t.k, y=udf(t.x)))


# auto = MicrobatchApplyNode -> operators._launch_udf_batch;
# off = inline BatchApplyExpression -> expression_vm._eval_batch_apply
@pytest.mark.parametrize("microbatch", ["auto", "off"])
def test_device_error_in_batched_udf_propagates(monkeypatch, microbatch):
    monkeypatch.setenv("PATHWAY_MICROBATCH", microbatch)

    def device_fails(x):
        raise jax.errors.JaxRuntimeError("INTERNAL: Mosaic failed to compile TPU kernel")

    udf = _BatchedUdf(device_fails)
    with pytest.raises(jax.errors.JaxRuntimeError):
        _select(udf)
    # one failed launch per bucket, never a second launch at B=1
    assert udf.launches and all(n > 1 for n in udf.launches)


@pytest.mark.parametrize("microbatch", ["auto", "off"])
def test_data_error_in_batched_udf_still_poisons_one_row(monkeypatch, microbatch):
    monkeypatch.setenv("PATHWAY_MICROBATCH", microbatch)

    def bad_row(x):
        if x == 13:
            raise ValueError("bad row")
        return x + 1

    rows = {row[0]: row for row in _select(_BatchedUdf(bad_row)).values()}
    assert rows[3] == (3, ERROR)
    assert [rows[k] for k in (0, 1, 2, 4, 5, 6, 7)] == [
        (k, 11 + k) for k in (0, 1, 2, 4, 5, 6, 7)
    ]


def test_traced_jit_marks_what_crosses_it_as_a_device_error():
    from pathway_tpu.observability import device

    def lowering_fails(x):
        raise NotImplementedError("Unimplemented primitive in Pallas TPU lowering")

    with pytest.raises(NotImplementedError) as info:
        device.traced_jit("test.lowering", lowering_fails)(1)
    assert errors.is_device_error(info.value)
    assert not errors.is_device_error(NotImplementedError("a row's own problem"))
    assert errors.is_device_error(jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED"))


def test_attention_gate_does_not_swallow_kernel_errors(monkeypatch):
    import jax.numpy as jnp

    from pathway_tpu.ops import attention_kernel

    def boom(*a, **k):
        raise RuntimeError("trace-time failure")

    monkeypatch.setattr(attention_kernel, "_attention_short_impl", boom)
    x = jnp.zeros((8, 16, 384), jnp.bfloat16)
    with pytest.raises(RuntimeError, match="trace-time failure"):
        attention_kernel.attention_short_flat(x, x, x, jnp.ones((8, 16), bool), 6, 0.125)
    # the shape gate is still a selection, not an error
    long = jnp.zeros((8, 256, 384), jnp.bfloat16)
    assert (
        attention_kernel.attention_short_flat(long, long, long, jnp.ones((8, 256), bool), 6, 0.125)
        is None
    )


def test_forced_device_plane_raises_when_it_cannot_build_its_mesh():
    from pathway_tpu.parallel.device_plane import DeviceExchangePlane

    too_many = len(jax.devices()) + 1
    with pytest.raises(RuntimeError, match="one device per worker"):
        DeviceExchangePlane(too_many, force=True).available()
    assert DeviceExchangePlane(too_many, force=False).available() is False


def test_relational_kernels_are_pinned_to_the_host_device(monkeypatch):
    from pathway_tpu.engine import jax_kernels

    monkeypatch.delenv("PATHWAY_ENGINE_JAX", raising=False)
    assert jax_kernels.host_device().platform == "cpu"
    assert jax_kernels._device(force_cpu=True).platform == "cpu"
    # a backend asked for by name and absent is an error, not the default backend
    monkeypatch.setenv("PATHWAY_ENGINE_JAX", "tpu")
    with pytest.raises(RuntimeError):
        jax_kernels._device()


def test_join_nets_several_blocks_accepted_in_one_tick():
    """Sharded runtimes deliver an upstream reduce's re-emissions as several
    blocks in one tick: ``+a`` then ``-a, +b`` must leave ``b`` alone."""
    from pathway_tpu.engine.blocks import DeltaBatch
    from pathway_tpu.engine.operators import JoinNode

    node = JoinNode(["ljk", "q"], ["rjk", "count"], "ljk", "rjk", how="left")

    def right(diffs, counts):
        n = len(diffs)
        return DeltaBatch(
            np.full(n, 7, dtype=np.uint64),
            np.asarray(diffs, dtype=np.int64),
            {"rjk": np.zeros(n, dtype=np.int64), "count": np.asarray(counts, dtype=np.int64)},
            0,
        )

    node.accept(1, right([1], [30]))
    node.accept(1, right([-1, 1], [30, 48]))
    node.process(node.drain(), 0)
    node.accept(
        0,
        DeltaBatch(
            np.asarray([1], dtype=np.uint64),
            np.asarray([1], dtype=np.int64),
            {"ljk": np.zeros(1, dtype=np.int64), "q": np.asarray([5], dtype=np.int64)},
            1,
        ),
    )
    (out,) = node.process(node.drain(), 1)
    assert out.diffs.tolist() == [1]
    assert [v.tolist() for k, v in out.data.items() if k.endswith("count")] == [[48]]


# --------------------------------------------------------------- compile cache


def test_cache_dir_placed_from_outside_is_left_alone(monkeypatch, tmp_path):
    assert compile_cache.default_dir(str(tmp_path), None, "tpu,cpu") is None
    assert compile_cache.default_dir(None, "/somewhere/else", "tpu,cpu") is None
    # applied: the option stays as it was, and the directory reported is the environment's
    saved = getattr(jax.config, compile_cache.DIR_OPTION)
    monkeypatch.setattr(compile_cache, "_applied", False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.ensure_compile_cache() == str(tmp_path)
    assert getattr(jax.config, compile_cache.DIR_OPTION) == saved  # JAX reads the env itself


def test_cache_dir_defaults_to_one_fixed_path_in_the_checkout():
    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.default_dir(None, None, "tpu,cpu") == fixed
    assert compile_cache.default_dir(None, None, None) == fixed
    assert compile_cache.default_dir("", None, "tpu") == fixed


def test_a_process_held_to_the_cpu_places_no_cache():
    assert compile_cache.default_dir(None, None, "cpu") is None
    # this test process is one
    assert jax.config.jax_platforms == "cpu"
    assert getattr(jax.config, compile_cache.DIR_OPTION) is None


def test_one_place_sets_the_cache_dir():
    hits = subprocess.run(
        ["grep", "-rn", "--include=*.py", compile_cache.DIR_OPTION, REPO,
         "--exclude-dir=_checkouts", "--exclude-dir=.jax_cache"],
        capture_output=True, text=True,
    ).stdout.splitlines()
    assert [h.split(":")[0] for h in hits] == [compile_cache.__file__], hits


# ---------------------------------------------------------------- native build


def test_native_build_is_keyed_on_source_content():
    from pathway_tpu import native

    for name in ("pwhash", "pwtok"):
        with open(os.path.join(os.path.dirname(native.__file__), f"{name}.c"), "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        assert os.path.basename(native.load(name).__file__) == f"{name}-{digest}.so"


# ------------------------------------------------------------ one chip a child


def test_child_i_gets_chip_i():
    env = {"TPU_VISIBLE_CHIPS": "0,1,2,3"}
    for pid in range(4):
        assert chips.child_chip_env(env, pid, 4) == {
            "TPU_VISIBLE_CHIPS": str(pid),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
        }
    # a single process may drive every chip; a CPU-forced cluster needs none
    assert chips.child_chip_env(env, 0, 1) == {}
    assert chips.child_chip_env(dict(env, JAX_PLATFORMS="cpu"), 1, 8) == {}


def test_more_processes_than_chips_is_an_error():
    with pytest.raises(ValueError, match="2 processes need 2 TPU chips"):
        chips.child_chip_env({"TPU_VISIBLE_CHIPS": "0"}, 0, 2)


def test_spawn_rejects_more_processes_than_chips_before_spawning(tmp_path):
    marker = tmp_path / "spawned"
    env = dict(os.environ, PYTHONPATH=REPO, TPU_VISIBLE_CHIPS="0")
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run(
        [sys.executable, "-m", "pathway_tpu", "spawn", "--processes", "2",
         sys.executable, "-c", f"open({str(marker)!r}, 'w').close()"],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 2 and "2 processes need 2 TPU chips" in r.stderr, r.stderr
    assert not marker.exists()


def test_supervisor_gives_each_child_a_chip_or_refuses(tmp_path):
    from pathway_tpu.resilience import Supervisor

    marker = tmp_path / "spawned"
    program = [sys.executable, "-c", f"open({str(marker)!r}, 'w').close()"]
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    sup = Supervisor(program, processes=2, env=dict(env, TPU_VISIBLE_CHIPS="2,3"))
    assert [sup._child_env(pid, 0)["TPU_VISIBLE_CHIPS"] for pid in (0, 1)] == ["2", "3"]
    with pytest.raises(ValueError, match="2 processes need 2 TPU chips"):
        Supervisor(program, processes=2, env=dict(env, TPU_VISIBLE_CHIPS="0")).run()
    assert not marker.exists()


# ------------------------------------------------------------------ chip smoke


@pytest.fixture(scope="module")
def chip_smoke():
    sys.path.insert(0, REPO)
    try:
        yield importlib.import_module("chip_smoke")
    finally:
        sys.path.remove(REPO)


def test_chip_smoke_pipeline_answers_at_64_documents(chip_smoke):
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
    from pathway_tpu.xpacks.llm.rerankers import CrossEncoderReranker

    stages = chip_smoke.corpus(((48, 120), (8, 300), (8, 10)))
    assert sum(len(s) for s in stages) == 64
    answers = chip_smoke.serve(
        stages,
        SentenceTransformerEmbedder(),
        CrossEncoderReranker(),
        n_retrieve=(2, 1, 1),
        n_rerank=(1, 1, 1),
        timeout_s=300,
    )
    assert chip_smoke.check_answers(answers) == {"retrieve": 4, "rerank": 3}
    assert chip_smoke.same_answers(answers, answers)["topk_identical"]


def test_chip_smoke_main_refuses_anything_but_a_tpu(chip_smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main() == 2
    captured = capsys.readouterr()
    assert "platform=cpu" in captured.out and '"ok"' not in captured.out
    assert "needs a TPU" in captured.err


class _FakeTpu:
    platform = "tpu"
    device_kind = "TPU v5 lite"


@pytest.mark.parametrize("fails", [False, True])
def test_chip_smoke_last_line_is_the_verdict_and_nothing_else(
    chip_smoke, capsys, monkeypatch, tmp_path, fails
):
    """The checker reads the last stdout line: exactly ``ok`` and ``device``
    (platform, kind, count); the full report is the line before it."""

    def phases(report):
        report["answers"] = {"retrieve": 24}
        if fails:
            raise AssertionError("a phase failed")

    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTpu()])
    monkeypatch.setattr(chip_smoke, "run_phases", phases)
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    assert chip_smoke.main() == (1 if fails else 0)
    lines = capsys.readouterr().out.splitlines()
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert json.loads(lines[-1]) == {"ok": not fails, "device": device}
    report = json.loads(lines[-2].removeprefix("chip_smoke: report "))
    assert report == json.loads((tmp_path / "chip_smoke.json").read_text())
    assert report["ok"] is (not fails) and report["claim"] is None
    assert report["answers"] == {"retrieve": 24} and ("error" in report) is fails
