"""The decode step's Pallas kernels at the published widths, compiled for a
described TPU v5e (no chip: libtpu's compiler is on the sandbox). It finds
what the interpreter cannot: a block the tiling refuses, more fast memory
than a kernel may use. It says nothing about results or times. The topology
is described in a fixture, so that only the worker given this file loads the
TPU's library; the other compile tests, if any come, belong in this file."""

import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from pathway_tpu.ops import decoder as D
from pathway_tpu.ops import mixers

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu from describing a chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_hybrid_step_compiles_for_a_v5e_with_its_kernels(one_chip, monkeypatch):
    from chipbench.reference_granite_4h import program_params

    with open(os.path.join(ROOT, "chipbench", "configs", "adaptive-rag-granite-4h-micro.llm.json"), encoding="utf-8") as f:
        llm = json.load(f)
    monkeypatch.setattr(mixers, "_interpret", lambda: False)  # the kernels themselves, not the interpreter
    cfg = D.DecoderConfig.from_hf(llm, jnp.bfloat16)
    model = D.JaxDecoder(cfg, None, cache_rows=16, cache_len=4096)

    def placed(tree):
        return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(lambda: program_params(jax.random.PRNGKey(0), llm, "bfloat16")))
    cache = placed(jax.eval_shape(model.new_cache))
    rows = jax.ShapeDtypeStruct((3, 4), jnp.int32, sharding=one_chip)
    compiled = D.step.fn.lower(params, cache, rows, cfg=cfg).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 36 * 2 + 4  # a state and a convolution kernel a recurrent layer, attention's
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes < 8.4e9 and memory.temp_size_in_bytes < 0.5e9
    assert memory.alias_size_in_bytes > 1.7e9  # the cache is updated where it lies
