"""The kernels' byte counts against the operand and result shapes of their
custom calls in programs compiled for a TPU v5e (no chip needed)."""

import re

import pytest

from bench import kernel_bytes as kb


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the installed TPU library
        pytest.skip(f"no v5e topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled(one_chip, shapes):
    import jax
    import jax.numpy as jnp

    from repro.kernels import rangemax

    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip) for s in shapes]
    fn = jax.jit(lambda x: rangemax.rangemax_pallas(x, interpret=False))
    calls = kb.module_custom_calls(fn.lower(*args).compile().as_text())
    return [c for c in calls if c["target"] == "tpu_custom_call"]


@pytest.mark.parametrize("B,L", [(8, 128), (16, 512), (64, 1024)])
def test_range_max_table_bytes(one_chip, B, L):
    (call,) = _compiled(one_chip, [(B, L)])
    assert call["result"] == [("s32", (B, kb.num_levels(L), L))]
    assert call["operand_bytes"] + call["result_bytes"] == kb.range_max_table_bytes(call["result"])
    assert kb.KERNELS["range_max_table"] is kb.range_max_table_bytes


def test_shapes_of_reads_tuples_and_layouts():
    text = "(s32[8,128]{1,0:T(8,128)}, f32[2,3,4]{2,1,0}) custom-call(%a)"
    assert kb.shapes_of(text) == [("s32", (8, 128)), ("f32", (2, 3, 4))]
    assert kb.nbytes(kb.shapes_of(text)) == 8 * 128 * 4 + 24 * 4
