"""shard_map MoE equals the reference dispatch on a real multi-device mesh
(subprocess: 16 forced host devices)."""

import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import sys
sys.path.insert(0, sys.argv[1])
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models.layers import init_moe, moe

mesh = Mesh(np.asarray(jax.devices()).reshape(4, 4), ("data", "model"))
out = {}
for name, shard in [("qwen3-moe-235b-a22b", "ep"), ("grok-1-314b", "tp")]:
    r = get_config(name).reduced()
    r = dataclasses.replace(r, num_experts=8, experts_per_token=2, moe_d_ff=64,
                            capacity_factor=16.0, moe_sharding=shard)
    p = init_moe(jax.random.PRNGKey(0), r)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, r.d_model), jnp.float32).astype(jnp.bfloat16)
    ref_out, _ = moe(p, x, r)  # no mesh -> reference path
    with jax.set_mesh(mesh):
        f = jax.jit(lambda p, x: moe(p, x, r),
                    in_shardings=(None, NamedSharding(mesh, P(("data",), None, None))))
        got_out, got_aux = f(p, x)
    err = float(jnp.max(jnp.abs(ref_out.astype(jnp.float32) - got_out.astype(jnp.float32))))
    out[shard] = {"err": err, "aux": float(got_aux)}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def child_output():
    res = subprocess.run(
        [sys.executable, "-c", _CHILD, SRC], capture_output=True, text=True, timeout=900
    )
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_ep_sharded_matches_reference(child_output):
    assert child_output["ep"]["err"] < 0.05


def test_tp_sharded_matches_reference(child_output):
    assert child_output["tp"]["err"] < 0.05


def test_aux_loss_sane(child_output):
    for k in ("ep", "tp"):
        assert 0.0 < child_output[k]["aux"] < 10.0
