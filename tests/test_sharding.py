"""Sharding rules + a real multi-device lowering (subprocess with 16 forced
host devices, since this process owns the single-device runtime)."""

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
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.configs import get_config, input_specs, TRAIN_4K
from repro.distributed.sharding import param_specs, data_specs, sanitize_spec
from jax.sharding import PartitionSpec as P

mesh = Mesh(np.asarray(jax.devices()).reshape(4, 4), ("data", "model"))
out = {}

# rule sanity on a TP arch
cfg = get_config("gemma2-9b")
shapes = jax.eval_shape(lambda: __import__("repro.models.model", fromlist=["init_params"]).init_params(jax.random.PRNGKey(0), cfg))
specs = param_specs(shapes, cfg, mesh)
flat = jax.tree_util.tree_flatten_with_path(specs)[0]
embed_spec = [s for p, s in flat if "embed" in str(p)][0]
out["embed"] = str(embed_spec.spec)
wq = [s for p, s in flat if "wq" in str(p)][0]
out["wq"] = str(wq.spec)

# sanitizer drops non-dividing axes
sp = sanitize_spec(mesh, P("model", "data"), (6, 8))
out["sanitized"] = str(sp)

# real lowering: tiny fsdp arch end-to-end on the 4x4 mesh
import dataclasses
r = dataclasses.replace(get_config("llama3.2-3b").reduced(), vocab_size=512)
from repro.models.model import init_params
from repro.train.train_step import TrainConfig, init_train_state, make_train_step
with jax.set_mesh(mesh):
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), r))
    state = jax.eval_shape(lambda p: init_train_state(p), params)
    p_sh = param_specs(params, r, mesh)
    st_sh = {"params": p_sh, "opt": {"mu": param_specs(state["opt"]["mu"], r, mesh),
             "nu": param_specs(state["opt"]["nu"], r, mesh),
             "step": jax.sharding.NamedSharding(mesh, P())},
             "step": jax.sharding.NamedSharding(mesh, P())}
    batch = {
        "tokens": jax.ShapeDtypeStruct((16, 32), jnp.int32),
        "labels": jax.ShapeDtypeStruct((16, 32), jnp.int32),
        "mask": jax.ShapeDtypeStruct((16, 32), jnp.int32),
    }
    d_sh = data_specs(mesh, batch, r)
    step = make_train_step(r, TrainConfig())
    compiled = jax.jit(step, in_shardings=(st_sh, d_sh)).lower(state, batch).compile()
    out["compiled"] = True
    out["temp_gb"] = compiled.memory_analysis().temp_size_in_bytes / 2**30
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def child_output():
    res = subprocess.run(
        [sys.executable, "-c", _CHILD, SRC], capture_output=True, text=True, timeout=600
    )
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_tp_rules(child_output):
    assert child_output["embed"] == "PartitionSpec('model', 'data')"
    assert child_output["wq"] == "PartitionSpec(None, 'data', 'model')"  # stacked


def test_sanitizer(child_output):
    # 6 % 4 != 0 -> "model" dropped on dim0; 8 % 4 == 0 -> "data" kept
    assert child_output["sanitized"] == "PartitionSpec(None, 'data')"


def test_multi_device_train_lowering(child_output):
    assert child_output["compiled"] is True
    assert child_output["temp_gb"] < 4.0  # tiny model stays tiny per device
