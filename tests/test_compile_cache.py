"""Persistent-compilation-cache smoke test (subprocesses, since the cache
must be configured before the backend compiles anything).

The entry points turn the cache on with ``repro.compat.enable_compile_cache``
— never an import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache
lands there and nowhere else; a first process populates it and a second
must get actual cache *hits*, asserted via jax's monitoring events, not
just file reuse.  Without the variable the cache goes to one fixed,
git-ignored directory inside the checkout."""

import json
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_CHILD = r"""
import os, sys, json
sys.path.insert(0, sys.argv[1])
from repro.compat import enable_compile_cache
path = enable_compile_cache()
import jax, jax.numpy as jnp
from jax._src import monitoring

hits = []
monitoring.register_event_listener(
    lambda name, **kw: hits.append(name) if "compilation_cache/cache_hit" in name else None
)
f = jax.jit(lambda x: jnp.cumsum(jnp.sin(x)) * 2.0)
f(jnp.ones((128,))).block_until_ready()
print(json.dumps({"hits": len(hits), "path": path, "config": jax.config.jax_compilation_cache_dir}))
"""


def _env(**kw) -> dict:
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(kw)
    return env


def _run(code: str, env: dict) -> dict:
    res = subprocess.run(
        [sys.executable, "-c", code, SRC], capture_output=True, text=True, timeout=300, env=env
    )
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_cache_populates_and_hits(tmp_path):
    cache = str(tmp_path / "xla_cache")
    env = _env(JAX_COMPILATION_CACHE_DIR=cache)
    first = _run(_CHILD, env)
    assert first["path"] == first["config"] == cache  # the variable's dir, no other
    entries = [f for f in os.listdir(cache) if f.endswith("-cache")]
    assert entries, "first process must write executables into the cache dir"
    assert first["hits"] == 0  # nothing to hit on a cold cache
    second = _run(_CHILD, env)
    assert second["hits"] >= 1, "second process must hit the persistent cache"


def test_cache_defaults_to_checkout_dir():
    probe = (
        "import sys, json; sys.path.insert(0, sys.argv[1]);"
        "from repro.compat import CHECKOUT_CACHE_DIR, enable_compile_cache;"
        "print(json.dumps({'path': enable_compile_cache(), 'fixed': str(CHECKOUT_CACHE_DIR)}))"
    )
    out = _run(probe, _env())
    repo = os.path.realpath(os.path.join(os.path.dirname(__file__), ".."))
    assert out["path"] == out["fixed"] == os.path.join(repo, ".jax_cache")
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_import_leaves_the_cache_off():
    """Importing the engines must not configure the cache: only the entry
    points decide where compiled programs go."""
    probe = (
        "import sys, json; sys.path.insert(0, sys.argv[1]);"
        "import repro.sim.batch_engine, repro.sim.device_timeline, repro.serve.admission;"
        "import jax; print(json.dumps({'dir': jax.config.jax_compilation_cache_dir}))"
    )
    assert _run(probe, _env())["dir"] is None
