"""MUST-PASS RA004: the sanctioned ladder-selection spellings.

The batch_engine pattern: dtype derived from the x64 flag via the
conditional expression, and float32 as a *signature default* (callers
override it through the ladder) — both exempt.
"""

import jax.numpy as jnp

from jax import enable_x64


def ladder(y, *, x64=False):
    dt = jnp.float64 if x64 else jnp.float32
    acc = jnp.zeros((), dt)
    with enable_x64(x64):
        return acc + y.sum().astype(dt)


def engine(y, dtype=jnp.float32):
    return y.astype(dtype).sum()
