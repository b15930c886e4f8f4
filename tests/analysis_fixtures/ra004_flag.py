"""MUST-FLAG RA004: dtype-literal drift in an x64-parity module.

The module imports the x64 context (making it x64-parity code); `ladder` threads the `x64` flag but hard-codes float32 in its
body — exactly the f32-ulp drift `ladder_x64` was added to close.
"""

import jax.numpy as jnp

from jax import enable_x64


def ladder(y, *, x64=False):
    acc = jnp.zeros((), jnp.float32)
    with enable_x64(x64):
        return acc + y.sum()
