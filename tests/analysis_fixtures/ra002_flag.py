"""MUST-FLAG RA002: PR 6's heap-corruption class, verbatim shape.

Donated-buffer executables deserialized from the persistent compile
cache crash jax 0.4.37's XLA:CPU (use-after-free on the donated input).
Unconditional donation is therefore a latent crash on every CPU CI run
with the persistent compilation cache on.
"""

import jax


def make_step(train_step):
    return jax.jit(train_step, donate_argnums=(0,))
