"""MUST-PASS RA005: the ``jax.enable_x64`` context, as the CPU reference
ladder uses it (``batch_engine.compute_cluster_ladders(x64=True)``).
"""

import jax


def dispatch(program, *args):
    with jax.enable_x64(True):
        return program(*args)
