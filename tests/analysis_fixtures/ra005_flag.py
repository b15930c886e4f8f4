"""MUST-FLAG RA005: the removed ``jax.experimental.enable_x64``.

Every batched program once imported it through one helper; on the
installed jax the import fails, and no device program may run in float64
anyway (the TPU compiler refuses f64 ``nextafter`` and f64 bitcasts).
"""

from jax.experimental import enable_x64


def dispatch(program, *args):
    with enable_x64():
        return program(*args)
