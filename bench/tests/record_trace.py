#!/usr/bin/env python3
"""Record the small chip trace that ``test_trace.py`` reduces.

    python bench/tests/record_trace.py <out_dir>

Profiles, inside a ``bench.window`` span, one ``range_max_table`` kernel call
under a ``bench.call`` span and an idle stretch under a ``bench.place.loop``
span, as a run's window does, and copies the ``.xplane.pb`` the profiler
writes to ``<out_dir>/range_max_table.xplane.pb``.  Needs a TPU."""

import glob
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

SHAPE = (16, 512)


def main() -> int:
    import jax
    import jax.numpy as jnp

    from bench import common
    from repro.kernels import rangemax

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    fn = jax.jit(lambda x: rangemax.rangemax_pallas(x, interpret=False))
    x = jnp.arange(SHAPE[0] * SHAPE[1], dtype=jnp.int32).reshape(SHAPE) % 977
    fn(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with common.span("bench.window"):
        with common.span("bench.call"):
            fn(x).block_until_ready()
        with common.span("bench.place.loop"):
            time.sleep(0.002)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(sys.argv[1], exist_ok=True)
    shutil.copy(path, os.path.join(sys.argv[1], "range_max_table.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
