"""The scheduling-epoch program's packed I/O contract.

``schedule_epoch`` crosses the host-device boundary once each way per
dispatch: ``_epoch_inputs`` writes all twelve inputs into one flat int32
buffer at offsets fixed by the static shape ``(N, L, H, Wb, k)`` (the
capacity per node, ``budget``, as N values), and ``_schedule_program``
returns its seven outputs as one int32 vector.  These tests pin the layout
against a plain construction of the twelve arrays, the
program's operand and result, and that the shape tuple (never the buffer's
length) selects the compiled variant.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.timeline import NEVER, Timeline, budget_units, quantize_steps, time_keys  # noqa: E402
from repro.sim.cluster import place_rows  # noqa: E402
from repro.sim.device_timeline import (  # noqa: E402
    _epoch_inputs,
    _epoch_layout,
    _epoch_unpack,
    _schedule_program,
)
from repro.sim.traces import bucket_size, fine_bucket  # noqa: E402


def _rows(w: int, k: int, seed: int):
    """``w`` attempt rows of ``k`` steps in timeline units: (boundary keys,
    value units, run keys, probe keys)."""
    rng = np.random.default_rng(seed)
    cuts = np.cumsum(rng.uniform(0.5, 1.5, (w, k)), axis=1)
    cuts[:, -1] = np.inf
    bnd, val = quantize_steps(cuts, rng.uniform(50.0, 400.0, (w, k)))
    run = time_keys(rng.uniform(2.0, 4.0, w))
    return bnd, val, run, run + time_keys(rng.uniform(0.0, 1.0, w))


def _node_events(n_nodes: int, per_node: int, seed: int):
    """Per node, a ``Timeline``'s events after ``per_node`` reservations."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_nodes):
        tl = Timeline()
        for j in range(per_node):
            start = time_keys(rng.uniform(0.0, 5.0))
            b, v = quantize_steps([1.0, np.inf], rng.uniform(10.0, 90.0, 2))
            tl.add(j, b, v, start, start + time_keys(rng.uniform(1.0, 6.0)))
        out.append(tl.events())
    return out


def _fields_reference(now, bnd, val, run, node_events, pending, budget, Wb, probe):
    """The twelve inputs as separate arrays, built plainly row by row."""
    w, k = bnd.shape
    N = len(node_events)
    cut = [int(np.sum(t <= now)) for t, _ in node_events]
    e0 = max([len(t) - c for (t, _), c in zip(node_events, cut)], default=0)
    L = fine_bucket(e0 + max(2, min(Wb, 8)) * (k + 2), floor=64)
    H = bucket_size(len(pending) + Wb, floor=32)
    f = {
        "tl_t": np.full((N, L), NEVER, np.int32),
        "tl_d": np.zeros((N, L), np.int32),
        "base0": np.zeros(N, np.int32),
        "ev": np.full(H, NEVER, np.int32),
        "h0": np.int32(len(pending)),
        "now0": np.int32(now),
        "bnd": np.full((Wb, k), NEVER, np.int32),
        "val": np.zeros((Wb, k), np.int32),
        "run": np.zeros(Wb, np.int32),
        "pdur": np.zeros(Wb, np.int32),
        "valid": np.zeros(Wb, np.int32),
        "budget": np.zeros(N, np.int32),
    }
    for n in range(N):
        f["budget"][n] = budget if np.ndim(budget) == 0 else budget[n]
    for n, ((t, d), c) in enumerate(zip(node_events, cut)):
        f["base0"][n] = sum(int(x) for x in d[:c])
        for j in range(c, len(t)):
            f["tl_t"][n, j - c], f["tl_d"][n, j - c] = t[j], d[j]
    for j, e in enumerate(sorted(int(p) for p in pending)):
        f["ev"][j] = e
    for r in range(w):
        f["bnd"][r], f["val"][r] = bnd[r], val[r]
        f["run"][r], f["pdur"][r], f["valid"][r] = run[r], probe[r], 1
    return (N, L, H, Wb, k), f


@pytest.mark.parametrize(
    "case",
    [
        # two empty nodes, an empty heap, a part-filled window, probe = run
        dict(n_nodes=2, per_node=0, n_pending=0, w=3, k=2, Wb=8, now=0.0, probe=False),
        # events folded at the clock, an unsorted heap, a full window
        dict(n_nodes=3, per_node=6, n_pending=5, w=8, k=2, Wb=8, now=2.5, probe=True),
        # a wide window of four-step rows on sixteen nodes
        dict(n_nodes=16, per_node=4, n_pending=40, w=20, k=4, Wb=32, now=4.0, probe=True),
        # a capacity per node: three nodes of three sizes
        dict(n_nodes=3, per_node=6, n_pending=5, w=8, k=2, Wb=8, now=2.5, probe=True,
             budget=[32_768, 8_192, 16_384]),
        # sixteen nodes of the mixed pool's four sizes, in its order (GiB)
        dict(n_nodes=16, per_node=4, n_pending=40, w=20, k=4, Wb=32, now=4.0, probe=True,
             budget=[g * 1024 for g in (64, 32, 64, 128, 64, 32, 64, 96, 64, 32, 64, 32, 64, 32, 64, 64)]),
    ],
    ids=["empty", "folded", "wide", "pernode", "mixed16"],
)
def test_epoch_inputs_unpack_bit_for_bit(case):
    bnd, val, run, probe = _rows(case["w"], case["k"], seed=case["w"])
    node_events = _node_events(case["n_nodes"], case["per_node"], seed=1)
    pending = np.random.default_rng(2).permutation(time_keys(np.arange(case["n_pending"]) * 0.75 + 1.0))
    now = int(time_keys(case["now"]))
    probe = probe if case["probe"] else None
    budget = case.get("budget", 12_345)
    buf, shape = _epoch_inputs(now, bnd, val, run, node_events, pending, budget, case["Wb"], probe)
    want_shape, want = _fields_reference(
        now, bnd, val, run, node_events, pending, budget, case["Wb"], run if probe is None else probe
    )
    assert shape == want_shape
    assert buf.dtype == np.int32 and buf.shape == (_epoch_layout(shape)[1],)
    got = _epoch_unpack(buf, shape)
    assert list(got) == list(want)
    for name, a in want.items():
        assert got[name].shape == np.shape(a), name
        np.testing.assert_array_equal(got[name], a, err_msg=name)


@pytest.mark.parametrize("shape", [(2, 64, 32, 8, 2), (16, 128, 64, 8, 4), (3, 96, 32, 32, 1)])
def test_schedule_program_one_operand_one_result(shape):
    Wb = shape[3]
    spec = jax.ShapeDtypeStruct((_epoch_layout(shape)[1],), jnp.int32)
    lowered = _schedule_program.lower(spec, shape=shape)
    (arg,) = jax.tree.leaves(lowered.args_info)
    assert arg.shape == spec.shape and arg.dtype == jnp.int32
    out = lowered.out_info
    assert isinstance(out, jax.ShapeDtypeStruct)
    assert out.shape == (3 * Wb + 4,) and out.dtype == jnp.int32


def _repack(buf, shape, new_shape):
    """``buf`` laid out for ``new_shape``: the timeline axis widened and the
    heap narrowed, with the same padding (``NEVER`` keys, zero deltas)."""
    old = _epoch_unpack(buf, shape)
    out = np.zeros(_epoch_layout(new_shape)[1], np.int32)
    new = _epoch_unpack(out, new_shape)
    L, H = shape[1], new_shape[2]
    new["tl_t"].fill(NEVER)
    new["ev"].fill(NEVER)
    assert (old["ev"][H:] == NEVER).all()
    for name in new:
        if name in ("tl_t", "tl_d"):
            new[name][:, :L] = old[name]
        elif name == "ev":
            new[name][...] = old[name][:H]
        else:
            new[name][...] = old[name]
    return out


@pytest.mark.parametrize("node_gib", [64.0, 0.75], ids=["roomy", "congested"])
def test_equal_length_shapes_compile_apart(node_gib):
    """Two shapes whose buffers have one length are two compiled variants,
    and each places the rows exactly as the host oracle does."""
    bnd, val, run, probe = _rows(8, 2, seed=7)
    node_mib = node_gib * 1024
    empty = np.empty(0, np.int64)
    buf_a, shape_a = _epoch_inputs(
        0, bnd, val, run, [(empty, empty)] * 2, empty, int(budget_units(node_mib)), 8, probe
    )
    N, L, H, Wb, k = shape_a
    shape_b = (N, L + 1, H - 2 * N, Wb, k)  # one more key and delta a node, 2N fewer heap slots
    buf_b = _repack(buf_a, shape_a, shape_b)
    assert buf_a.shape == buf_b.shape and shape_a != shape_b

    ref_node, ref_start, _ = place_rows(bnd, val, run, probe, N, node_mib)
    _schedule_program.clear_cache()
    for i, (buf, shape) in enumerate(((buf_a, shape_a), (buf_b, shape_b))):
        out = np.asarray(_schedule_program(buf, shape))
        assert _schedule_program._cache_size() == i + 1
        assert out.dtype == np.int32 and out.shape == (3 * Wb + 4,)
        placed, node, start, tail = out[:Wb], out[Wb : 2 * Wb], out[2 * Wb : 3 * Wb], out[3 * Wb :]
        assert placed.tolist() == [1] * 8
        np.testing.assert_array_equal(node, ref_node)
        np.testing.assert_array_equal(start, ref_start)
        now_f, pops, waited, dead = tail.tolist()
        assert now_f == ref_start[-1] and dead == 0
        assert (waited > 0) == (node_gib < 1)
