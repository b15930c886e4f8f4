"""Bytes a Pallas kernel moves through HBM, from the shapes it is given.

A kernel reads each operand once and writes each result once, so its least
traffic is the sum of their sizes.  The trace names each kernel's result
shapes (``s32[8,10,512]``); the formulas below give the whole traffic from
them, since each kernel's operands follow from its results.  The tests hold
the formulas to the operand and result shapes of the custom calls in
programs compiled for a TPU v5e (``module_custom_calls``)."""

from __future__ import annotations

import math
import re

ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
            "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}
SHAPE = re.compile(r"\b(" + "|".join(ITEMSIZE) + r")\[([0-9,]*)\]")
DEF = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*?)\s+([\w\-]+)\((.*)$")


def shapes_of(text: str) -> list[tuple[str, tuple[int, ...]]]:
    """Every array shape written in ``text``: (dtype, dims)."""
    return [(t, tuple(int(d) for d in dims.split(",") if d)) for t, dims in SHAPE.findall(text)]


def nbytes(shapes) -> int:
    return sum(ITEMSIZE[t] * math.prod(d) for t, d in shapes)


def module_custom_calls(hlo_text: str) -> list[dict]:
    """Each custom call of a compiled HLO module: its name, target, and the
    bytes of its operands (from the instructions that define them) and of its
    results."""
    defs, calls = {}, []
    for line in hlo_text.splitlines():
        m = DEF.match(line)
        if not m:
            continue
        name, shape, op, rest = m.groups()
        defs[name] = shapes_of(shape)
        if op == "custom-call":
            operands = re.findall(r"%([\w.\-]+)", rest.split(")", 1)[0])
            target = re.search(r'custom_call_target="([^"]+)"', rest)
            calls.append({"name": name, "target": target.group(1) if target else None,
                          "operands": operands, "result": defs[name]})
    for c in calls:
        c["operand_bytes"] = nbytes([s for o in c["operands"] for s in defs[o]])
        c["result_bytes"] = nbytes(c["result"])
    return calls


def num_levels(L: int) -> int:
    return max(L.bit_length() - 1, 0) + 1


def range_max_table_bytes(result) -> int:
    """Result (..., P, L) doubling levels of (..., L) rows: the rows in, the
    levels out."""
    (t, dims), = result
    rows = math.prod(dims[:-2])
    return ITEMSIZE[t] * rows * dims[-1] * (1 + dims[-2])


KERNELS = {"range_max_table": range_max_table_bytes}
