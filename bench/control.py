#!/usr/bin/env python3
"""The control: the plain reference put in the program's place, computed in
bfloat16, read by the same numbers a run compares.  Not part of a run.

    python bench/control.py --workload <cell> --seeds 11,12,13

For each seed it builds the cell's inputs as a run does, then computes the
reference twice — exactly, and with what it stores rounded to bfloat16 — and
prints each compared number for the bfloat16 twin.  A limit has to lie below
these readings (the upper ones) and above what sound runs of the program
read (the lower ones).

The ladder's regression sums, offsets and predictions, and the
placement's demand sums, are rounded to bfloat16.  ``ladder_row_gap``
compares each policy's bfloat16 attempt rows with the exact ladder's, and
``wastage_rel_gap`` its wastage; ``placement_mismatches`` compares the
bfloat16 placement of the bfloat16 rows with the exact placement of the
same rows.

It runs on the host alone; the machine's accelerator is not touched.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def bf16(x):
    """Round to bfloat16 and back to float64."""
    import ml_dtypes

    return np.asarray(x, dtype=np.float64).astype(ml_dtypes.bfloat16).astype(np.float64)


def _cell(workload: str, overrides: dict | None = None):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = next(w for w in spec["workloads"] if w["name"] == workload)
    with open(os.path.join(BENCH, "configs", f"{cell['config']}.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", f"{cell['traffic']}.json")) as f:
        traffic = json.load(f)
    if overrides:
        from bench.run_cell import _merge

        config = _merge(config, overrides.get("config", {}))
        traffic = _merge(traffic, overrides.get("traffic", {}))
    return config, traffic


def readings(workload: str, seed: int, overrides: dict | None = None) -> dict:
    """The control's reading of each number a run of a cluster cell compares."""
    from bench import cluster_cells as cc
    from bench.ref import cluster_ref

    config, traffic = _cell(workload, overrides)
    p = {**config["params"], **traffic["params"]}
    corpus = cc.corpus(config, seed)
    budget = int(np.floor(p["node_mib"]))
    gap, row_gap, bad = 0.0, 0.0, 0
    for pol in p["policies"]:
        exact, w_ref = cc.ref_ladder(corpus, pol, p)
        low, w_ctl = cc.ref_ladder(corpus, pol, p, q=bf16)
        gap = max(gap, abs(w_ctl - w_ref) / w_ref)
        row_gap = max(row_gap, cc.row_gap(low, exact))
        rows = cc.row_list(low)
        on_exact = cluster_ref.place(rows, p["n_nodes"], budget)
        on_low = cluster_ref.place(rows, p["n_nodes"], budget, q=bf16)
        bad += int(np.sum(np.any(on_exact != on_low, axis=1)))
    return {"ladder_row_gap": row_gap, "placement_mismatches": bad, "wastage_rel_gap": float(gap)}


def main() -> int:
    ap = argparse.ArgumentParser(description="Read the bfloat16 control of one cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    args = ap.parse_args()
    sys.path[:0] = [ROOT]
    for s in args.seeds.split(","):
        t0 = time.perf_counter()
        r = readings(args.workload, int(s))
        print(json.dumps({"workload": args.workload, "seed": int(s), "control": r,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
