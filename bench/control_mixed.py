#!/usr/bin/env python3
"""The control of a mixed-pool cell: the plain reference put in the
program's place, computed in bfloat16, read by the same numbers a run
compares.  Not part of a run.

    python bench/control_mixed.py --workload sched.nfcore.mix16n --seeds 11,12,13

As ``bench/control.py`` does for a uniform pool: for each seed it builds the
cell's inputs as a run does, then computes the reference twice — exactly,
and with what it stores rounded to bfloat16 (``control.bf16``) — and prints
each compared number for the bfloat16 twin.  The ladders cap at the largest
node; the placement is ``mixed_ref.place``, each node against its own
capacity.  It runs on the host alone; the machine's accelerator is not
touched.
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


def readings(workload: str, seed: int, overrides: dict | None = None) -> dict:
    """The control's reading of each number a run of a mixed-pool cell
    compares."""
    from bench import cluster_cells as cc
    from bench.control import _cell, bf16
    from bench.ref import mixed_ref

    config, traffic = _cell(workload, overrides)
    p = {**config["params"], **traffic["params"]}
    node_budgets = mixed_ref.budgets(p["node_mib"])
    p["node_mib"] = float(np.max(p["node_mib"]))  # the ladders' cap
    corpus = cc.corpus(config, seed)
    gap, row_gap, bad = 0.0, 0.0, 0
    for pol in p["policies"]:
        exact, w_ref = cc.ref_ladder(corpus, pol, p)
        low, w_ctl = cc.ref_ladder(corpus, pol, p, q=bf16)
        gap = max(gap, abs(w_ctl - w_ref) / w_ref)
        row_gap = max(row_gap, cc.row_gap(low, exact))
        rows = cc.row_list(low)
        on_exact = mixed_ref.place(rows, node_budgets)
        on_low = mixed_ref.place(rows, node_budgets, q=bf16)
        bad += int(np.sum(np.any(on_exact != on_low, axis=1)))
    return {"ladder_row_gap": row_gap, "placement_mismatches": bad, "wastage_rel_gap": float(gap)}


def main() -> int:
    ap = argparse.ArgumentParser(description="Read the bfloat16 control of one mixed-pool cell.")
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
