"""Plain reference of first-fit placement on a pool whose nodes differ in
memory: each node is held to its own capacity.

Written for the benchmark from the semantics the program documents; it
imports nothing of the program.  The retry ladders are
``cluster_ref.ladder_rows`` capped at the largest node, and a node's
reservations are ``cluster_ref._Node``, whose ``fits`` takes the budget
per call; only the loop that walks the nodes is new here.

``q`` rounds what a computation stores; the default keeps float64.  The
control passes a rounding to bfloat16 (see ``bench/control_mixed.py``).
"""

from __future__ import annotations

import heapq

import numpy as np

from bench.ref import cluster_ref


def budgets(node_mib) -> np.ndarray:
    """Each node's capacity in whole MiB, rounded down (int64)."""
    return np.floor(np.asarray(node_mib, dtype=np.float64)).astype(np.int64)


def place(rows, node_budgets, q=cluster_ref._exact):
    """First-fit placement of attempt rows in order, over the nodes in
    their given order, each node against its own budget (``node_budgets``,
    MiB).  A row is fit-checked over its probe window from the clock and
    occupies its node for its run; when no node fits, the clock moves to
    the next completion.  Returns (node, start key, end key) per row."""
    k = max(len(r[0]) for r in rows)
    caps = [int(c) for c in node_budgets]
    nodes = [cluster_ref._Node(k, q) for _ in caps]
    done: list[int] = []  # completion keys
    now, expired = 0, -1
    out = np.empty((len(rows), 3), dtype=np.int64)
    for r, (b, v, run, probe) in enumerate(rows):
        b, v = cluster_ref._pad(np.asarray(b, dtype=np.int64), np.asarray(v, dtype=np.int64), k)
        while True:
            if now != expired:
                for nd in nodes:
                    nd.expire(now)
                expired = now
            hit = next(
                (i for i, (nd, cap) in enumerate(zip(nodes, caps)) if nd.fits(b, v, now, int(probe), cap)),
                None,
            )
            if hit is not None:
                break
            now = max(now, heapq.heappop(done)) if done else now + 2 * cluster_ref.TICKS_PER_S
        end = now + int(run)
        nodes[hit].add(now, end, b, v)
        heapq.heappush(done, end)
        out[r] = hit, now, end
    return out
