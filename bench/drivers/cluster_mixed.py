"""Driver: online scheduling of a whole backlog on a pool whose nodes differ
in memory, call after call.

One call is ``run_cluster_batched`` over the configuration's corpus with
``node_mib`` a capacity per node, as ``cluster_backlog`` runs it; only the
check differs: the plain reference places the rows with each node held to
its own capacity (``bench/ref/mixed_ref.py``), and the ladders cap at the
largest node.  Besides the backlog's counters it reports the windows
engine's waits and congested entries by rows wider than the smallest node
(``placement_stats["waits_wide"]``, ``["wide_blocks"]``)."""

from __future__ import annotations

import numpy as np

from bench import cluster_cells as cc
from bench.drivers import cluster_backlog
from bench.ref import cluster_ref, mixed_ref


def place_ticks(rows, node_budgets, q=cluster_ref._exact) -> np.ndarray:
    """The plain reference's placement of a program policy's rows on the
    mixed pool, in ticks."""
    out = mixed_ref.place(cc.row_list(rows), node_budgets, q)
    return np.stack([out[:, 0], out[:, 1] // 2, out[:, 2] // 2], axis=1)


class Driver(cluster_backlog.Driver):
    def counters(self) -> dict:
        out = super().counters()
        last = self.calls[-1][1]
        # a program without the counters reports none (not zeros)
        for key, name in (("waits_wide", "wide_waits_per_call"), ("wide_blocks", "wide_blocks_per_call")):
            if key in last:
                out[name] = self._total(key) / len(self.calls)
        return out

    def check(self) -> list[dict]:
        """Every call of the window against the plain reference, by the
        three numbers of ``cluster_backlog``, each the worst over calls and
        policies; the reference ladder caps at the largest node and its
        placement holds each node to its own capacity."""
        p = self.p
        node_budgets = mixed_ref.budgets(p["node_mib"])
        capped = {**p, "node_mib": float(np.max(p["node_mib"]))}
        ref = {pol: cc.ref_ladder(self.corpus, pol, capped) for pol in self.policies}
        placed: dict = {}
        worst_rows, worst_w, worst_gap = 0, 0.0, 0.0
        for out, rows in zip(self.outputs, self.calls_rows):
            n_bad = 0
            for pol, (got_pl, got_w) in out.items():
                r = rows[pol]
                want_rows, want_w = ref[pol]
                worst_gap = max(worst_gap, cc.row_gap(r, want_rows))
                key = (pol, r[0].tobytes(), r[1].tobytes(), r[2].tobytes())
                if key not in placed:
                    placed[key] = place_ticks(r, node_budgets)
                n_bad += cc.mismatches(got_pl, placed[key])
                worst_w = max(worst_w, abs(got_w - want_w) / abs(want_w))
            worst_rows = max(worst_rows, n_bad)
        if len(self.calls_rows) != len(self.outputs):
            worst_rows, worst_gap = max(worst_rows, 1), 1.0
        self._failed = worst_rows
        values = {"ladder_row_gap": worst_gap, "placement_mismatches": worst_rows, "wastage_rel_gap": worst_w}
        return [
            {"name": k, "value": v, "limit": self.limits[k], "ok": v <= self.limits[k]} for k, v in values.items()
        ]
