"""Driver: online scheduling of a whole backlog, call after call.

One call is ``run_cluster_batched`` over the configuration's corpus: every
queued execution's retry ladder for every policy, then first-fit placement
of all attempt rows on the cell's nodes with the placement engine the
traffic file names.  Every call schedules the same backlog."""

from __future__ import annotations

import numpy as np

from bench import cluster_cells as cc


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, spans: bool):
        from repro.core.ksegments import KSegmentsConfig

        self.p = p = {**config["params"], **traffic["params"]}
        self.limits = traffic["limits"]
        self.policies = tuple(p["policies"])
        self.corpus = cc.corpus(config, seed)
        self.wfs = cc.to_program(self.corpus)
        self._rows: list = []
        cc.install_spans(self._rows, spans)
        self.kw = dict(
            n_nodes=p["n_nodes"],
            placement=p["placement"],
            node_mib=p["node_mib"],
            train_frac=p["train_frac"],
            max_tasks_per_type=p["max_tasks_per_type"],
            min_executions=p["min_executions"],
            ksegments_config=KSegmentsConfig(k=p["k"], error_mode=p["error_mode"]),
        )
        self.calls: list = []

    def _call(self):
        from repro.sim.cluster import run_cluster_batched

        stats: dict = {}
        return run_cluster_batched(self.wfs, self.policies, placement_stats=stats, **self.kw), stats

    def warm(self) -> None:
        self._call()
        self._rows.clear()

    def call(self) -> None:
        self.calls.append(self._call())

    def _total(self, key: str) -> int:
        return sum(st.get(key, 0) for _, st in self.calls)

    def end_to_end(self, window_s: float) -> dict:
        return {"rows_per_s": self._total("rows") / window_s}

    def counters(self) -> dict:
        last = self.calls[-1][1]
        return {
            "rows": self._total("rows"),
            "program_calls": self._total("program_calls"),
            "placement_dispatches_per_call": self._total("program_calls") / len(self.calls),
            "waits_program": self._total("waits_program"),
            "waits_host": self._total("waits_host"),
            "lanes_replayed": self._total("lanes_replayed"),
            "timeline_axis": last.get("timeline_axis"),
            "routed": "sweep" if "timeline_axis" in last else "windows",
        }

    def attempted(self) -> int:
        return int(self._total("rows"))

    def failed(self) -> int:
        return self._failed

    def release(self) -> None:
        """Keep each call's placements, wastage and attempt rows as arrays;
        drop the program's result objects."""
        self.outputs = [
            {pol: (cc.placements(res[pol]), res[pol].wastage_gib_s) for pol in self.policies}
            for res, _ in self.calls
        ]
        self.calls_rows = list(self._rows)
        self.calls = [(None, st) for _, st in self.calls]

    def check(self) -> list[dict]:
        """Every call of the window against the plain reference, by three
        numbers, each the worst over calls and policies:

        * ``ladder_row_gap`` — each attempt's allocation (boundaries, values,
          run) against the reference ladder's (``cc.row_gap``);
        * ``placement_mismatches`` — attempts whose placement differs from
          the reference's first-fit placement of the rows the call produced;
        * ``wastage_rel_gap`` — the policy's total wastage against the
          reference ladder's, relative."""
        p = self.p
        budget = int(np.floor(p["node_mib"]))
        ref = {pol: cc.ref_ladder(self.corpus, pol, p) for pol in self.policies}
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
                    placed[key] = cc.place_ticks(r, p["n_nodes"], budget)
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
