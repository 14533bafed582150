"""``design_points``: each call generates the configuration's fabric and
runs ``AnalysisEngine(g, mesh=None, seed=...).report(stages)`` with the
mix's ``stages`` (null for the default report); one call is one design
point.

The comparison holds the first design point and one drawn from the seed
(dist, mult, the slack counts and the ECMP loads where the stages make
them) cell by cell against the reference, and every design point's report
against the reference's reductions.
"""
from __future__ import annotations

import numpy as np

from bench.kinds import Spy, cells_off, fabric_off, rel_err, sub_seed
from bench.reference import counts as ref_counts
from bench.reference import fabrics, reports

#: report keys compared exactly, then relatively, in every design point
_SUMMARY_INTS = ("routers", "edges", "servers", "concentration",
                 "min_degree", "max_degree")
_EXACT_KEYS = ("diameter", "exact", "path_multiplicity_min",
               "path_multiplicity_max", "path_counts_exact", "path_histogram")
_SPECTRAL_KEYS = ("fiedler_lambda2", "laplacian_lambda_max",
                  "bisection_lower_bound", "edge_expansion_lower_bound",
                  "full_bisection_edges", "diameter_upper_bound")


class Workload:
    unit = "design points"

    def __init__(self, config: dict, mix: dict, seed: int, spans):
        from repro.core import topology
        from repro.core.analysis import AnalysisEngine
        from repro.core.routing import assign

        self.config, self.mix, self.seed, self.spans = config, mix, seed, spans
        self.stages = mix.get("stages")
        self._make = lambda: topology.make(config["family"],
                                           **config["params"])
        self._engine = AnalysisEngine
        # the design point drawn from the seed whose matrices are compared
        # beside the first one; a shorter window compares its last instead
        self.keep_at = 1 + sub_seed(seed, 7) % 8
        self.reports = []          # (engine seed, report) of every point
        self.kept = {}             # slot -> (graph, engine, ECMP loads)
        self._loads = None
        self._spy = Spy(assign, "ecmp_all_pairs_loads",
                        lambda a, k, out: setattr(self, "_loads", out))

    def _point(self, engine_seed: int):
        with self.spans("bench.generate"):
            g = self._make()
        eng = self._engine(g, mesh=None, seed=engine_seed)
        return g, eng, eng.report(self.stages)

    def warmup(self) -> None:
        self._point(sub_seed(self.seed, 0))
        self._loads = None

    def step(self, i: int) -> int:
        engine_seed = sub_seed(self.seed, 1, i)
        g, eng, rep = self._point(engine_seed)
        self.reports.append((engine_seed, rep))
        if i == 0 or i <= self.keep_at:
            self.kept[min(i, 1)] = (g, eng, self._loads)
        self._loads = None
        return 1

    def close(self) -> None:
        self._spy.restore()

    def check(self) -> dict:
        """The compared numbers, from the reference run once over the
        fabric built from its construction."""
        slack = self.stages is None or "multiplicities" in self.stages
        adj = fabrics.build(self.config)
        ref = ref_counts.path_counts(adj, slack=slack)
        out = {"fabric_cells_off": 0, "dist_cells_off": 0,
               "mult_cells_off": 0}
        if slack:
            out.update(plus1_cells_off=0, plus2_cells_off=0)
        comparison = self.stages is not None and "comparison" in self.stages
        ref_loads = None
        if comparison:
            ref_loads = ref_counts.ecmp_loads(adj, ref["dist"], ref["mult"])
            out["loads_rel_err"] = 0.0
        for g, eng, loads in self.kept.values():
            out["fabric_cells_off"] += fabric_off(g, adj)
            out["dist_cells_off"] += cells_off(eng.distances(), ref["dist"])
            out["mult_cells_off"] += cells_off(eng.shortest_path_mult(),
                                               ref["mult"])
            if slack:
                paths = eng.multiplicities()
                out["mult_cells_off"] += cells_off(paths["multiplicity"],
                                                   ref["mult"])
                out["plus1_cells_off"] += cells_off(paths["plus1"],
                                                    ref["plus1"])
                out["plus2_cells_off"] += cells_off(paths["plus2"],
                                                    ref["plus2"])
            if comparison:
                err = (rel_err(loads, ref_loads) if loads is not None
                       else float("inf"))
                out["loads_rel_err"] = max(out["loads_rel_err"], err)
        out.update(self._check_reports(adj, ref, slack, comparison,
                                       ref_loads))
        return out

    def _check_reports(self, adj, ref, slack, comparison, ref_loads) -> dict:
        deg = adj.sum(axis=1, dtype=np.int64)
        c = self.config
        want = {"routers": c["routers"], "edges": c["edges"],
                "servers": c["servers"], "concentration": c["concentration"],
                "min_degree": int(deg.min()), "max_degree": int(deg.max()),
                "avg_degree": float(deg.mean())}
        want.update(reports.distance_summary(ref["dist"]))
        if slack:
            want.update(reports.multiplicity_summary(ref))
        if self.stages is None:
            want["path_histogram"] = reports.histogram(ref["dist"])
            spectral = reports.spectral(adj)
            want.update(spectral)
        if comparison:
            off = np.isfinite(ref["dist"]) & (ref["dist"] > 0)
            want["ecmp_saturation_throughput"] = 1.0 / float(ref_loads.max())
            want["path_multiplicity_mean"] = float(
                ref["mult"][off].astype(np.float64).mean())
            want.update(reports.cost_and_power(c))
        edges = fabrics.canonical_edges(adj)
        ints_off, rel, spec_rel = 0, 0.0, 0.0
        for engine_seed, rep in self.reports:
            have = dict(want)
            if self.stages is None:
                have["path_diversity_mean"] = reports.path_diversity_mean(
                    adj, ref["dist"], engine_seed)
                have.update(reports.interference(edges, ref["dist"],
                                                 engine_seed))
            for key, value in have.items():
                if key not in rep:
                    ints_off += 1
                elif key in _SUMMARY_INTS or key in _EXACT_KEYS:
                    ints_off += int(rep[key] != value)
                elif key in _SPECTRAL_KEYS:
                    spec_rel = max(spec_rel, rel_err(rep[key], value))
                else:
                    rel = max(rel, rel_err(rep[key], value))
        out = {"report_ints_off": ints_off, "report_rel_err": rel}
        if self.stages is None:
            out["spectral_rel_err"] = spec_rel
        return out
