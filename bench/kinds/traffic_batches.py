"""``traffic_batches``: each call generates the configuration's fabric and
runs ``evaluate_traffic_batch(g, "<pattern>:rate=<rate>,samples=<samples>,
seed=<s>")`` with the program's defaults (kernel path, automatic chunking,
capacity 1); one call is one design point under a batch of demand
matrices.

The warm-up is one call of as many matrices as the engine stacks in one
pass. The comparison rebuilds every demand matrix the program drew
(warm-up included) from its seed with `bench.reference.patterns` and
compares them cell by cell, checks each matrix's row and column sums
against the servers of each router, holds the loads of the first call's
first matrix and of one matrix drawn from the seed against the plain ECMP
sum, and every metric of those two calls' matrices against plain float64
reductions of the reference loads.
"""
from __future__ import annotations

import numpy as np

from bench.kinds import Spy, fabric_off, rel_err, sub_seed
from bench.reference import counts as ref_counts
from bench.reference import fabrics, patterns


class Workload:
    unit = "design points"

    def __init__(self, config: dict, mix: dict, seed: int, spans):
        from repro.core import topology
        from repro.core.routing import assign
        from repro.core.resilience.degradation import _auto_chunk
        from repro.core.traffic import TrafficSpec, scenarios

        self.config, self.mix, self.seed, self.spans = config, mix, seed, spans
        self.samples, self.rate = int(mix["samples"]), float(mix["rate"])
        # an unknown pattern fails here, in set-up
        TrafficSpec.parse(self._spec(0, self.samples))
        # the warm-up draws one pass of the engine's stacked chunk: the
        # window's every device shape at the least host time
        self.warm_samples = _auto_chunk(config["routers"], self.samples)
        self._make = lambda: topology.make(config["family"],
                                           **config["params"])
        self._evaluate = scenarios.evaluate_traffic_batch
        # the call drawn from the seed whose matrices are compared beside
        # the first one's; a shorter window compares its last instead
        self.keep_at = 1 + sub_seed(seed, 7) % 4
        self.pick = sub_seed(seed, 8) % self.samples
        self.calls = []            # (demand seed, samples), warm-up first
        self.drawn = []            # the demand batch of every call
        self.kept = {}             # slot -> (graph, seed, metrics, loads)
        self._loads = []
        self._spies = [
            Spy(scenarios, "demand_batch",
                lambda a, k, out: self.drawn.append(out[0])),
            Spy(assign, "ecmp_demand_loads",
                lambda a, k, out: self._loads.append(out))]

    def _spec(self, demand_seed: int, samples: int) -> str:
        return (f"{self.mix['pattern']}:rate={self.rate!r},"
                f"samples={samples},seed={demand_seed}")

    def _point(self, demand_seed: int, samples: int):
        self.calls.append((demand_seed, samples))
        self._loads = []
        with self.spans("bench.generate"):
            g = self._make()
        return g, self._evaluate(g, self._spec(demand_seed, samples))

    def warmup(self) -> None:
        self._point(sub_seed(self.seed, 0), self.warm_samples)

    def step(self, i: int) -> int:
        demand_seed = sub_seed(self.seed, 1, i)
        g, metrics = self._point(demand_seed, self.samples)
        if i == 0 or i <= self.keep_at:
            self.kept[min(i, 1)] = (g, demand_seed, metrics, self._loads)
        return 1

    def close(self) -> None:
        for spy in self._spies:
            spy.restore()

    def check(self) -> dict:
        """The compared numbers, from the reference run over the fabric
        built from its construction."""
        from repro.core.traffic import TRAFFIC_METRICS

        adj = fabrics.build(self.config)
        n = adj.shape[0]
        counts = patterns.server_counts(self.config)
        want_sums = self.rate * counts
        out = {"fabric_cells_off": 0, "demand_cells_off": 0,
               "demand_sums_off": 0}
        if len(self.drawn) != len(self.calls):
            out["demand_cells_off"] = n * n * self.samples * len(self.calls)
        for (demand_seed, samples), got in zip(self.calls, self.drawn):
            got = np.asarray(got)
            flows = patterns.server_flows(counts, demand_seed, samples)
            if got.shape != (samples, n, n):
                out["demand_cells_off"] += n * n * samples
                continue
            for sample, (pairs, count) in zip(got, flows):
                out["demand_cells_off"] += _cells_off(sample, pairs,
                                                      count * self.rate)
            out["demand_sums_off"] += int(
                np.count_nonzero(got.sum(axis=-1) != want_sums)
                + np.count_nonzero(got.sum(axis=-2) != want_sums))

        ref = ref_counts.path_counts(adj, slack=False)
        links = int(adj.sum())
        loads_err, traffic_err = 0.0, 0.0
        for slot, (g, demand_seed, metrics, loads) in self.kept.items():
            out["fabric_cells_off"] += fabric_off(g, adj)
            j = 0 if slot == 0 else self.pick
            demand = patterns.server_permutation(counts, self.rate,
                                                 demand_seed, self.samples)
            for i, d in enumerate(demand):
                # float32 demand: the same share (a correctly rounded
                # quotient of exact integers) at half the host traffic
                ref_loads = ref_counts.ecmp_loads(adj, ref["dist"],
                                                  ref["mult"],
                                                  d.astype(np.float32))
                if i == j:
                    got = _matrix(loads, i)
                    loads_err = max(loads_err, float("inf") if got is None
                                    else rel_err(got, ref_loads))
                want = patterns.traffic_metrics(ref_loads, ref["dist"], d,
                                                links)
                for key in TRAFFIC_METRICS:
                    have = metrics.get(key)
                    traffic_err = max(traffic_err, float("inf")
                                      if have is None or len(have) <= i
                                      else rel_err(have[i], want[key]))
        out["loads_rel_err"] = loads_err if self.kept else float("inf")
        out["traffic_rel_err"] = traffic_err if self.kept else float("inf")
        return out


def _cells_off(got: np.ndarray, pairs: np.ndarray, want: np.ndarray) -> int:
    """Cells of ``got`` that differ from the matrix holding ``want`` at the
    flat indices ``pairs`` and 0 elsewhere."""
    flat = got.reshape(-1)
    at = flat[pairs]
    return int(np.count_nonzero(at != want)
               + np.count_nonzero(flat) - np.count_nonzero(at))


def _matrix(chunks: list, i: int):
    """Matrix ``i`` of a call's loads, returned in chunks of the batch."""
    for chunk in chunks:
        if i < len(chunk):
            return chunk[i]
        i -= len(chunk)
    return None
