"""TrafficSpec — the one demand language every engine speaks.

Before this module the repo had three parallel demand conventions:
``workload.make_traffic(pattern: str)`` (flow pairs), ``Workload
.demand_matrix`` (pairs -> matrix), and ``routing.assign.demand_matrix``
(the raw scatter). A :class:`TrafficSpec` replaces all three with one
spec -> pairs / matrix / stacked-batch path:

* ``spec.batch(g, samples=S)``  -> ``(S, n, n)`` stacked demand matrices,
  the native input of the batched scenario engine (`traffic.scenarios`);
* ``spec.matrix(g)``            -> one ``(n, n)`` matrix (sample 0);
* ``spec.pairs(g)``             -> ``(flows, 2)`` sampled flow pairs for
  the per-flow samplers — *exactly* ``flows`` pairs, never fewer: pairs
  are drawn from the pattern's demand distribution, whose diagonal is
  zero by construction, so no self-pair filter can shrink the sample
  (the historical ``make_traffic`` bug).

Patterns are registered like topology families (`topology.base`): a
generator ``fn(n, rate, rng, samples, **params) -> (S, n, n) float64``
under a name; see `traffic.patterns` for the shipped suite. A pattern
registered with ``servers=True`` sends between servers, not routers: its
generator also takes ``servers``, the (n,) per-router server counts, which
:meth:`TrafficSpec.batch` reads from the graph (`Graph.server_counts`).
Specs parse from and print to the shared CLI flag grammar::

    permutation
    hotspot:zipf_a=1.4,samples=8
    permutation:flows=4096,seed=0

``name[:key=value,...]`` — ``rate``/``seed``/``samples``/``flows``/
``volume`` bind to the spec fields, every other key is passed to the
generator. ``TrafficSpec.parse(spec.describe())`` round-trips.

Unreachable-demand contract (the one place it is defined)
---------------------------------------------------------
Every load/throughput engine in this repo treats demand on the diagonal
and on unreachable pairs (``dist == inf``) as *dropped*, never routed and
never an error — partitioned graphs are first-class. The engines mask
implicitly (their level decompositions are gated on finite distance);
callers that need the dropped volume use
`routing.assign.mask_unreachable_demand`, which also owns the optional
``renormalize=True`` mode (rescale surviving entries to preserve total
volume — the resilience convention of "uniform demand over the reachable
pairs"). Entry points report the dropped fraction rather than silently
under-routing: ``dropped_demand_frac`` in the traffic engines,
``disconnected_fraction`` in `routing.throughput`, ``reachable_frac`` in
`resilience.degradation`.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Mapping, Optional, Tuple, Union

import numpy as np

__all__ = ["TrafficSpec", "as_spec", "register", "patterns", "generate",
           "pairs_to_matrix", "sample_pairs_from_matrix"]

#: generator signature: (n, rate, rng, samples, **params) -> (S, n, n) f64
PatternFn = Callable[..., np.ndarray]

_REGISTRY: Dict[str, PatternFn] = {}
#: the registered patterns whose generators take ``servers``
_SERVER_PATTERNS: set = set()

#: spec fields the flag grammar binds directly (everything else is a
#: generator parameter)
_INT_FIELDS = ("seed", "samples", "flows")
_FLOAT_FIELDS = ("rate", "volume")


def register(name: str, servers: bool = False):
    """Register a demand-pattern generator under ``name`` (decorator);
    ``servers=True`` hands it the per-router server counts."""

    def deco(fn: PatternFn) -> PatternFn:
        _REGISTRY[name] = fn
        if servers:
            _SERVER_PATTERNS.add(name)
        return fn

    return deco


def patterns() -> List[str]:
    """Registered pattern names (sorted)."""
    return sorted(_REGISTRY)


def _pattern(name: str) -> PatternFn:
    if name not in _REGISTRY:
        raise KeyError(f"unknown traffic pattern {name!r}; "
                       f"known: {patterns()}")
    return _REGISTRY[name]


def generate(name: str, n: int, rate: float = 1.0, seed: int = 0,
             samples: int = 1, servers: Optional[np.ndarray] = None,
             **params) -> np.ndarray:
    """Run the registered generator: ``(samples, n, n)`` float64 demand.

    The generator draws from ``numpy.random.default_rng([seed, tag])`` and
    nothing else, ``tag`` the first 8 bytes of the pattern's name read as a
    big-endian integer (zero-padded), mod 2^31. A server pattern needs
    ``servers``, the (n,) per-router server counts.
    """
    if n < 1:
        raise ValueError("traffic needs at least one router")
    if samples < 1:
        raise ValueError("samples must be >= 1")
    fn = _pattern(name)
    if name in _SERVER_PATTERNS:
        if servers is None:
            raise ValueError(f"pattern {name!r} sends between servers: "
                             f"give it a graph, not a router count")
        params["servers"] = np.asarray(servers, np.int64)
        if params["servers"].shape != (n,):
            raise ValueError(f"servers has shape {params['servers'].shape},"
                             f" wanted ({n},)")
    rng = np.random.default_rng([int(seed), _stable_tag(name)])
    out = fn(int(n), float(rate), rng, int(samples), **params)
    out = np.asarray(out, np.float64)
    if out.shape != (samples, n, n):
        raise RuntimeError(f"pattern {name!r} returned {out.shape}, "
                           f"wanted {(samples, n, n)}")
    return out


def _stable_tag(name: str) -> int:
    """Deterministic per-pattern seed component (hash() is salted)."""
    return int.from_bytes(name.encode()[:8].ljust(8, b"\0"), "big") % (1 << 31)


# -- pairs <-> matrix ---------------------------------------------------------

def pairs_to_matrix(n: int, pairs: np.ndarray,
                    volume: float = 1.0) -> np.ndarray:
    """(n, n) f64 demand from (F, 2) flow pairs: volume per flow, summed.

    The one pairs -> matrix primitive (``routing.assign.demand_matrix`` is
    its deprecated Graph-taking shim). Self-pairs are zeroed: self-demand
    never crosses a link.
    """
    pairs = np.asarray(pairs, np.int64)
    d = np.zeros((n, n), dtype=np.float64)
    if len(pairs):
        np.add.at(d, (pairs[:, 0], pairs[:, 1]), float(volume))
    np.fill_diagonal(d, 0.0)
    return d


def sample_pairs_from_matrix(matrix: np.ndarray, flows: int,
                             rng: np.random.Generator) -> np.ndarray:
    """Draw exactly ``flows`` (src, dst) pairs ∝ the demand matrix.

    The matrix diagonal is zero for every registered pattern, so no
    self-pair can be drawn and the returned array always has ``flows``
    rows — the contract ``make_traffic`` historically broke by filtering
    self-pairs after independent src/dst draws.
    """
    m = np.asarray(matrix, np.float64).copy()
    n = m.shape[0]
    np.fill_diagonal(m, 0.0)
    total = m.sum()
    if total <= 0:
        raise ValueError("cannot sample flows from an all-zero demand "
                         "matrix (e.g. a bursty off-phase)")
    idx = rng.choice(n * n, size=int(flows), p=(m / total).ravel())
    return np.stack([idx // n, idx % n], axis=1).astype(np.int64)


# -- the spec -----------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TrafficSpec:
    """One demand scenario: pattern + rate/seed/samples (+ flow sampling).

    ``rate`` is the per-router injection rate: every registered router
    pattern emits matrices whose live row sums equal ``rate`` (bursty rows
    are ``rate`` in an on-phase and 0 in an off-phase). For a server
    pattern it is the per-server rate, so row i sums to ``rate`` times the
    servers of router i. ``samples`` is the stacked-batch depth —
    independent draws for stochastic patterns, the time axis for
    ``bursty``, identical copies for deterministic ones.

    With ``flows`` set the spec is in *flow-sampled* mode: ``pairs()``
    draws exactly that many flows from the pattern's demand distribution
    and ``matrix()``/``batch()`` return the sampled (volume-weighted)
    matrices instead of the closed-form ones.

    ``params`` holds generator-specific knobs (``zipf_a``, ``shift``,
    ``duty``, ...) as a sorted tuple of (name, float) so specs stay
    hashable; construct with a dict, read via :attr:`extras`.
    """

    pattern: str
    rate: float = 1.0
    seed: int = 0
    samples: int = 1
    flows: Optional[int] = None
    volume: float = 1.0
    params: Tuple[Tuple[str, float], ...] = ()

    def __post_init__(self):
        p = self.params
        if isinstance(p, Mapping):
            p = tuple(sorted((str(k), float(v)) for k, v in p.items()))
        else:
            p = tuple(sorted((str(k), float(v)) for k, v in p))
        object.__setattr__(self, "params", p)
        for name, _ in p:
            if name in _INT_FIELDS or name in _FLOAT_FIELDS or \
                    name in ("pattern", "servers"):
                raise ValueError(f"{name!r} is a spec field or the graph's,"
                                 f" not a generator parameter")

    # -- construction ------------------------------------------------------
    @classmethod
    def parse(cls, text: Union[str, "TrafficSpec"]) -> "TrafficSpec":
        """Parse the shared flag grammar, e.g. ``hotspot:zipf_a=1.4``."""
        if isinstance(text, cls):
            return text
        text = str(text).strip()
        name, _, rest = text.partition(":")
        if not name:
            raise ValueError(f"empty traffic spec {text!r}")
        fields: Dict[str, object] = {}
        extras: Dict[str, float] = {}
        if rest:
            for item in rest.split(","):
                if not item:
                    continue
                key, eq, val = item.partition("=")
                key = key.strip()
                if not eq:
                    raise ValueError(f"traffic spec item {item!r} is not "
                                     f"key=value")
                if key in _INT_FIELDS:
                    fields[key] = int(val)
                elif key in _FLOAT_FIELDS:
                    fields[key] = float(val)
                else:
                    extras[key] = float(val)
        spec = cls(pattern=name, params=extras, **fields)
        _pattern(name)  # fail fast on unknown patterns
        return spec

    def describe(self) -> str:
        """Canonical flag-grammar form; ``parse(describe())`` round-trips."""
        items: List[Tuple[str, str]] = []
        default = TrafficSpec(pattern=self.pattern)
        for f in _FLOAT_FIELDS:
            v = getattr(self, f)
            if v != getattr(default, f):
                items.append((f, f"{v:g}"))
        for f in _INT_FIELDS:
            v = getattr(self, f)
            if v != getattr(default, f) and v is not None:
                items.append((f, str(int(v))))
        items.extend((k, f"{v:g}") for k, v in self.params)
        if not items:
            return self.pattern
        body = ",".join(f"{k}={v}" for k, v in sorted(items))
        return f"{self.pattern}:{body}"

    def __str__(self) -> str:  # pragma: no cover - convenience
        return self.describe()

    @property
    def extras(self) -> Dict[str, float]:
        return dict(self.params)

    def with_(self, **changes) -> "TrafficSpec":
        """`dataclasses.replace` that accepts a params dict."""
        return dataclasses.replace(self, **changes)

    def scaled(self, factor: float) -> "TrafficSpec":
        """Same scenario at ``factor`` x the injection rate (the knob the
        saturation search bisects on)."""
        return self.with_(rate=self.rate * float(factor))

    # -- materialization ---------------------------------------------------
    def _generate(self, g, samples: int) -> np.ndarray:
        graph = not isinstance(g, (int, np.integer))
        servers = (g.server_counts()
                   if graph and self.pattern in _SERVER_PATTERNS else None)
        return generate(self.pattern, g.n if graph else int(g),
                        rate=self.rate, seed=self.seed, samples=samples,
                        servers=servers, **self.extras)

    def batch(self, g, samples: Optional[int] = None) -> np.ndarray:
        """``(S, n, n)`` stacked demand matrices over graph/int ``g`` (a
        server pattern needs the graph)."""
        n = g if isinstance(g, (int, np.integer)) else g.n
        s = int(samples) if samples is not None else self.samples
        base = self._generate(g, s)
        if self.flows is None:
            return base
        rng = np.random.default_rng([int(self.seed), 0x70AD])
        out = np.zeros_like(base)
        for i in range(s):
            p = sample_pairs_from_matrix(base[i], self.flows, rng)
            out[i] = pairs_to_matrix(n, p, self.volume)
        return out

    def matrix(self, g) -> np.ndarray:
        """One ``(n, n)`` demand matrix (stacked sample 0)."""
        return self.batch(g, samples=1)[0]

    def pairs(self, g) -> np.ndarray:
        """``(flows, 2)`` sampled flow pairs — flow-sampled mode only."""
        if self.flows is None:
            raise ValueError(f"{self.describe()}: pairs() needs flows=N")
        base = self._generate(g, 1)
        rng = np.random.default_rng([int(self.seed), 0x70AD])
        return sample_pairs_from_matrix(base[0], self.flows, rng)


def as_spec(demand: Union[str, TrafficSpec]) -> TrafficSpec:
    """str | TrafficSpec -> TrafficSpec (the CLI normalization hook)."""
    return TrafficSpec.parse(demand)
