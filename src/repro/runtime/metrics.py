"""Counters and streaming percentile histograms.

A tiny metrics kernel (:class:`Counter`, :class:`Histogram`,
:class:`MetricsRegistry`) behind ``RuntimeTelemetry.metrics`` and
``Tracer.metrics``, mergeable across workers: histograms use *fixed
log-spaced bins*, so merging two histograms is exact bin-count addition
(associative, commutative) — per-worker registries roll up without
resampling.
Percentiles (p50/p95/p99) come from a cumulative walk over the bins; the
answer is the geometric midpoint of the rank's bin, clamped to the
observed [min, max], so a single-sample histogram reports the sample
itself exactly and every estimate carries at most one bin of relative
error (~15% at the default 16 bins/decade — plenty for latency
attribution spanning microseconds to seconds).

Zero dependencies beyond the stdlib; importable before jax is.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Iterable, Mapping

__all__ = ["Counter", "Histogram", "MetricsRegistry"]


@dataclasses.dataclass
class Counter:
    """A monotone event count."""

    value: int = 0

    def inc(self, n: int = 1) -> int:
        self.value += n
        return self.value

    def merge(self, other: "Counter") -> None:
        self.value += other.value


class Histogram:
    """Streaming histogram over fixed log-spaced bins.

    Args:
      lo: values at or below ``lo`` land in the underflow bin.
      hi: values at or above ``hi`` land in the overflow bin.
      bins_per_decade: bin resolution; percentile estimates carry at most
        one bin of relative error (``10 ** (1/bins_per_decade) - 1``).

    The bin layout is part of the histogram's identity: :meth:`merge`
    refuses mismatched layouts rather than resampling (resampling would
    break merge associativity, the property that makes per-worker
    histograms roll up exactly).
    """

    def __init__(self, lo: float = 1e-9, hi: float = 1e4,
                 bins_per_decade: int = 16) -> None:
        if not (0.0 < lo < hi):
            raise ValueError("need 0 < lo < hi")
        if bins_per_decade < 1:
            raise ValueError("bins_per_decade must be >= 1")
        self.lo = float(lo)
        self.hi = float(hi)
        self.bins_per_decade = int(bins_per_decade)
        decades = math.log10(self.hi / self.lo)
        # interior bins + one underflow + one overflow
        self._n_bins = int(math.ceil(decades * self.bins_per_decade)) + 2
        self.counts = [0] * self._n_bins
        self.n = 0
        self.total = 0.0
        self.min = math.inf
        self.max = -math.inf

    def _layout(self) -> tuple[float, float, int]:
        return (self.lo, self.hi, self.bins_per_decade)

    def _bin(self, v: float) -> int:
        if v <= self.lo:
            return 0
        if v >= self.hi:
            return self._n_bins - 1
        i = 1 + int(math.log10(v / self.lo) * self.bins_per_decade)
        return min(max(i, 1), self._n_bins - 2)

    def _bin_mid(self, i: int) -> float:
        if i <= 0:
            return self.lo
        if i >= self._n_bins - 1:
            return self.hi
        # geometric midpoint of interior bin i
        exp = (i - 0.5) / self.bins_per_decade
        return self.lo * (10.0 ** exp)

    def record(self, v: float) -> None:
        v = float(v)
        self.counts[self._bin(v)] += 1
        self.n += 1
        self.total += v
        self.min = min(self.min, v)
        self.max = max(self.max, v)

    @property
    def mean(self) -> float:
        return self.total / self.n if self.n else math.nan

    def percentile(self, p: float) -> float:
        """The p-th percentile estimate (p in [0, 100]); NaN when empty."""
        if not 0.0 <= p <= 100.0:
            raise ValueError("percentile must be in [0, 100]")
        if self.n == 0:
            return math.nan
        rank = max(1, math.ceil(self.n * p / 100.0))
        acc = 0
        for i, c in enumerate(self.counts):
            acc += c
            if acc >= rank:
                return min(max(self._bin_mid(i), self.min), self.max)
        return self.max  # unreachable: counts sum to n

    def percentiles(self, ps: Iterable[float] = (50.0, 95.0, 99.0),
                    ) -> dict[float, float]:
        return {p: self.percentile(p) for p in ps}

    def merge(self, other: "Histogram") -> None:
        if self._layout() != other._layout():
            raise ValueError(
                f"histogram layouts differ: {self._layout()} vs "
                f"{other._layout()} — merging would need resampling")
        for i, c in enumerate(other.counts):
            self.counts[i] += c
        self.n += other.n
        self.total += other.total
        self.min = min(self.min, other.min)
        self.max = max(self.max, other.max)

    def copy(self) -> "Histogram":
        h = Histogram(self.lo, self.hi, self.bins_per_decade)
        h.merge(self)
        return h

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        if self.n == 0:
            return "Histogram(empty)"
        return (f"Histogram(n={self.n}, p50={self.percentile(50):.3g}, "
                f"p95={self.percentile(95):.3g}, "
                f"p99={self.percentile(99):.3g})")


def _key(name: str, labels: Mapping[str, Any]) -> tuple:
    return (name,) + tuple(sorted((k, str(v)) for k, v in labels.items()))


class MetricsRegistry:
    """Name+label-keyed counters and histograms, mergeable across workers."""

    def __init__(self) -> None:
        self._counters: dict[tuple, Counter] = {}
        self._hists: dict[tuple, Histogram] = {}

    def counter(self, name: str, **labels: Any) -> Counter:
        key = _key(name, labels)
        c = self._counters.get(key)
        if c is None:
            c = self._counters[key] = Counter()
        return c

    def histogram(self, name: str, **labels: Any) -> Histogram:
        key = _key(name, labels)
        h = self._hists.get(key)
        if h is None:
            h = self._hists[key] = Histogram()
        return h

    def counters(self) -> dict[tuple, int]:
        return {k: c.value for k, c in sorted(self._counters.items())}

    def histograms(self) -> dict[tuple, Histogram]:
        return dict(self._hists)

    def select(self, name: str, **match: Any,
               ) -> list[tuple[dict[str, str], Counter | Histogram]]:
        """``(labels, metric)`` for every counter or histogram called
        ``name`` whose labels include ``match`` (compared as strings; a
        ``None`` value matches any), in recording order."""
        want = {k: str(v) for k, v in match.items() if v is not None}
        out = []
        for store in (self._counters, self._hists):
            for key, m in store.items():
                if key[0] != name:
                    continue
                labels = dict(key[1:])
                if all(labels.get(k) == v for k, v in want.items()):
                    out.append((labels, m))
        return out

    def total(self, name: str, **match: Any) -> int:
        """Sum of the matching counters (0 when none match)."""
        return sum(c.value for _, c in self.select(name, **match))

    def merged(self, name: str, **match: Any) -> Histogram | None:
        """A merged copy of the matching histograms; ``None`` when none
        match."""
        out: Histogram | None = None
        for _, h in self.select(name, **match):
            if out is None:
                out = h.copy()
            else:
                out.merge(h)
        return out

    def merge(self, other: "MetricsRegistry") -> None:
        for k, c in other._counters.items():
            self._counters.setdefault(k, Counter()).merge(c)
        for k, h in other._hists.items():
            if k in self._hists:
                self._hists[k].merge(h)
            else:
                self._hists[k] = h.copy()

    def reset(self) -> None:
        self._counters.clear()
        self._hists.clear()

    def summary(self) -> str:
        rows = ["metrics:"]
        for k, v in self.counters().items():
            name = k[0] + "".join(f" {a}={b}" for a, b in k[1:])
            rows.append(f"  {name}: {v}")
        for k, h in sorted(self._hists.items()):
            name = k[0] + "".join(f" {a}={b}" for a, b in k[1:])
            rows.append(f"  {name}: {h!r}")
        return "\n".join(rows)
