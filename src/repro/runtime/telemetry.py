"""Execution telemetry: measured per-category traffic -> ``CategoryProfile``s.

The planner (``repro.core.planner``) prices offload from a workload profile.
The seed repo fed it *hand-written* profiles (or ``OpProfiler`` brackets the
caller had to place manually).  The runtime records the same quantities as a
side effect of executing requests — call counts, boundary sample counts,
wall time — keyed by ``(category, backend)``, so after any traffic has
flowed through the :class:`~repro.runtime.executor.OffloadExecutor` the
observed workload can be handed straight back to ``plan_offload``:

    telemetry.start()
    ... route traffic through the executor ...
    telemetry.stop()
    plan = plan_offload(telemetry.profiles(), spec)

closing the paper's profile -> plan -> execute -> re-profile loop.

``host_s`` in an emitted profile prefers wall time measured on the digital
backends (``host`` / ``ideal``) because that is the quantity the planner
compares accelerator pricing against; a category observed only through the
optical-sim backend falls back to its simulated wall time (flagged via
:meth:`RuntimeTelemetry.host_timed`).
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Sequence

from repro.core.accelerator import StepCost
from repro.core.planner import CategoryProfile
from repro.runtime.metrics import Histogram, MetricsRegistry

__all__ = ["BackendStats", "RuntimeTelemetry"]

# Backends whose measured wall time is honest *host* time for planning
# (sharded-over-host still executes digitally, scattered or not).
_HOST_LIKE = ("host", "ideal", "sharded-host", "sharded-ideal")


@dataclasses.dataclass
class BackendStats:
    """Accumulated traffic for one (category, backend) pair."""

    calls: int = 0            # logical offload requests
    invocations: int = 0      # accelerator dispatches (batches) serving them
    samples_in: int = 0       # scalars that crossed (or would cross) the DAC
    samples_out: int = 0      # scalars back through the ADC
    wall_s: float = 0.0       # measured execution wall time
    bytes_in: int = 0         # measured operand bytes staged per dispatch
    bytes_out: int = 0        # measured result bytes read back
    modeled: StepCost = StepCost(0.0, 0.0, 0.0, 0.0)
    # per-tile samples: invocation depth (calls coalesced into ONE
    # dispatched stack — the tile size under memory-budgeted tiling) ->
    # how many invocations dispatched at that depth
    tiles: dict = dataclasses.field(default_factory=dict)

    def add(self, *, calls: int, samples_in: int, samples_out: int,
            wall_s: float, modeled: StepCost | None,
            bytes_in: int = 0, bytes_out: int = 0) -> None:
        self.calls += calls
        self.invocations += 1
        self.samples_in += samples_in
        self.samples_out += samples_out
        self.wall_s += wall_s
        self.bytes_in += bytes_in
        self.bytes_out += bytes_out
        self.tiles[calls] = self.tiles.get(calls, 0) + 1
        if modeled is not None:
            self.modeled = self.modeled + modeled


# How many recent submit timestamps back the arrival-rate estimate (enough
# to smooth Poisson burstiness, few enough to track a changing rate).
_ARRIVAL_WINDOW = 64


class RuntimeTelemetry:
    """Records executor traffic and emits measured ``CategoryProfile``s.

    Every runtime event lands once, in :attr:`metrics` (a
    :class:`MetricsRegistry` of labelled counters and histograms):

      ``faults{category, kind}``         error / straggle / drift /
                                         device_loss / fallback / reroute
      ``recovery_s{category}``           first fault to a correct result
      ``invocation_wall_s{category, backend}``  per-invocation wall
      ``window_occupancy{category, backend}``   in-flight at dispatch
      ``residency{category, event}``     hit / miss / eviction / ...
      ``delta_flip{category}``, ``delta_full{category}``  write staging
      ``device_invocations|device_samples_in|device_samples_out
        {category, backend, device}``    sharded per-device traffic
      ``retries``, ``release``, ``quarantines``, ``placements``

    With a ``tracer`` attached, the event methods (:meth:`fault`,
    :meth:`fallback`, :meth:`quarantined`) also emit the event's instant or
    span on the tracer; without one they only count.  ``stats`` stays a
    dataclass per ``(category, backend)`` because it sums a ``StepCost``.
    """

    def __init__(self, tracer=None) -> None:
        self.stats: dict[tuple[str, str], BackendStats] = \
            collections.defaultdict(BackendStats)
        # category -> recent submit timestamps (the arrival process itself,
        # recorded at submit rather than dispatch so held traffic still has
        # an honest rate estimate)
        self._submits: dict[str, collections.deque[float]] = \
            collections.defaultdict(
                lambda: collections.deque(maxlen=_ARRIVAL_WINDOW))
        self.metrics = MetricsRegistry()
        # the owning executor's Tracer (None = tracing off)
        self.tracer = tracer
        self._t0: float | None = None
        self._window_s: float = 0.0
        self._in_window_s: float = 0.0  # recorded wall inside the window

    # -- whole-run window (for the non-offloadable 'other' bucket) -----------
    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self) -> float:
        """Close the measurement window; idempotent.  ``stop`` without a
        matching ``start`` (teardown paths can hit this — an example's
        ``finally`` block, a reset mid-window) is a no-op returning the
        accumulated window, not an error."""
        if self._t0 is not None:
            self._window_s += time.perf_counter() - self._t0
            self._t0 = None
        return self._window_s

    @property
    def window_s(self) -> float:
        return self._window_s

    # -- arrival process (the scheduler's admission signal) -------------------
    def note_submit(self, category: str, t: float | None = None) -> None:
        """Record one offload submission at time ``t`` (the executor stamps
        its own clock so submit ages and arrival rates share a timebase)."""
        self._submits[category].append(
            time.perf_counter() if t is None else t)

    def arrival_rate(self, category: str) -> float:
        """Estimated submit arrival rate for ``category`` in calls/second,
        from the recent submit timestamps (0.0 until two arrivals have been
        seen — no estimate is *no* claim, not a claim of zero traffic; the
        scheduler treats it as "hold until the deadline says otherwise").

        A burst of simultaneous submits (span ~0) estimates ``inf``:
        the next arrival is expected immediately, so waiting is free."""
        ts = self._submits.get(category)
        if ts is None or len(ts) < 2:
            return 0.0
        span = ts[-1] - ts[0]
        if span <= 0.0:
            return float("inf")
        return (len(ts) - 1) / span

    # -- recording (called by the executor and the sharded backend) ----------
    def record(self, category: str, backend: str, *, calls: int,
               samples_in: int, samples_out: int, wall_s: float,
               modeled: StepCost | None = None,
               per_device: Sequence[tuple[int, int]] | None = None,
               bytes_in: int = 0, bytes_out: int = 0) -> None:
        self.stats[(category, backend)].add(
            calls=calls, samples_in=samples_in, samples_out=samples_out,
            wall_s=wall_s, modeled=modeled, bytes_in=bytes_in,
            bytes_out=bytes_out)
        m = self.metrics
        m.histogram("invocation_wall_s", category=category,
                    backend=backend).record(wall_s)
        for d, (s_in, s_out) in enumerate(per_device or ()):
            labels = dict(category=category, backend=backend, device=d)
            m.counter("device_invocations", **labels).inc()
            m.counter("device_samples_in", **labels).inc(int(s_in))
            m.counter("device_samples_out", **labels).inc(int(s_out))
        if self._t0 is not None:  # only in-window traffic offsets 'other'
            self._in_window_s += wall_s

    def count(self, name: str, n: int = 1, **labels) -> None:
        """Add ``n`` to the counter ``name{labels}``."""
        self.metrics.counter(name, **labels).inc(n)

    def fault(self, category: str, kind: str, *, lane: str = "sched",
              parent=None, **attrs) -> None:
        """One fault event (the executor's retry path, the watchdog, the
        sharded backend's per-device recovery, the drift check): counted in
        ``faults{category, kind}`` and, traced, a ``fault`` instant."""
        self.count("faults", category=category, kind=kind)
        if self.tracer is not None:
            self.tracer.instant("fault", lane=lane, parent=parent,
                                category=category, kind=kind, **attrs)

    def fallback(self, category: str, *, backend: str, to: str, reason: str,
                 kind: str | None = "fallback", parent=None) -> None:
        """A dispatch served by ``to`` in place of ``backend``: counted in
        ``faults{category, kind}`` (``"fallback"`` after retry exhaustion,
        ``"reroute"`` around a quarantine; ``None`` when the fault that
        forced it was already counted) and, traced, a ``fallback``
        instant."""
        if kind is not None:
            self.count("faults", category=category, kind=kind)
        if self.tracer is not None:
            self.tracer.instant("fallback", lane="sched", parent=parent,
                                category=category, backend=backend, to=to,
                                reason=reason)

    def quarantined(self, ev, *, lane: str = "sched", parent=None) -> None:
        """A :class:`~repro.runtime.faults.QuarantineEvent`: counted in
        ``quarantines{reason}`` and, traced, a ``quarantine`` span as long
        as the window."""
        self.count("quarantines", reason=ev.reason)
        if self.tracer is not None:
            q0 = self.tracer.now()
            self.tracer.record("quarantine", q0, q0 + (ev.until - ev.t),
                               lane=lane, kind="async", parent=parent,
                               key=str(ev.key), reason=ev.reason,
                               level=ev.level)

    def recovered(self, category: str, dt_s: float) -> None:
        """One recovery latency: the span from a dispatch's first fault to
        the caller having a correct result again."""
        self.metrics.histogram("recovery_s", category=category).record(
            max(dt_s, 0.0))

    def note_window(self, category: str, backend: str, *,
                    in_flight: int) -> None:
        """One dispatch's pipeline-window occupancy for the ``(category,
        backend)`` engine: how many of its invocations were in flight as
        this one entered the window, itself included."""
        self.metrics.histogram("window_occupancy", category=category,
                               backend=backend).record(in_flight)

    def window_occupancy(self, category: str | None = None,
                         backend: str | None = None) -> float:
        """Mean in-flight-at-dispatch occupancy across the matching engine
        windows (dispatch-weighted); 0.0 when nothing dispatched."""
        h = self.metrics.merged("window_occupancy", category=category,
                                backend=backend)
        return h.total / h.n if h is not None and h.n else 0.0

    def note_residency(self, category: str, event: str) -> None:
        """Count one residency-cache event ("hit" / "miss" / "eviction" /
        "invalidation" / "delta") against ``category``."""
        self.count("residency", category=category, event=event)

    def residency_hit_rate(self, category: str | None = None,
                           ) -> float | None:
        """hits / (hits + misses) for ``category`` (overall when None);
        ``None`` before any residency lookup — no traffic is no claim,
        and the router treats it as rate 0."""
        hits = self.metrics.total("residency", category=category,
                                  event="hit")
        total = hits + self.metrics.total("residency", category=category,
                                          event="miss")
        return None if total == 0 else hits / total

    def note_delta(self, category: str, *,
                   flip_fraction: float | None = None) -> None:
        """Count one *written* (non-hit) operand staging against
        ``category``: with a ``flip_fraction`` it was a delta-encoded
        partial write at that measured LSB flip fraction; with ``None``
        it re-staged in full (first sighting, or a flip fraction past
        the delta threshold)."""
        if flip_fraction is None:
            self.count("delta_full", category=category)
        else:
            self.metrics.histogram("delta_flip", category=category).record(
                max(0.0, min(1.0, float(flip_fraction))))

    def delta_rate(self, category: str | None = None) -> float | None:
        """delta writes / all writes for ``category`` (overall when None);
        ``None`` before any write-side staging was classified — no traffic
        is no claim, and the router treats it as rate 0."""
        h = self.metrics.merged("delta_flip", category=category)
        frames = h.n if h is not None else 0
        total = frames + self.metrics.total("delta_full", category=category)
        return None if total == 0 else frames / total

    def mean_flip_fraction(self, category: str | None = None) -> float:
        """Mean LSB flip fraction across the observed delta writes for
        ``category`` (overall when None); 0.0 when none occurred."""
        h = self.metrics.merged("delta_flip", category=category)
        return h.total / h.n if h is not None and h.n else 0.0

    @property
    def fault_counts(self) -> dict[str, collections.Counter]:
        """Read-only ``{category: Counter(kind -> events)}`` view of
        ``faults``."""
        out: dict[str, collections.Counter] = \
            collections.defaultdict(collections.Counter)
        for labels, c in self.metrics.select("faults"):
            out[labels["category"]][labels["kind"]] += c.value
        return out

    def faults_total(self, category: str | None = None) -> int:
        """Total fault events observed (for ``category``, or overall)."""
        return self.metrics.total("faults", category=category)

    def recovery_stats(self, category: str | None = None) -> dict | None:
        """``{n, mean_s, p50_s, p95_s}`` of recovery latency for
        ``category`` (merged across categories when None); ``None`` when
        nothing ever needed recovering."""
        h = self.metrics.merged("recovery_s", category=category)
        if h is None or h.n == 0:
            return None
        return {"n": h.n, "mean_s": h.total / h.n,
                "p50_s": h.percentile(50), "p95_s": h.percentile(95)}

    def discount_window(self, wall_s: float) -> None:
        """Exclude ``wall_s`` of measurement overhead (e.g. the fidelity
        checker's shadow reference run) from the window's 'other' bucket —
        it elapsed inside the window but is not workload."""
        if self._t0 is not None:
            self._in_window_s += wall_s

    # -- views ----------------------------------------------------------------
    def categories(self) -> tuple[str, ...]:
        seen: dict[str, None] = {}
        for cat, _ in self.stats:
            seen.setdefault(cat)
        return tuple(seen)

    def host_timed(self, category: str) -> bool:
        """True when ``category`` has wall time from a host-like backend."""
        return any(self.stats[(category, b)].wall_s > 0.0
                   for b in _HOST_LIKE if (category, b) in self.stats)

    def _category_rollup(self, category: str) -> tuple[int, int, int, float]:
        calls = s_in = s_out = host_calls = 0
        host_s = other_s = 0.0
        for (cat, backend), st in self.stats.items():
            if cat != category:
                continue
            calls += st.calls
            s_in += st.samples_in
            s_out += st.samples_out
            if backend in _HOST_LIKE:
                host_s += st.wall_s
                host_calls += st.calls
            else:
                other_s += st.wall_s
        if host_s > 0.0 and host_calls > 0:
            # price ALL observed calls at the measured host rate, so a
            # category that later ran offloaded is not under-weighted on
            # the host side of the next replan
            est = host_s * (calls / host_calls)
        else:
            est = other_s
        return calls, s_in, s_out, est

    def recorded_s(self) -> float:
        return sum(st.wall_s for st in self.stats.values())

    def samples_per_call(self, category: str) -> tuple[int, int]:
        """Observed mean boundary traffic per call: (n_in, n_out) scalars.

        This is what adaptive batching prices invocations from — the
        per-call DAC/ADC sample counts the category's traffic actually
        exhibited, not a hand-written workload guess."""
        calls = s_in = s_out = 0
        for (cat, _backend), st in self.stats.items():
            if cat != category:
                continue
            calls += st.calls
            s_in += st.samples_in
            s_out += st.samples_out
        if calls <= 0:
            return (0, 0)
        return (s_in // calls, s_out // calls)

    def device_samples(self, category: str) -> dict[int, tuple[int, int]]:
        """Per-device aggregated boundary traffic for ``category``:
        ``{device_index: (samples_in, samples_out)}`` summed across
        backends.  Empty when the category never ran sharded."""
        out: dict[int, list[int]] = {}
        for j, name in enumerate(("device_samples_in", "device_samples_out")):
            for labels, c in self.metrics.select(name, category=category):
                out.setdefault(int(labels["device"]), [0, 0])[j] += c.value
        return {i: (s[0], s[1]) for i, s in sorted(out.items())}

    def devices_observed(self, category: str | None = None) -> int:
        """Widest device fan-out any recorded invocation used (1 when no
        sharded traffic was recorded)."""
        seen: dict[tuple[str, str], int] = {}
        for labels, _ in self.metrics.select("device_invocations",
                                             category=category):
            key = (labels["category"], labels["backend"])
            seen[key] = seen.get(key, 0) + 1
        return max([1, *seen.values()])

    def tile_sizes_observed(self, category: str) -> dict[int, int]:
        """Per-tile samples: ``{invocation depth: dispatch count}`` merged
        across backends — the tile granularity the executor *actually*
        dispatched at.  A monolithic K-deep flush shows ``{K: 1}``; the
        same group streamed through a ``tile_k=4`` budget shows
        ``{4: K//4, ...}`` (plus a ragged tail entry).  Tests assert the
        budget-chosen ``tile_k`` against this — the tile the planner
        picked must be the tile the boundary saw."""
        out: dict[int, int] = {}
        for (cat, _backend), st in self.stats.items():
            if cat != category:
                continue
            for size, count in st.tiles.items():
                out[size] = out.get(size, 0) + count
        return dict(sorted(out.items()))

    def latency_histogram(self, category: str,
                          backend: str | None = None) -> Histogram | None:
        """Per-invocation wall-time histogram for ``(category, backend)``
        — or, with ``backend=None``, a merged copy across every backend
        that served the category.  ``None`` when no traffic recorded."""
        return self.metrics.merged("invocation_wall_s", category=category,
                                   backend=backend)

    def percentiles(self, category: str, backend: str | None = None,
                    ps: Sequence[float] = (50.0, 95.0, 99.0),
                    ) -> dict[float, float]:
        """p50/p95/p99 (by default) of per-invocation wall time for
        ``(category, backend)`` — NaN-valued when no traffic recorded, so
        SLO dashboards can render the absence without special-casing."""
        h = self.latency_histogram(category, backend)
        if h is None:
            return {p: float("nan") for p in ps}
        return h.percentiles(ps)

    def bytes_per_frame(self, category: str) -> int:
        """Measured mean staged bytes per call (operand in + result out) —
        the ground truth the tiling model's working-set estimate is judged
        against.  0 until traffic with byte accounting has flowed."""
        calls = total = 0
        for (cat, _backend), st in self.stats.items():
            if cat != category:
                continue
            calls += st.calls
            total += st.bytes_in + st.bytes_out
        if calls <= 0:
            return 0
        return total // calls

    def observed_occupancy(self, category: str | None = None) -> int:
        """Average calls coalesced per invocation in the observed traffic,
        per category (or globally when ``category`` is None).

        This is the amortization the workload *actually achieved* — pricing
        a plan at a deeper batch than a category's traffic exhibits would
        credit the accelerator with handshake amortization it never gets,
        and one category's deep batches must not subsidize another's
        serial calls."""
        calls = invocations = 0
        for (cat, _backend), st in self.stats.items():
            if category is not None and cat != category:
                continue
            calls += st.calls
            invocations += st.invocations
        if invocations <= 0:
            return 1
        return max(1, round(calls / invocations))

    # -- the loop-closing output ----------------------------------------------
    def profiles(self, include_other: bool = True) -> list[CategoryProfile]:
        """Observed traffic as planner input.

        One profile per executed category, plus (when a start/stop window was
        used) an ``other`` profile holding the non-offloadable remainder of
        the window — exactly the shape ``plan_offload`` expects.
        """
        out: list[CategoryProfile] = []
        for cat in self.categories():
            calls, s_in, s_out, host_s = self._category_rollup(cat)
            out.append(CategoryProfile(cat, host_s=host_s, calls=max(calls, 1),
                                       samples_in=s_in, samples_out=s_out))
        if include_other and self._window_s > 0.0:
            other = max(self._window_s - self._in_window_s, 0.0)
            out.append(CategoryProfile("other", host_s=other))
        return out

    def merge(self, other: "RuntimeTelemetry") -> None:
        for key, st in other.stats.items():
            mine = self.stats[key]
            mine.calls += st.calls
            mine.invocations += st.invocations
            mine.samples_in += st.samples_in
            mine.samples_out += st.samples_out
            mine.wall_s += st.wall_s
            mine.bytes_in += st.bytes_in
            mine.bytes_out += st.bytes_out
            mine.modeled = mine.modeled + st.modeled
            for size, count in st.tiles.items():
                mine.tiles[size] = mine.tiles.get(size, 0) + count
        for cat, ts in other._submits.items():
            mine_ts = self._submits[cat]
            merged = sorted(list(mine_ts) + list(ts))
            mine_ts.clear()
            mine_ts.extend(merged[-_ARRIVAL_WINDOW:])
        self.metrics.merge(other.metrics)
        self._window_s += other._window_s
        self._in_window_s += other._in_window_s

    def reset(self) -> None:
        self.stats.clear()
        self._submits.clear()
        self.metrics.reset()
        self._t0 = None
        self._window_s = 0.0
        self._in_window_s = 0.0

    def summary(self) -> str:
        rows = ["telemetry:"]
        for (cat, backend), st in sorted(self.stats.items()):
            rows.append(
                f"  {cat:>8}/{backend:<11} calls={st.calls} "
                f"batches={st.invocations} in={st.samples_in} "
                f"out={st.samples_out} wall={st.wall_s:.4g}s "
                f"modeled={st.modeled.total_s:.4g}s "
                f"(conv {st.modeled.conversion_s:.4g}s)")
            if len(st.tiles) > 1:  # tiled / mixed-depth dispatch is news
                parts = [f"depth{s} x{c}"
                         for s, c in sorted(st.tiles.items())]
                rows.append("           tiles: " + "; ".join(parts))
        if self._window_s:
            rows.append(f"  window={self._window_s:.4g}s "
                        f"recorded={self.recorded_s():.4g}s")
        rows.append(self.metrics.summary())
        return "\n".join(rows)
