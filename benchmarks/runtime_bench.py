"""Runtime benchmark: batching amortizes the conversion boundary — for real.

Three claims, measured on the executing runtime (not just the cost model):

* **Amortization sweep** — submitting K same-shape FFT offload calls and
  letting the executor coalesce them into ONE batched invocation reduces
  both the modeled per-call conversion + interface time AND the measured
  wall time per call (the paper's §6 lever: one link handshake, one SLM
  settle, one lane-ceil residue, one dispatch round-trip, one kernel
  launch per batch instead of per call).  The ``looped_speedup`` column is
  the measured batched-vs-looped execution ratio.
* **Pipelined flush** — the executor's two-deep async flush (DAC-in of
  invocation k+1 staged while invocation k's analog+ADC compute is in
  flight) beats strictly serial dispatch-then-block crossings.
* **Telemetry round trip** — traffic profiled by the runtime itself feeds
  ``plan_offload`` and yields a plan whose offload decisions match how the
  router then executes (categories the plan offloads run on the analog
  backend, the rest stay host).
* **Trickle arrivals: holding vs drain-on-flush** — under a Poisson
  arrival process too sparse to fill a batch between flushes, the
  admission-controlled ``OffloadScheduler`` holds partially filled groups
  open across flushes (releasing on full / deadline / futile-to-wait) and
  achieves strictly higher measured occupancy — calls and boundary samples
  per conversion crossing — than the drain-every-flush regime, at a
  bounded queueing-delay cost that the modeled wall prices explicitly
  (``StepCost.hold_s``).  Arrivals ride a ``ManualClock``, so the
  admission decisions (and therefore the column) are deterministic.
* **Large frames: looped vs monolithic vs memory-budgeted tiled** — at
  512x512 the monolithic (K, H, W) stack blows the LLC off-TPU and
  batching measurably loses to looping; the memory-budgeted executor
  streams the group as ``choose_tile``-sized sub-invocations through the
  two-deep pipeline and beats both.  The row stamps the budget (bytes,
  source) and asserts the budget-chosen ``tile_k`` is the tile size the
  executor actually dispatched.
* **Traced column** — the opt-in span tracer re-runs the K-deep flush and
  reports (a) its own overhead vs the untraced executor (< 5% or the CI
  smoke fails) and (b) how much of the measured flush wall the per-stage
  charged spans reconcile (coverage ~1).
* **Chaos column** — the same offload traffic through a chaos-wrapped
  optical backend injecting a seeded fault mix (transient errors,
  stragglers, ENOB drift, device loss) at 0 / 1% / 10% per-dispatch
  rates: every frame still retires within the ENOB bound of the looped
  host baseline (retry + host fallback + drift correction), and the row
  reports goodput, fault counts, recovery-latency percentiles, and
  quarantine events.  A separate overhead row shows the rate-0 chaos
  wrapper costs < 2% on the traced wall.
* **Residency column** — a conv layer stack re-using its frames and
  kernel through the opt-in operand residency cache: the cached flush
  (every operand resident) beats the always-cold re-stage flush on the
  measured wall, the modeled hit cost carries zero write-side DAC time
  (read-side-only pricing), and the results stay bit-equal to the
  residency-off executor.
* **Sharded vs single-device** — scattering the K=16 flush group across n
  replicated simulated accelerators (each paying its own DAC/ADC boundary)
  cuts the modeled invocation wall to max-over-devices + sync: the
  streaming conversion/interface terms split n ways while every device
  still pays the frame-sync handshake.  The wall column on a single real
  device exercises the *sequential fallback* (n smaller dispatches, no
  parallel hardware — expect ~1x or below); with real devices present the
  shards scatter via ``device_put`` and the wall follows the modeled
  column.

Frames are 128x128: small enough that per-invocation dispatch/launch
overhead is a real fraction of the work (the regime §6 batching targets —
at CNN-feature-map scale the boundary dominates), while 16 of them still
pack into one 2048x2048 SLM frame (one frame-sync).

Run:  PYTHONPATH=src python -m benchmarks.runtime_bench
"""

from __future__ import annotations

import dataclasses
import json
import time

import jax
import numpy as np

from repro.runtime import (
    BATCHED_4F,
    CONV_CAPTURES,
    FidelityChecker,
    ManualClock,
    MemoryBudget,
    OffloadExecutor,
    OffloadScheduler,
    PlanRouter,
    Tracer,
    choose_tile,
    enob_error_bound,
    reconcile,
    register_chaos,
    write_trace,
)

SHAPE = (128, 128)
CALLS = 16
BENCH_JSON = "BENCH_runtime.json"

# Large-frame scenario: the regime where a monolithic (K, H, W) stack
# falls out of the LLC off-TPU (ROADMAP's last open lever) and the
# memory budget decides the staging granularity.
LARGE_SHAPE = (512, 512)
LARGE_CALLS = 16

# Chaos scenario: the fault-injection config stamped into
# BENCH_runtime.json.  Rates are per-dispatch fault probabilities; the
# schedule is seeded, so every bench run injects the identical fault
# sequence and the goodput/recovery columns are comparable across PRs.
CHAOS_RATES = (0.0, 0.01, 0.10)
CHAOS_CALLS = 48
CHAOS_SHAPE = (64, 64)
CHAOS_MAX_BATCH = 8
# seed chosen so the 10% stream provably injects within the bench's
# dispatch count (48 calls / max_batch 8 -> 6 draws; seed 2 faults at
# draw 2) — a chaos bench that never faults proves nothing
CHAOS_SEED = 2

# Trickle-arrival scenario: the scheduler config stamped into
# BENCH_runtime.json so the occupancy trajectory stays interpretable
# across PRs (change these and the column's meaning changes with them).
TRICKLE_RATE_HZ = 200.0     # mean Poisson arrival rate
TRICKLE_DEADLINE_S = 0.05   # per-call queueing-delay budget while held
TRICKLE_ARRIVALS = 48
TRICKLE_MAX_BATCH = 8
TRICKLE_SEED = 0


def _images(n: int = CALLS, shape: tuple[int, int] = SHAPE):
    key = jax.random.PRNGKey(7)
    return [jax.random.uniform(jax.random.fold_in(key, i), shape)
            for i in range(n)]


def _timed_flush(ex: OffloadExecutor, imgs, reps: int = 3) -> float:
    """Best-of-``reps`` measured wall seconds per call for one full flush."""
    best = float("inf")
    for _ in range(reps):
        handles = [ex.submit("fft", im) for im in imgs]
        t0 = time.perf_counter()
        ex.flush()
        best = min(best, (time.perf_counter() - t0) / len(handles))
    return best


def sweep(batch_sizes=(1, 2, 4, 8, 16), shape: tuple[int, int] = SHAPE,
          calls: int = CALLS) -> list[dict]:
    """Measured + modeled per-call cost vs executor batch ceiling.

    Every executor is warmed first (single-item AND batched jit shapes) so
    first-flush compilation does not masquerade as execution time.  The
    ``max_batch=1`` row is the looped baseline: one invocation per call.
    """
    imgs = _images(calls, shape)
    rows = []
    looped_wall = None
    for k in batch_sizes:
        ex = OffloadExecutor(BATCHED_4F, max_batch=k)
        ex.warm("fft", imgs[0])
        wall = _timed_flush(ex, imgs)
        # fresh telemetry for the cost-collection flush, so the reported
        # invocation count reflects exactly the CALLS submitted calls (the
        # timing reps above would otherwise inflate it)
        ex.telemetry.reset()
        handles = [ex.submit("fft", im) for im in imgs]
        ex.flush()
        # per-call share of the modeled batched invocation cost, averaged
        # over the calls (the tail batch may be smaller than k)
        per_call = [h.cost.conversion_s + h.cost.interface_s for h in handles]
        total = [h.cost.total_s for h in handles]
        if looped_wall is None:
            looped_wall = wall
        rows.append({
            "max_batch": k,
            "boundary_s_per_call": sum(per_call) / len(per_call),
            "modeled_s_per_call": sum(total) / len(total),
            "wall_s_per_call": wall,
            "looped_speedup": looped_wall / max(wall, 1e-12),
            "invocations": ex.telemetry.stats[("fft", "optical-sim")].invocations,
        })
    return rows


def pipeline_comparison(shape: tuple[int, int] = (256, 256),
                        calls: int = CALLS) -> dict:
    """Two-deep async flush vs strictly serial dispatch-then-block.

    ``max_batch=1`` forces one invocation per call so the flush has
    ``calls`` boundary crossings to overlap; the only difference between
    the two executors is ``pipeline_depth``.  Frames are 256x256 — the
    overlap hides the host-side staging/retire work behind in-flight
    device compute, so each crossing needs enough compute to hide it
    behind (at 128x128 the win is within run-to-run noise).
    """
    imgs = _images(calls, shape)
    walls = {}
    for depth in (1, 2):
        ex = OffloadExecutor(BATCHED_4F, max_batch=1, pipeline_depth=depth)
        ex.warm("fft", imgs[0])
        walls[depth] = _timed_flush(ex, imgs)
    return {
        "serial_wall_s_per_call": walls[1],
        "pipelined_wall_s_per_call": walls[2],
        "pipeline_speedup": walls[1] / max(walls[2], 1e-12),
    }


def _scatter_stage_s(tracer: Tracer, calls: int) -> float:
    """Per-call sum of scatter-staging span time across all devices — the
    host-side re-``device_put`` cost the resident placement eliminates."""
    return (sum(s.duration_s for s in tracer.find("scatter_stage"))
            / max(calls, 1))


def sharded_comparison(shape: tuple[int, int] = SHAPE, calls: int = CALLS,
                       device_counts=(1, 2, 4)) -> list[dict]:
    """Group-sharded flush across n simulated accelerators vs one.

    The ``n_devices=1`` row is the single-device batched baseline.  The
    modeled column is the multi-aperture claim (max-over-devices boundary
    cost + per-device sync) — deterministic, asserted by the CI smoke; the
    wall column is honest about the hardware underneath (sequential
    fallback on one device, genuinely scattered when ``jax.devices()`` has
    enough).

    Two columns attack the re-scatter tax the 0.71x investigation blamed
    (every flush re-``device_put``-ing every shard through the host):

      resident    the same group flushed through a committed device-
                  resident placement (``residency=True``): after the
                  priming flush the shards live on their devices, repeat
                  flushes skip the scatter staging entirely and gather
                  only at readout.  ``scatter_stage_s`` / ``resident_
                  scatter_stage_s`` attribute the eliminated staging
                  per row from traced scatter spans.
      per_engine  a mixed fft+conv stream dispatched under per-engine
                  pipeline windows vs the old single shared window
                  (``shared_window=True``), with the ``engines=``
                  composed modeled price alongside the measured walls.
    """
    imgs = _images(calls, shape)
    h_, w_ = shape
    conv_kernel = (jax.numpy.zeros(shape)
                   .at[0, 0].set(0.5).at[1, 2].set(0.25)
                   .at[h_ - 1, 1].set(0.15))
    rows = []
    base_wall = base_modeled = None
    for n in device_counts:
        ex = OffloadExecutor(BATCHED_4F, max_batch=calls, n_devices=n,
                             default_backend="sharded")
        ex.warm("fft", imgs[0], batch=calls)
        wall = _timed_flush(ex, imgs)
        ex.telemetry.reset()
        handles = [ex.submit("fft", im) for im in imgs]
        ex.flush()
        modeled = sum(h.cost.total_s for h in handles) / len(handles)
        boundary = sum(h.cost.conversion_s + h.cost.interface_s
                       for h in handles) / len(handles)
        if base_wall is None:
            base_wall, base_modeled = wall, modeled
        # attribution flush (satellite of the 0.71x investigation): rerun
        # the same group with a tracer attached so the row carries the
        # per-device scatter-staging breakdown — the timed wall above
        # stays untraced
        tracer = Tracer()
        ex.tracer = ex.ctx.tracer = tracer
        for h in [ex.submit("fft", im) for im in imgs]:
            pass
        ex.flush()
        ex.tracer = ex.ctx.tracer = None
        scatter_s = _scatter_stage_s(tracer, calls)

        # resident column: same group, committed placement — the priming
        # flush pays the scatter once, the timed reps flush against
        # device-resident shards
        ex_r = OffloadExecutor(BATCHED_4F, max_batch=calls, n_devices=n,
                               default_backend="sharded", residency=True)
        ex_r.warm("fft", imgs[0], batch=calls)
        for im in imgs:                       # priming flush
            ex_r.submit("fft", im)
        ex_r.flush()
        resident_wall = _timed_flush(ex_r, imgs)
        r_tracer = Tracer()
        ex_r.tracer = ex_r.ctx.tracer = r_tracer
        for im in imgs:
            ex_r.submit("fft", im)
        ex_r.flush()
        ex_r.tracer = ex_r.ctx.tracer = None
        resident_scatter_s = _scatter_stage_s(r_tracer, calls)

        # per_engine column: fft and conv streams in one flush — each
        # engine rides its own pipeline window vs the old shared gate
        mb = max(2, calls // 4)
        pe_walls = {}
        for shared in (False, True):
            ex_m = OffloadExecutor(BATCHED_4F, max_batch=mb, n_devices=n,
                                   default_backend="sharded",
                                   shared_window=shared)
            ex_m.warm("fft", imgs[0], batch=mb)
            ex_m.warm("conv", imgs[0], kernel=conv_kernel, batch=mb)
            best = float("inf")
            for _ in range(3):
                hs = []
                for im in imgs:
                    hs.append(ex_m.submit("fft", im))
                    hs.append(ex_m.submit("conv", im, kernel=conv_kernel))
                t0 = time.perf_counter()
                ex_m.flush()
                best = min(best, (time.perf_counter() - t0) / len(hs))
            pe_walls[shared] = best
        # engines= composed modeled price for one fft+conv window pair
        n_in = shape[0] * shape[1]
        spec4 = dataclasses.replace(BATCHED_4F,
                                    phase_shift_captures=CONV_CAPTURES)
        composed = BATCHED_4F.batched_step_cost(n_in, engines={
            "fft": BATCHED_4F.batched_step_cost(
                n_in, batch=mb, pipeline_depth=2, n_devices=n),
            "conv": spec4.batched_step_cost(
                n_in, batch=mb, pipeline_depth=2, n_devices=n),
        })
        rows.append({
            "n_devices": n,
            "wall_s_per_call": wall,
            "modeled_s_per_call": modeled,
            "boundary_s_per_call": boundary,
            "wall_speedup": base_wall / max(wall, 1e-12),
            "modeled_speedup": base_modeled / max(modeled, 1e-12),
            "scatter_stage_s": scatter_s,
            "resident_wall_s_per_call": resident_wall,
            "resident_wall_speedup": base_wall / max(resident_wall, 1e-12),
            "resident_vs_rescatter": wall / max(resident_wall, 1e-12),
            "resident_scatter_stage_s": resident_scatter_s,
            "resident_hit_rate": ex_r.telemetry.residency_hit_rate("fft"),
            "per_engine_wall_s_per_call": pe_walls[False],
            "shared_window_wall_s_per_call": pe_walls[True],
            "per_engine_speedup": pe_walls[True] / max(pe_walls[False],
                                                       1e-12),
            "per_engine_modeled_s_per_call": composed.total_s / (2 * mb),
            "devices_present": len(jax.devices()),
            "devices_used": ex.telemetry.devices_observed("fft"),
        })
    return rows


def traced_comparison(shape: tuple[int, int] = SHAPE, calls: int = CALLS,
                      trace_path: str | None = None) -> dict:
    """The observability column: what does attaching a tracer cost, and do
    its spans reconcile with the measured wall?

    Two numbers, each a gate the CI smoke asserts:

    * ``tracer_overhead`` — best-of-reps traced vs untraced K-deep flush
      wall (< 5%: tracing must be cheap enough to leave on in serving).
    * ``reconcile.coverage`` — per-stage charged sums (stage + compute +
      hold + shadow) over the measured accounting-flush wall (~1: the
      span decomposition accounts for the flush end to end).

    Pass ``trace_path`` to also write the Perfetto-loadable export (the CI
    trace artifact).
    """
    imgs = _images(calls, shape)
    ex0 = OffloadExecutor(BATCHED_4F, max_batch=calls)
    ex0.warm("fft", imgs[0])
    untraced = _timed_flush(ex0, imgs, reps=5)
    tracer = Tracer()
    ex = OffloadExecutor(BATCHED_4F, max_batch=calls, tracer=tracer)
    ex.warm("fft", imgs[0])
    traced = _timed_flush(ex, imgs, reps=5)
    # accounting flush on a cleared trace: one flush's spans, one wall
    tracer.clear()
    for im in imgs:
        ex.submit("fft", im)
    t0 = time.perf_counter()
    ex.flush()
    flush_wall = time.perf_counter() - t0
    spans = tracer.spans()
    rec = reconcile(spans, flush_wall)
    out = {
        "shape": list(shape),
        "calls": calls,
        "untraced_wall_s_per_call": untraced,
        "traced_wall_s_per_call": traced,
        "tracer_overhead": traced / max(untraced, 1e-12) - 1.0,
        "spans": len(spans),
        "reconcile": rec,
    }
    if trace_path:
        write_trace(trace_path, spans)
        out["trace_path"] = trace_path
    return out


def large_frame_comparison(shape: tuple[int, int] = LARGE_SHAPE,
                           calls: int = LARGE_CALLS) -> dict:
    """Looped vs monolithic vs memory-budgeted tiled dispatch at 512x512.

    At large frames the monolithic ``(K, H, W)`` stack (in + complex
    intermediates + out: ~64 MB here) falls out of the CPU's last-level
    cache off-TPU, so one big batched invocation turns every XLA pass into
    a DRAM stream — *batching measurably loses to looping*.  The
    memory-budgeted executor streams the same released group as
    ``choose_tile``-sized sub-invocations through the two-deep async
    pipeline instead: amortization per tile, cache-resident working set,
    staging of tile t+1 overlapped with tile t's in-flight compute.  The
    row stamps the budget it ran under (bytes, source, reserve) plus the
    ``tile_k`` the budget chose AND the tile sizes the executor actually
    dispatched (telemetry), so the acceptance check — chosen == dispatched,
    tiled wall <= monolithic wall — is auditable from the JSON alone.
    """
    imgs = _images(calls, shape)
    budget = MemoryBudget.detect()
    plan = choose_tile(shape[0] * shape[1], calls, budget, pipeline_depth=2)
    out = {
        "shape": list(shape),
        "calls": calls,
        "budget_bytes": budget.bytes_limit,
        "budget_source": budget.source,
        "budget_reserve": budget.reserve,
        "chosen_tile_k": plan.tile_k,
        "modeled_bytes_per_frame": plan.bytes_per_frame,
    }
    regimes = {
        "looped": dict(max_batch=1, mem_budget=MemoryBudget.unlimited()),
        "monolithic": dict(max_batch=calls,
                           mem_budget=MemoryBudget.unlimited()),
        "tiled": dict(max_batch=calls, mem_budget=budget),
    }
    for name, kw in regimes.items():
        ex = OffloadExecutor(BATCHED_4F, **kw)
        ex.warm("fft", imgs[0], batch=kw["max_batch"])
        wall = _timed_flush(ex, imgs)
        ex.telemetry.reset()
        handles = [ex.submit("fft", im) for im in imgs]
        ex.flush()
        st = ex.telemetry.stats[("fft", "optical-sim")]
        out[f"{name}_wall_s_per_call"] = wall
        out[f"{name}_modeled_s_per_call"] = \
            sum(h.cost.total_s for h in handles) / len(handles)
        out[f"{name}_invocations"] = st.invocations
        if name == "tiled":
            tiles = ex.telemetry.tile_sizes_observed("fft")
            out["dispatched_tile_sizes"] = {str(k): v
                                            for k, v in tiles.items()}
            out["measured_bytes_per_frame"] = \
                ex.telemetry.bytes_per_frame("fft")
            # the acceptance link: the budget's pick IS the dispatch depth
            out["tile_matches_dispatch"] = \
                bool(tiles) and max(tiles) == plan.tile_k
    out["tiled_vs_monolithic_speedup"] = \
        out["monolithic_wall_s_per_call"] / max(out["tiled_wall_s_per_call"],
                                                1e-12)
    out["tiled_vs_looped_speedup"] = \
        out["looped_wall_s_per_call"] / max(out["tiled_wall_s_per_call"],
                                            1e-12)
    return out


def trickle_comparison(shape: tuple[int, int] = (64, 64),
                       arrivals: int = TRICKLE_ARRIVALS,
                       rate_hz: float = TRICKLE_RATE_HZ,
                       deadline_s: float = TRICKLE_DEADLINE_S,
                       max_batch: int = TRICKLE_MAX_BATCH,
                       seed: int = TRICKLE_SEED) -> dict:
    """Continuous batching vs drain-on-flush under Poisson trickle arrivals.

    One seeded exponential inter-arrival trace drives both regimes on a
    ``ManualClock`` (deterministic admission — no sleeps, no wall-clock
    races).  ``drain`` flushes on every arrival, the pre-scheduler serving
    pattern: occupancy pins at 1 whenever arrivals are sparser than
    flushes.  ``held`` routes the same trace through an
    ``OffloadScheduler``: groups stay open until full / due / futile, so
    occupancy climbs toward ``rate * deadline`` (capped by ``max_batch``)
    and the per-crossing boundary cost amortizes accordingly.  The queueing
    delay that buys it is reported, not hidden: ``held_hold_s_per_call`` is
    the modeled ``StepCost.hold_s`` share, and the modeled wall per call
    includes it.
    """
    rng = np.random.RandomState(seed)
    gaps = rng.exponential(1.0 / rate_hz, size=arrivals)
    imgs = _images(arrivals, shape)

    def _run(held: bool):
        clk = ManualClock()
        ex = OffloadExecutor(BATCHED_4F, max_batch=max_batch, clock=clk)
        ex.warm("fft", imgs[0])
        sched = OffloadScheduler(ex, deadline_s=deadline_s, clock=clk) \
            if held else None
        for gap, im in zip(gaps, imgs):
            clk.advance(float(gap))
            if held:
                sched.submit("fft", im)
            else:
                ex.submit("fft", im)
                ex.flush()          # drain-on-flush: one crossing per arrival
        if held:
            ex.drain()              # releases still-held groups
        st = ex.telemetry.stats[("fft", "optical-sim")]
        per_call = st.modeled.scaled(1.0 / st.calls)
        return {
            "occupancy": st.calls / st.invocations,
            "samples_per_crossing": st.samples_in / st.invocations,
            "invocations": st.invocations,
            "boundary_s_per_call": per_call.conversion_s + per_call.interface_s,
            "modeled_s_per_call": per_call.total_s,
            "hold_s_per_call": per_call.hold_s,
        }

    drain, held = _run(held=False), _run(held=True)
    return {
        # the scheduler config this column was measured under
        "arrival_rate_hz": rate_hz,
        "deadline_s": deadline_s,
        "arrivals": arrivals,
        "max_batch": max_batch,
        "seed": seed,
        "shape": list(shape),
        "drain_occupancy": drain["occupancy"],
        "held_occupancy": held["occupancy"],
        "drain_samples_per_crossing": drain["samples_per_crossing"],
        "held_samples_per_crossing": held["samples_per_crossing"],
        "drain_invocations": drain["invocations"],
        "held_invocations": held["invocations"],
        "drain_boundary_s_per_call": drain["boundary_s_per_call"],
        "held_boundary_s_per_call": held["boundary_s_per_call"],
        "held_hold_s_per_call": held["hold_s_per_call"],
        "drain_modeled_s_per_call": drain["modeled_s_per_call"],
        "held_modeled_s_per_call": held["modeled_s_per_call"],
        "boundary_amortization":
            drain["boundary_s_per_call"] / max(held["boundary_s_per_call"],
                                               1e-12),
    }


def chaos_comparison(rates=CHAOS_RATES, shape=CHAOS_SHAPE,
                     calls: int = CHAOS_CALLS,
                     max_batch: int = CHAOS_MAX_BATCH,
                     seed: int = CHAOS_SEED) -> dict:
    """Goodput and recovery latency under injected boundary faults.

    Each rate row routes the same ``calls`` submissions through a
    chaos-wrapped optical backend injecting a seeded fault mix (transient
    dispatch errors, stragglers, ENOB drift, device loss) at that
    per-dispatch probability, on a ``ManualClock`` so injected straggles
    and retry backoffs advance deterministic time instead of sleeping.
    The equivalence contract is asserted per row: every submitted frame
    retires, and every result lands within the converters' ENOB error
    bound of the looped host baseline (frames the retry policy degraded to
    the host fallback, or the drift-correction path repaired from the
    fidelity shadow, match it bit-for-bit).  ``recovery`` summarizes the
    first-fault-to-correct-result latency histogram from telemetry.
    """
    imgs = _images(calls, shape)
    host = OffloadExecutor(BATCHED_4F, default_backend="host", max_batch=1)
    refs = [np.asarray(h.get()) for h in
            [host.submit("fft", im) for im in imgs]]
    enob = min(BATCHED_4F.dac.effective_bits, BATCHED_4F.adc.effective_bits)
    bound = enob_error_bound(enob, 16.0)
    rows = []
    for rate in rates:
        name = register_chaos("optical-sim", name=f"chaos{int(100 * rate)}",
                              rate=rate, seed=seed)
        clk = ManualClock()
        ex = OffloadExecutor(BATCHED_4F, default_backend=name,
                             max_batch=max_batch, clock=clk,
                             fidelity=FidelityChecker() if rate else None)
        ex.warm("fft", imgs[0], backend="optical-sim")
        wall = _timed_flush(ex, imgs)
        # no telemetry reset: the fault/recovery columns cover the whole
        # seeded run (timed reps + the accounting flush below) — one
        # continuous draw stream on a ManualClock, so still deterministic
        handles = [ex.submit("fft", im) for im in imgs]
        ex.flush()
        rel = [float(np.linalg.norm(np.asarray(h.value) - r)
                     / max(float(np.linalg.norm(r)), 1e-12))
               for h, r in zip(handles, refs)]
        retired = sum(1 for h in handles
                      if h.ready and h.value is not None)
        rows.append({
            "fault_rate": rate,
            "calls": calls,
            "retired": retired,
            "all_retired": retired == calls,
            "max_rel_err": max(rel),
            "enob_bound": bound,
            "within_bound": max(rel) <= bound,
            "wall_s_per_call": wall,
            "goodput_calls_per_s": retired / max(wall * calls, 1e-12),
            "faults": {k: int(v) for k, v in
                       sorted(ex.telemetry.fault_counts.get("fft",
                                                            {}).items())},
            "faults_total": ex.telemetry.faults_total("fft"),
            "recovery": ex.telemetry.recovery_stats("fft"),
            "quarantine_events": len(ex.quarantine.events),
        })
    return {"shape": list(shape), "calls": calls, "max_batch": max_batch,
            "seed": seed, "enob_bound": bound, "rows": rows}


def chaos_overhead(shape: tuple[int, int] = SHAPE, calls: int = CALLS,
                   reps: int = 7) -> dict:
    """What the chaos wrapper costs when it injects nothing: traced
    K-deep flush through a rate-0 chaos-wrapped optical backend vs the
    bare optical backend (< 2% or the chaos CI smoke fails — fault
    *readiness* must be cheap enough to leave on)."""
    imgs = _images(calls, shape)
    plain = OffloadExecutor(BATCHED_4F, max_batch=calls, tracer=Tracer())
    plain.warm("fft", imgs[0])
    base = _timed_flush(plain, imgs, reps=reps)
    name = register_chaos("optical-sim", name="chaos-idle", rate=0.0)
    chaos = OffloadExecutor(BATCHED_4F, default_backend=name,
                            max_batch=calls, tracer=Tracer())
    chaos.warm("fft", imgs[0], backend="optical-sim")
    wall = _timed_flush(chaos, imgs, reps=reps)
    return {"plain_wall_s_per_call": base, "chaos_wall_s_per_call": wall,
            "overhead": wall / max(base, 1e-12) - 1.0}


def residency_comparison(shape: tuple[int, int] = SHAPE, calls: int = CALLS,
                         reps: int = 5) -> dict:
    """Operand residency: a conv layer stack re-using its frames and kernel.

    Four executors flush the same K-deep conv group repeatedly:

      hit      residency on, SAME frames every rep — after the priming
               flush every operand is resident, so each rep skips the
               content hashing AND the host staging stack (the measured
               win) and the model prices the flush read-side-only
               (``dac_s == 0``, the modeled win).
      delta    residency on, a CORRELATED workload — every rep drifts a
               quarter of the frames by ~1% (a drifting sensor: ~15% of
               code bits flip at 8 DAC bits, under ``DELTA_THRESHOLD``)
               and keeps the rest as the same long-lived arrays.  Each
               flush misses at group grain, but the unchanged frames are
               slot-resident (id-memoized digests, no re-hash) and the
               drifted ones take the delta-encoded partial write — the
               measured wall and the modeled ``dac_s`` both land strictly
               between the hit and restage rows.
      restage  residency on, DISTINCT frames every rep — every flush
               misses, paying digest + staging on top of the same compute
               (the honest baseline for the hit path: same code path,
               cache always cold).
      plain    residency off — the historical staging path, unchanged.

    The CI smoke asserts hit < delta < restage on the measured wall,
    that the modeled delta DAC time sits strictly between zero and the
    restage price, and that both cached paths retire bit-equal to plain.
    """
    def _conv_kernel():
        h, w = shape
        return (jax.numpy.zeros(shape)
                .at[0, 0].set(0.5).at[1, 2].set(0.25)
                .at[h - 1, 1].set(0.15))

    def _timed(ex, groups, kernel):
        best = float("inf")
        for imgs in groups:
            hs = [ex.submit("conv", im, kernel=kernel) for im in imgs]
            t0 = time.perf_counter()
            ex.flush()
            best = min(best, (time.perf_counter() - t0) / len(hs))
        return best, hs

    kernel = _conv_kernel()
    imgs = _images(calls, shape)
    fresh = [[jax.random.uniform(
        jax.random.fold_in(jax.random.PRNGKey(100 + r), i), shape)
        for i in range(calls)] for r in range(reps)]

    plain = OffloadExecutor(BATCHED_4F, max_batch=calls)
    plain.warm("conv", imgs[0], kernel=kernel)
    plain_wall, plain_hs = _timed(plain, [imgs] * reps, kernel)

    hot = OffloadExecutor(BATCHED_4F, max_batch=calls, residency=True)
    hot.warm("conv", imgs[0], kernel=kernel)
    for im in imgs:                       # priming flush: populate the cache
        hot.submit("conv", im, kernel=kernel)
    hot.flush()
    hit_wall, hot_hs = _timed(hot, [imgs] * reps, kernel)
    hit_cost = hot_hs[0].cost

    cold = OffloadExecutor(BATCHED_4F, max_batch=calls, residency=True)
    cold.warm("conv", imgs[0], kernel=kernel)
    restage_wall, cold_hs = _timed(cold, fresh, kernel)
    restage_cost = cold_hs[0].cost

    # the correlated workload: every rep drifts frames 0, 4, 8, ... by a
    # fresh ~1% perturbation of the SAME base frame, so rep-to-rep flips
    # stay small, and keeps the other frames as the same array objects
    stride = 4
    drifted = []
    for r in range(reps):
        grp = list(imgs)
        for i in range(0, calls, stride):
            key = jax.random.fold_in(jax.random.PRNGKey(500 + r), i)
            grp[i] = imgs[i] + 0.01 * jax.random.uniform(key, shape)
        drifted.append(grp)
    part = OffloadExecutor(BATCHED_4F, max_batch=calls, residency=True)
    part.warm("conv", imgs[0], kernel=kernel)
    for im in imgs:                       # priming flush: seed the slots
        part.submit("conv", im, kernel=kernel)
    part.flush()
    delta_wall, part_hs = _timed(part, drifted, kernel)
    delta_cost = part_hs[0].cost
    # the delta path's equivalence reference: plain re-stage of the LAST
    # drifted group (_timed leaves part_hs on that group)
    _, ref_hs = _timed(plain, [drifted[-1]], kernel)

    bit_equal = all(
        np.array_equal(np.asarray(h.value), np.asarray(p.value))
        for h, p in zip(hot_hs, plain_hs))
    delta_bit_equal = all(
        np.array_equal(np.asarray(h.value), np.asarray(p.value))
        for h, p in zip(part_hs, ref_hs))
    return {
        "calls": calls,
        "shape": list(shape),
        "hit_wall_s_per_call": hit_wall,
        "delta_wall_s_per_call": delta_wall,
        "restage_wall_s_per_call": restage_wall,
        "plain_wall_s_per_call": plain_wall,
        "hit_speedup_vs_restage": restage_wall / max(hit_wall, 1e-12),
        "delta_speedup_vs_restage": restage_wall / max(delta_wall, 1e-12),
        "modeled_hit_dac_s": hit_cost.dac_s,
        "modeled_delta_dac_s": delta_cost.dac_s,
        "modeled_restage_dac_s": restage_cost.dac_s,
        "hit_rate": hot.telemetry.residency_hit_rate("conv"),
        "delta_rate": part.telemetry.delta_rate("conv"),
        "delta_flip_fraction": part.telemetry.mean_flip_fraction("conv"),
        "delta_frames_per_flush": calls // stride,
        "resident_bytes": hot.residency.resident_bytes(),
        "bit_equal_to_plain": bit_equal,
        "delta_bit_equal_to_plain": delta_bit_equal,
    }


def roundtrip() -> dict:
    """Profile on host -> plan from telemetry -> execute -> compare."""
    imgs = _images()
    ex = OffloadExecutor(BATCHED_4F, max_batch=16)
    router = PlanRouter(ex)
    # prime the jit caches (single-item and batched stack shapes) so
    # one-time compilation does not masquerade as measured per-call host
    # time in the profiles
    ex.warm("fft", imgs[0], backend="host")
    # submit in groups: replan() prices amortization at the *observed*
    # queue occupancy, so serial submission would (correctly) earn none
    ex.telemetry.start()
    for h in [router.submit("fft", im) for im in imgs]:
        h.get()
    ex.telemetry.stop()
    plan = router.replan()
    for h in [router.submit("fft", im) for im in imgs]:
        h.get()
    planned_offload = {d.category: d.offload for d in plan.decisions
                       if d.category != "other"}
    executed_on = {
        cat: [b for (c, b) in ex.telemetry.stats if c == cat]
        for cat in planned_offload
    }
    matches = all(
        ("optical-sim" in executed_on[cat]) == off
        for cat, off in planned_offload.items())
    return {
        "plan_speedup": plan.end_to_end_speedup,
        "planned_offload": planned_offload,
        "executed_on": executed_on,
        "adaptive_max_batch": dict(ex.category_max_batches()),
        "decisions_match_execution": matches,
    }


def bench_payload() -> dict:
    """Machine-readable benchmark record (written to ``BENCH_runtime.json``)
    so the perf trajectory is tracked across PRs.  ``trickle_comparison``
    carries its scheduler config (deadline, arrival rate, seed) alongside
    the measured occupancies, so the column stays interpretable when the
    scenario constants move."""
    rt = roundtrip()
    rt = {k: v for k, v in rt.items() if k != "executed_on"}
    return {
        "bench": "runtime",
        "shape": list(SHAPE),
        "calls": CALLS,
        "sweep": sweep(),
        "pipeline": pipeline_comparison(),
        "sharded": sharded_comparison(),
        "trickle_comparison": trickle_comparison(),
        "large_frame": large_frame_comparison(),
        "traced": traced_comparison(),
        "chaos": chaos_comparison(),
        "chaos_overhead": chaos_overhead(),
        "residency": residency_comparison(),
        "roundtrip": rt,
    }


def write_json(path: str = BENCH_JSON) -> dict:
    payload = bench_payload()
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, default=str)
    return payload


def run(payload: dict | None = None) -> list[str]:
    """CSV rows per the harness contract: section,name,us_per_call,derived."""
    if payload is None:
        payload = bench_payload()
    rows = []
    base = None
    for r in payload["sweep"]:
        if base is None:
            base = r["boundary_s_per_call"]
        rows.append(
            f"runtime,batch{r['max_batch']},"
            f"{1e6 * r['wall_s_per_call']:.1f},"
            f"looped_speedup={r['looped_speedup']:.2f}x"
            f"|boundary={1e6 * r['boundary_s_per_call']:.1f}us"
            f"|amortization={base / max(r['boundary_s_per_call'], 1e-12):.2f}x"
            f"|modeled_total={1e6 * r['modeled_s_per_call']:.1f}us"
            f"|invocations={r['invocations']}")
    p = payload["pipeline"]
    rows.append(
        f"runtime,pipeline,{1e6 * p['pipelined_wall_s_per_call']:.1f},"
        f"speedup_vs_serial={p['pipeline_speedup']:.2f}x"
        f"|serial={1e6 * p['serial_wall_s_per_call']:.1f}us")
    for r in payload["sharded"]:
        rows.append(
            f"runtime,sharded{r['n_devices']},"
            f"{1e6 * r['wall_s_per_call']:.1f},"
            f"modeled_speedup={r['modeled_speedup']:.3f}x"
            f"|wall_speedup={r['wall_speedup']:.2f}x"
            f"|resident_wall_speedup={r['resident_wall_speedup']:.2f}x"
            f"|resident={1e6 * r['resident_wall_s_per_call']:.1f}us"
            f"|scatter_stage={1e6 * r['scatter_stage_s']:.1f}us"
            f"->{1e6 * r['resident_scatter_stage_s']:.1f}us"
            f"|per_engine={1e6 * r['per_engine_wall_s_per_call']:.1f}us"
            f"vs{1e6 * r['shared_window_wall_s_per_call']:.1f}us"
            f"shared({r['per_engine_speedup']:.2f}x)"
            f"|boundary={1e6 * r['boundary_s_per_call']:.1f}us"
            f"|devices_used={r['devices_used']}"
            f"/{r['devices_present']}present")
    t = payload["trickle_comparison"]
    rows.append(
        f"runtime,trickle,{1e6 * t['held_boundary_s_per_call']:.1f},"
        f"held_occupancy={t['held_occupancy']:.2f}"
        f"|drain_occupancy={t['drain_occupancy']:.2f}"
        f"|samples_per_crossing={t['held_samples_per_crossing']:.0f}"
        f"vs{t['drain_samples_per_crossing']:.0f}"
        f"|amortization={t['boundary_amortization']:.2f}x"
        f"|hold={1e6 * t['held_hold_s_per_call']:.1f}us"
        f"|rate={t['arrival_rate_hz']:.0f}/s"
        f"|deadline={1e3 * t['deadline_s']:.0f}ms")
    lf = payload["large_frame"]
    rows.append(
        f"runtime,large_frame,{1e6 * lf['tiled_wall_s_per_call']:.1f},"
        f"tiled_vs_monolithic={lf['tiled_vs_monolithic_speedup']:.2f}x"
        f"|tiled_vs_looped={lf['tiled_vs_looped_speedup']:.2f}x"
        f"|monolithic={1e6 * lf['monolithic_wall_s_per_call']:.1f}us"
        f"|looped={1e6 * lf['looped_wall_s_per_call']:.1f}us"
        f"|tile_k={lf['chosen_tile_k']}"
        f"|match={lf['tile_matches_dispatch']}"
        f"|budget={lf['budget_bytes'] // (1024 * 1024)}MiB"
        f"({lf['budget_source']})")
    tc = payload["traced"]
    rows.append(
        f"runtime,traced,{1e6 * tc['traced_wall_s_per_call']:.1f},"
        f"tracer_overhead={100 * tc['tracer_overhead']:.1f}%"
        f"|untraced={1e6 * tc['untraced_wall_s_per_call']:.1f}us"
        f"|coverage={tc['reconcile']['coverage']:.2f}"
        f"|spans={tc['spans']}")
    for r in payload["chaos"]["rows"]:
        rec = r["recovery"] or {}
        rec_txt = (f"{1e3 * rec['p95_s']:.1f}ms" if rec else "n/a")
        faults = ";".join(f"{k}x{v}" for k, v in r["faults"].items()) or "none"
        rows.append(
            f"runtime,chaos{int(100 * r['fault_rate'])},"
            f"{1e6 * r['wall_s_per_call']:.1f},"
            f"retired={r['retired']}/{r['calls']}"
            f"|goodput={r['goodput_calls_per_s']:.0f}/s"
            f"|max_rel_err={r['max_rel_err']:.2e}"
            f"|within_bound={r['within_bound']}"
            f"|faults={faults}"
            f"|recovery_p95={rec_txt}"
            f"|quarantines={r['quarantine_events']}")
    co = payload["chaos_overhead"]
    rows.append(
        f"runtime,chaos_overhead,{1e6 * co['chaos_wall_s_per_call']:.1f},"
        f"overhead={100 * co['overhead']:.1f}%"
        f"|plain={1e6 * co['plain_wall_s_per_call']:.1f}us")
    res = payload["residency"]
    rows.append(
        f"runtime,residency,{1e6 * res['hit_wall_s_per_call']:.1f},"
        f"hit_vs_restage={res['hit_speedup_vs_restage']:.2f}x"
        f"|delta={1e6 * res['delta_wall_s_per_call']:.1f}us"
        f"|restage={1e6 * res['restage_wall_s_per_call']:.1f}us"
        f"|plain={1e6 * res['plain_wall_s_per_call']:.1f}us"
        f"|hit_dac_s={res['modeled_hit_dac_s']:.2e}"
        f"|delta_dac_s={res['modeled_delta_dac_s']:.2e}"
        f"|hit_rate={res['hit_rate']:.2f}"
        f"|mean_flip={res['delta_flip_fraction']:.2f}"
        f"|bit_equal={res['bit_equal_to_plain']}"
        f"|delta_bit_equal={res['delta_bit_equal_to_plain']}")
    rt = payload["roundtrip"]
    rows.append(
        f"runtime,roundtrip,,speedup={rt['plan_speedup']:.2f}x"
        f"|offload={rt['planned_offload']}"
        f"|adaptive_max_batch={rt['adaptive_max_batch']}"
        f"|match={rt['decisions_match_execution']}")
    return rows


def main() -> None:
    payload = write_json()
    print("section,name,us_per_call,derived")
    for row in run(payload):
        print(row)


if __name__ == "__main__":
    main()
