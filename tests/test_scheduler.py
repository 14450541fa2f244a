"""Admission-controlled continuous batching: the scheduler property harness.

The invariant this file locks down (the ISSUE's acceptance criterion):

    scheduler-held execution == eager flush == looped per-frame

on all three backends *including sharded*, for ragged tails and
deadline-forced partial releases: holding a partially filled group open
across flushes, releasing it early on a deadline, or splitting one
submission stream across several admission passes must never change a
result — only when the boundary is crossed and how many frames share the
crossing.  All timing rides a ``ManualClock``, so every admission decision
(ages, arrival rates, deadlines) is deterministic.

Runs under hypothesis when installed (nightly CI uses the ``nightly``
profile for more examples); falls back to a fixed example grid otherwise.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core.accelerator import ANDERSON_MVM, PROTOTYPE_4F
from repro.core.conversion import ConverterSpec
from repro.core.planner import CategoryProfile, plan_offload
from repro.runtime import (
    FidelityChecker,
    ManualClock,
    OffloadExecutor,
    OffloadScheduler,
    PlanRouter,
    RuntimeTelemetry,
)

LANED_4F = dataclasses.replace(
    PROTOTYPE_4F, name="laned-4f", interface_latency_s=1.0e-3,
    dac_lanes=48, adc_lanes=48,
    slm_interface_hz=100e6, camera_interface_hz=100e6,
    device_sync_s=1.0e-5)

HI_FI_ADC = ConverterSpec(name="hifi-adc", kind="adc", bits=12,
                          rate_hz=5.0e8, power_w=0.060, enob=10.5)

SPEC = dataclasses.replace(LANED_4F, adc=HI_FI_ADC)
MVM = dataclasses.replace(ANDERSON_MVM, adc=HI_FI_ADC)

# inner backend -> its registered sharded wrapper (group sharding: tight)
SHARDED_OF = {"host": "sharded-host", "optical-sim": "sharded",
              "ideal": "sharded-ideal"}

DEADLINE = 0.1
# Inter-arrival pattern cycled over the submissions: two quick arrivals,
# then a pause longer than the deadline — the pre-arrival poll() then
# force-releases whatever is held (a deadline-forced partial release),
# while the quick pairs exercise accumulation and rule-(a) full releases.
GAPS = (0.01, 0.01, 0.25)


def _imgs(n, shape, seed=0):
    key = jax.random.PRNGKey(seed)
    return [jax.random.uniform(jax.random.fold_in(key, i), shape)
            for i in range(n)]


def _kernel(shape):
    h, w = shape
    return (jnp.zeros(shape)
            .at[0, 0].set(0.5).at[1, 2 % w].set(0.25)
            .at[h - 1, 1 % w].set(0.15))


def _run_eager(backend, category, imgs, spec, *, max_batch, n_devices=1,
               kernel=None, weights=None, tile_k=None):
    ex = OffloadExecutor(spec, max_batch=max_batch, n_devices=n_devices,
                         default_backend=backend, tile_k=tile_k)
    kw = {k: v for k, v in (("kernel", kernel), ("weights", weights))
          if v is not None}
    hs = [ex.submit(category, im, **kw) for im in imgs]
    ex.flush()
    return hs, ex


def _run_scheduled(backend, category, imgs, spec, *, max_batch, n_devices=1,
                   kernel=None, weights=None, gaps=GAPS, deadline=DEADLINE,
                   tile_k=None):
    """Drive the same submissions through an admission-controlled stream:
    clock-advance, event-loop poll (may deadline-release held groups
    *before* the new arrival joins them — a genuinely partial release),
    then submit (whose own poll fires rule (a) full releases)."""
    clk = ManualClock()
    ex = OffloadExecutor(spec, max_batch=max_batch, n_devices=n_devices,
                         default_backend=backend, clock=clk, tile_k=tile_k)
    sched = OffloadScheduler(ex, deadline_s=deadline, clock=clk)
    kw = {k: v for k, v in (("kernel", kernel), ("weights", weights))
          if v is not None}
    hs = []
    for i, im in enumerate(imgs):
        clk.advance(gaps[i % len(gaps)])
        sched.poll()
        hs.append(sched.submit(category, im, **kw))
    clk.advance(2 * deadline)
    sched.poll()          # due-release the tail the event loop still holds
    ex.drain()            # belt and braces: nothing may stay pending
    return hs, ex


def check_scheduled_equivalence(backend, category, shape, calls, max_batch,
                                n_devices=1, tile_k=None):
    imgs = _imgs(calls, shape)
    kernel = _kernel(shape) if category == "conv" else None
    name = SHARDED_OF[backend] if n_devices > 1 else backend
    held, hex_ = _run_scheduled(name, category, imgs, SPEC,
                                max_batch=max_batch, n_devices=n_devices,
                                kernel=kernel, tile_k=tile_k)
    eager, _ = _run_eager(backend, category, imgs, SPEC, max_batch=max_batch,
                          kernel=kernel)
    looped, _ = _run_eager(backend, category, imgs, SPEC, max_batch=1,
                           kernel=kernel)
    # Digital backends are bit-stable across groupings; the optical sim
    # quantizes, and XLA lowers batch-1 vs batch-K reductions differently,
    # so a borderline sample may legitimately snap one converter level
    # (~2^-12 here) apart.  Tolerance = a few quantizer steps, far below
    # any real divergence — batch *composition* is verified bit-tight by
    # the scheduled-vs-eager comparison whenever chunks coincide.
    atol = 1e-3 if backend == "optical-sim" else 1e-5
    for hh, he, hl in zip(held, eager, looped):
        np.testing.assert_allclose(hh.value, he.value, rtol=1e-4, atol=atol)
        np.testing.assert_allclose(he.value, hl.value, rtol=1e-4, atol=atol)
    st = hex_.telemetry.stats[(category, name)]
    assert st.calls == calls                      # nothing lost or doubled
    assert st.invocations >= math.ceil(calls / max_batch)
    assert hex_.pending == 0 and hex_.in_flight == 0
    if tile_k is not None:
        # admission-held releases honored the tile ceiling too
        assert max(hex_.telemetry.tile_sizes_observed(category)) \
            <= max(1, min(tile_k, max_batch))


SCHED_CASES = [
    # (backend, category, shape, calls, max_batch, n_devices, tile_k) —
    # ragged tails (calls % max_batch != 0) and deadline-forced partial
    # releases (the GAPS pause) throughout; n_devices > 1 routes via the
    # sharded wrapper (the held queue feeding the fleet); tile_k forces
    # memory-budgeted tiled dispatch of the released groups.
    ("host", "fft", (16, 12), 7, 3, 1, None),
    ("host", "conv", (16, 12), 5, 4, 1, None),
    ("optical-sim", "fft", (16, 12), 8, 3, 1, None),
    ("optical-sim", "conv", (12, 8), 7, 4, 1, None),
    ("ideal", "fft", (16, 12), 6, 4, 1, None),
    ("ideal", "conv", (16, 12), 4, 3, 1, None),
    ("host", "fft", (16, 12), 7, 4, 2, None),
    ("optical-sim", "fft", (16, 12), 9, 4, 4, None),
    ("optical-sim", "conv", (16, 12), 7, 3, 2, None),
    ("ideal", "conv", (12, 8), 6, 4, 4, None),
    # scheduler-held + tiled (+ sharded): a deadline-released partial
    # group still streams through the tile ceiling, ragged tiles included
    ("optical-sim", "fft", (16, 12), 8, 5, 1, 2),
    ("optical-sim", "conv", (12, 8), 7, 4, 2, 3),
    ("host", "fft", (16, 12), 6, 6, 1, 1),
    ("ideal", "fft", (12, 8), 7, 4, 4, 2),
]


@pytest.mark.parametrize(
    "backend,category,shape,calls,max_batch,n_devices,tile_k", SCHED_CASES)
def test_scheduled_equivalence_fixed(backend, category, shape, calls,
                                     max_batch, n_devices, tile_k):
    """Tier-1 anchor grid (the hypothesis sweep below is nightly/slow)."""
    check_scheduled_equivalence(backend, category, shape, calls, max_batch,
                                n_devices, tile_k)


if HAVE_HYPOTHESIS:

    @pytest.mark.slow
    @settings(deadline=None)
    @given(backend=st.sampled_from(["host", "optical-sim", "ideal"]),
           category=st.sampled_from(["fft", "conv"]),
           h=st.integers(min_value=4, max_value=20),
           w=st.integers(min_value=4, max_value=20),
           calls=st.integers(min_value=1, max_value=9),
           max_batch=st.integers(min_value=1, max_value=5),
           n_devices=st.sampled_from([1, 2, 4]),
           tile_k=st.one_of(st.none(), st.integers(min_value=1, max_value=6)))
    def test_scheduled_equivalence_property(backend, category, h, w, calls,
                                            max_batch, n_devices, tile_k):
        check_scheduled_equivalence(backend, category, (h, w), calls,
                                    max_batch, n_devices, tile_k)


def test_scheduled_matmul_equivalence():
    key = jax.random.PRNGKey(5)
    xs = [jax.random.normal(jax.random.fold_in(key, i), (12, 16))
          for i in range(7)]
    w = jax.random.normal(jax.random.fold_in(key, 99), (16, 8))
    held, _ = _run_scheduled("optical-sim", "matmul", xs, MVM, max_batch=3,
                             weights=w)
    eager, _ = _run_eager("optical-sim", "matmul", xs, MVM, max_batch=3,
                          weights=w)
    looped, _ = _run_eager("optical-sim", "matmul", xs, MVM, max_batch=1,
                           weights=w)
    for hh, he, hl in zip(held, eager, looped):
        np.testing.assert_allclose(hh.value, he.value, rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(he.value, hl.value, rtol=1e-4, atol=1e-3)


# --- the admission rules, one by one ------------------------------------------


def _sched(max_batch=4, deadline=0.1, **kw):
    clk = ManualClock()
    ex = OffloadExecutor(SPEC, max_batch=max_batch, clock=clk, **kw)
    return clk, ex, OffloadScheduler(ex, deadline_s=deadline, clock=clk)


def test_rule_full_group_releases_on_submit():
    """(a) a group reaching max_batch dispatches on the spot — no poll
    pump needed — and the ragged tail stays held."""
    clk, ex, sched = _sched(max_batch=2)
    imgs = _imgs(3, (8, 8))
    sched.submit("fft", imgs[0])
    assert sched.held == 1 and ex.in_flight == 0
    sched.submit("fft", imgs[1])       # group full: dispatched by submit
    assert sched.held == 0 and ex.in_flight == 1
    sched.submit("fft", imgs[2])       # tail: held again
    assert sched.held == 1
    ex.drain()
    st = ex.telemetry.stats[("fft", "optical-sim")]
    assert st.invocations == 2 and st.calls == 3


def test_rule_deadline_releases_partial_group():
    """(b) the oldest held call's age reaching the deadline forces the
    group out, whatever its occupancy."""
    clk, ex, sched = _sched(max_batch=8, deadline=0.1)
    h = sched.submit("fft", _imgs(1, (8, 8))[0])
    assert sched.held == 1
    clk.advance(0.09)
    assert sched.poll() == [] and sched.held == 1    # not due yet
    clk.advance(0.02)                                 # age 0.11 > deadline
    released = sched.poll()
    assert [r for r in released] == [h] and sched.held == 0
    ex.drain()
    assert h.done()


def test_rule_arrival_rate_futility_releases_early():
    """(c) when the measured arrival rate says the next arrival lands past
    the deadline, holding buys latency without occupancy: release now,
    well before the deadline itself expires."""
    clk, ex, sched = _sched(max_batch=8, deadline=1.0)
    imgs = _imgs(3, (8, 8))
    # establish a sparse arrival history: ~0.45 s between submits
    clk.advance(0.45)
    sched.submit("fft", imgs[0])
    clk.advance(0.45)
    sched.submit("fft", imgs[1])
    # rate ~2.2/s -> expected next arrival in ~0.45 s; oldest age 0.45;
    # 0.45 + 0.45 < 1.0 -> still worth holding
    assert sched.held == 2
    clk.advance(0.45)
    sched.submit("fft", imgs[2])
    # oldest age 0.9; 0.9 + ~0.45 > 1.0 -> futile to keep holding: the
    # submit's own poll released the group 0.1 s before its deadline
    assert sched.held == 0 and ex.in_flight == 1
    ex.drain()
    assert ex.telemetry.stats[("fft", "optical-sim")].invocations == 1


def test_unknown_rate_holds_until_deadline():
    """One arrival = no rate estimate: the scheduler holds optimistically
    (rule (c) stays quiet) and only the deadline can release."""
    clk, ex, sched = _sched(max_batch=8, deadline=0.5)
    sched.submit("fft", _imgs(1, (8, 8))[0])
    assert ex.telemetry.arrival_rate("fft") == 0.0
    clk.advance(0.4)
    assert sched.poll() == [] and sched.held == 1
    clk.advance(0.2)
    assert len(sched.poll()) == 1


def test_burst_arrivals_estimate_infinite_rate_and_hold():
    """Simultaneous submits (span ~0) estimate an infinite rate: the next
    arrival is expected immediately, so the scheduler keeps holding."""
    clk, ex, sched = _sched(max_batch=8, deadline=0.5)
    imgs = _imgs(3, (8, 8))
    for im in imgs:
        sched.submit("fft", im)       # no clock advance: a burst
    assert ex.telemetry.arrival_rate("fft") == math.inf
    assert sched.held == 3            # held: occupancy is still climbing
    clk.advance(1.0)
    sched.poll()
    ex.drain()
    assert ex.telemetry.stats[("fft", "optical-sim")].invocations == 1


def test_holding_beats_draining_under_poisson_trickle():
    """One seeded Poisson trickle (200 Hz, 48 arrivals) on a ManualClock:
    drain-on-flush crosses once an arrival, while the scheduler holds
    groups open under a 50 ms deadline, so it coalesces more calls and
    more boundary samples into each crossing."""
    rng = np.random.RandomState(0)
    gaps = rng.exponential(1.0 / 200.0, size=48)
    imgs = _imgs(48, (16, 16))

    def crossings(held):
        clk = ManualClock()
        ex = OffloadExecutor(SPEC, max_batch=8, clock=clk)
        sched = OffloadScheduler(ex, deadline_s=0.05, clock=clk) \
            if held else None
        for gap, im in zip(gaps, imgs):
            clk.advance(float(gap))
            if held:
                sched.submit("fft", im)
            else:
                ex.submit("fft", im)
                ex.flush()
        ex.drain()
        st = ex.telemetry.stats[("fft", "optical-sim")]
        assert st.calls == len(imgs)
        return st.calls / st.invocations, st.samples_in / st.invocations

    drain_occ, drain_samples = crossings(held=False)
    held_occ, held_samples = crossings(held=True)
    assert held_occ > drain_occ, (held_occ, drain_occ)
    assert held_samples > drain_samples, (held_samples, drain_samples)


def test_hold_time_priced_into_invocation_cost():
    """The modeled wall honestly charges the queueing delay holding spent
    (StepCost.hold_s) — and eager executors price zero hold."""
    clk, ex, sched = _sched(max_batch=4, deadline=0.2)
    imgs = _imgs(2, (8, 8))
    sched.submit("fft", imgs[0])
    clk.advance(0.05)
    sched.submit("fft", imgs[1])
    clk.advance(0.30)
    (h, h2) = sched.poll()
    ex.drain()
    # oldest member waited 0.35; the per-call share splits it across the 2
    assert h.cost.hold_s == pytest.approx(0.35 / 2)
    assert h.cost.total_s > h.cost.conversion_s + h.cost.interface_s
    st = ex.telemetry.stats[("fft", "optical-sim")]
    assert st.modeled.hold_s == pytest.approx(0.35)
    eager, eex = _run_eager("optical-sim", "fft", imgs, SPEC, max_batch=4)
    assert eager[0].cost.hold_s == 0.0
    assert eex.telemetry.stats[("fft", "optical-sim")].modeled.hold_s == 0.0


def test_batched_step_cost_hold_term():
    """The cost model's hold_s term is additive, scales, and survives the
    sharded max-over-devices recursion exactly once."""
    base = SPEC.batched_step_cost(4096, batch=4)
    held = SPEC.batched_step_cost(4096, batch=4, hold_s=0.25)
    assert held.hold_s == 0.25
    assert held.total_s == pytest.approx(base.total_s + 0.25)
    assert held.conversion_s == base.conversion_s
    sharded = SPEC.batched_step_cost(4096, batch=4, n_devices=2, hold_s=0.25)
    assert sharded.hold_s == 0.25
    mvm = MVM.batched_step_cost(512, 512, batch=8, hold_s=0.1)
    assert mvm.hold_s == pytest.approx(0.1)
    assert mvm.scaled(0.5).hold_s == pytest.approx(0.05)
    assert (mvm + mvm).hold_s == pytest.approx(0.2)


def test_force_flush_escape_hatches_release_held_groups():
    """flush / get / drain / the context manager are the force-release
    path: held work dispatches immediately through every one of them."""
    # executor.flush()
    clk, ex, sched = _sched()
    h = sched.submit("fft", _imgs(1, (8, 8))[0])
    ex.flush()
    assert h.done() and sched.held == 0
    # result.get()
    clk, ex, sched = _sched()
    h = sched.submit("fft", _imgs(1, (8, 8))[0])
    _ = h.get()
    assert h.done()
    # drain() alone (the satellite: drain releases scheduler-held groups)
    clk, ex, sched = _sched()
    h = sched.submit("fft", _imgs(1, (8, 8))[0])
    ex.drain()
    assert h.done() and ex.pending == 0 and ex.in_flight == 0
    # scheduler context manager
    clk, ex, sched = _sched()
    with sched:
        h = sched.submit("fft", _imgs(1, (8, 8))[0])
    assert h.done()


def test_executor_context_manager_drains_everything():
    """``with OffloadExecutor(...)`` cannot leak pending, held, or
    in-flight work — even when the body raises."""
    imgs = _imgs(5, (8, 8))
    with OffloadExecutor(SPEC, max_batch=2) as ex:
        hs = [ex.submit("fft", im) for im in imgs]
        ex.flush_async()              # some in flight, none retired
        hs.append(ex.submit("fft", imgs[0]))   # and one still queued
    assert ex.pending == 0 and ex.in_flight == 0
    assert all(h.done() for h in hs)
    st = ex.telemetry.stats[("fft", "optical-sim")]
    assert st.calls == 6
    # exception path: handles still materialize
    with pytest.raises(RuntimeError):
        with OffloadExecutor(SPEC, max_batch=4) as ex2:
            h = ex2.submit("fft", imgs[0])
            raise RuntimeError("boom")
    assert h.done() and ex2.pending == 0 and ex2.in_flight == 0


def test_scheduler_routes_through_plan_router():
    """A scheduler wrapping a PlanRouter paces release while the router's
    table picks the backend."""
    clk = ManualClock()
    ex = OffloadExecutor(SPEC, max_batch=4, clock=clk)
    router = PlanRouter(ex)           # all-host profiling mode
    sched = OffloadScheduler(router, deadline_s=0.1, clock=clk)
    h = sched.submit("fft", _imgs(1, (8, 8))[0])
    assert sched.held == 1
    clk.advance(0.2)
    sched.poll()
    ex.drain()
    assert h.backend == "host"
    assert ("fft", "host") in ex.telemetry.stats


def test_held_groups_diagnostics_and_summary():
    clk, ex, sched = _sched(max_batch=4, deadline=0.1)
    sched.submit("fft", _imgs(1, (8, 8))[0])
    clk.advance(0.03)
    (row,) = sched.held_groups()
    assert row["category"] == "fft" and row["held"] == 1
    assert row["oldest_age_s"] == pytest.approx(0.03)
    assert "held=1" in sched.summary()
    ex.drain()


# --- telemetry: the arrival process -------------------------------------------


def test_telemetry_arrival_rate_estimation():
    t = RuntimeTelemetry()
    assert t.arrival_rate("fft") == 0.0           # no arrivals
    t.note_submit("fft", 1.0)
    assert t.arrival_rate("fft") == 0.0           # one arrival: no estimate
    for ts in (1.5, 2.0, 2.5):
        t.note_submit("fft", ts)
    assert t.arrival_rate("fft") == pytest.approx(2.0)   # 3 gaps / 1.5 s
    assert t.arrival_rate("conv") == 0.0          # per category
    t.note_submit("conv", 3.0)
    t.note_submit("conv", 3.0)
    assert t.arrival_rate("conv") == math.inf     # burst
    t.reset()
    assert t.arrival_rate("fft") == 0.0


def test_telemetry_arrival_rate_merge():
    a, b = RuntimeTelemetry(), RuntimeTelemetry()
    a.note_submit("fft", 0.0)
    b.note_submit("fft", 1.0)
    a.merge(b)
    assert a.arrival_rate("fft") == pytest.approx(1.0)


# --- fidelity-gated planning (the acceptance criterion) -----------------------


def test_plan_offload_fidelity_gate_vetoes_fast_offload():
    """A category whose observed rel_err blows the ENOB budget must NOT be
    offloaded even when category_speedup > 1 (ISSUE acceptance)."""
    prof = CategoryProfile("fft", host_s=10.0, calls=16,
                           samples_in=16 * 4096, samples_out=16 * 4096)
    clean = plan_offload([prof], SPEC, max_batch=16)
    d_clean = clean.decisions[0]
    assert d_clean.offload and d_clean.category_speedup > 1  # sanity: fast
    bad = dataclasses.replace(prof, rel_err=0.9)   # over the ENOB budget
    # (the limiting converter here is the 5-ENOB DAC: budget 16 * 2^-5 = 0.5)
    gated = plan_offload([bad], SPEC, max_batch=16)
    d = gated.decisions[0]
    assert d.accel_s < d.host_s                    # still faster on paper...
    assert not d.offload and d.fidelity_bound      # ...and still vetoed
    assert gated.fidelity_bound and not clean.fidelity_bound
    assert "FIDELITY-GATED" in gated.summary()
    # the plan's bottom line prices the veto honestly: fft stays on host
    assert gated.total_planned_s == pytest.approx(gated.total_host_s)
    # an in-budget rel_err sails through the gate
    enob = min(SPEC.dac.effective_bits, SPEC.adc.effective_bits)
    ok = dataclasses.replace(prof, rel_err=0.5 * 16.0 * 2.0 ** (-enob))
    assert plan_offload([ok], SPEC, max_batch=16).decisions[0].offload


def test_replan_threads_fidelity_reports_and_falls_back_to_host():
    """The loop-closer: a VIOLATION report observed while serving flips the
    category's route back to host on the next replan, even though the spec
    is fast enough that speed alone would keep it offloaded."""
    # near-free boundary: speed strongly favors offload...
    fast = dataclasses.replace(
        SPEC, name="fast-4f", interface_latency_s=0.0, slm_settle_s=0.0,
        exposure_s=0.0, dac_lanes=4096, adc_lanes=4096,
        # ...but the write path is a deliberately mis-ranged 1-bit DAC
        # whose claimed ENOB (8 bits) its actual resolution cannot honor:
        # the shadow run scores a rel_err far outside the 2^-8 budget.
        dac=ConverterSpec(name="dac1", kind="dac", bits=1, rate_hz=1e9,
                          power_w=0.05, enob=8.0))
    checker = FidelityChecker(slack=1.0)
    ex = OffloadExecutor(fast, fidelity=checker, max_batch=4)
    router = PlanRouter(ex)
    imgs = _imgs(4, (32, 32))
    ex.telemetry.start()
    for im in imgs:                   # measured host baseline
        router.run("fft", im)
    ex.telemetry.stop()
    plan1 = router.replan()
    # no fidelity evidence yet (host traffic is never shadowed): the
    # fast spec wins on speed and fft routes to the optical engine
    assert router.backend_for("fft") == "optical-sim"
    assert not plan1.fidelity_bound
    for im in imgs:                   # offloaded traffic is shadow-scored
        router.run("fft", im)
    assert not checker.all_ok         # the VIOLATION the gate needs
    plan2 = router.replan()
    d = next(d for d in plan2.decisions if d.category == "fft")
    assert d.fidelity_bound and not d.offload
    assert d.accel_s < d.host_s       # speed still says offload; gate wins
    assert router.backend_for("fft") == "host"   # fallen back


# --- serving-engine hook ------------------------------------------------------


def test_serving_engine_polls_scheduler_across_decode_steps():
    """With an OffloadScheduler as the engine's offload hook, the decode
    step runs an admission poll instead of a forced flush: a partially
    filled aux group survives decode steps and coalesces submissions made
    *between* steps into one boundary crossing once due."""
    from repro.configs import get_smoke_config
    from repro.models import init_params
    from repro.serving import Request, ServingEngine

    cfg = get_smoke_config("stablelm-1.6b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    clk = ManualClock()
    ex = OffloadExecutor(SPEC, max_batch=8, default_backend="host",
                         clock=clk)
    sched = OffloadScheduler(ex, deadline_s=0.5, clock=clk)
    engine = ServingEngine(cfg, params, batch_slots=2, max_len=32,
                           offload=sched)
    engine.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=4))
    imgs = _imgs(3, (8, 8), seed=9)
    h0 = engine.submit_aux("fft", imgs[0])
    engine.step()
    # pre-scheduler the engine would have flushed here; now the group is
    # held (age < deadline, no rate evidence says waiting is futile)
    assert engine.pending_aux == 1 and not h0.ready
    clk.advance(0.01)
    engine.submit_aux("fft", imgs[1])
    engine.step()
    assert engine.pending_aux == 2          # still riding across steps
    clk.advance(0.01)
    engine.submit_aux("fft", imgs[2])
    clk.advance(1.0)                        # deadline expires
    engine.step()                           # this step's poll releases
    assert engine.pending_aux == 0
    ex.drain()
    st = ex.telemetry.stats[("fft", "host")]
    assert st.invocations == 1 and st.calls == 3   # ONE crossing for all 3
    engine.run_to_completion(max_steps=8)


def test_replan_gates_with_the_checkers_own_slack():
    """The gate must judge with the attached checker's slack, not the
    default: a rel_err the strict checker flags as VIOLATION must flip the
    plan even though the default slack would wave it through."""
    checker = FidelityChecker(slack=2.0)
    ex = OffloadExecutor(SPEC, fidelity=checker, max_batch=4)
    router = PlanRouter(ex)
    enob = min(SPEC.dac.effective_bits, SPEC.adc.effective_bits)
    # between the strict bound (2 * 2^-enob) and the default (16 * 2^-enob)
    rel_err = 4.0 * 2.0 ** (-enob)
    ex.telemetry.record("fft", "host", calls=8, samples_in=8 * 4096,
                        samples_out=8 * 4096, wall_s=10.0)
    profiles = [dataclasses.replace(p, rel_err=rel_err)
                for p in ex.telemetry.profiles(include_other=False)]
    default_plan = plan_offload(profiles, SPEC, max_batch=8)
    assert not default_plan.decisions[0].fidelity_bound   # 16x slack: passes
    # hand the checker a report carrying that same rel_err and replan
    checker.check("fft", "optical-sim",
                  [jnp.ones((4, 4)) * (1.0 + rel_err)], [jnp.ones((4, 4))],
                  enob=enob)
    assert not checker.all_ok                              # 2x slack: VIOLATION
    plan = router.replan(apply=False, max_batch=8)
    d = next(d for d in plan.decisions if d.category == "fft")
    assert d.fidelity_bound and not d.offload


def test_scheduler_held_fidelity_shadowing_still_scores():
    """Held groups released by the scheduler flow through the same shadow
    scoring as eager flushes (validation mode stays synchronous)."""
    clk = ManualClock()
    checker = FidelityChecker()
    ex = OffloadExecutor(SPEC, fidelity=checker, max_batch=4, clock=clk)
    sched = OffloadScheduler(ex, deadline_s=0.1, clock=clk)
    for im in _imgs(3, (16, 16)):
        clk.advance(0.01)
        sched.submit("fft", im)
    clk.advance(0.2)
    (h, *_rest) = sched.poll()
    assert h.fidelity is not None and h.fidelity.batch == 3
    assert ex.in_flight == 0          # shadow batches retire synchronously


def test_run_and_get_force_release_held_groups_under_manual_clock():
    """The blocking path with a scheduler attached: ``OffloadResult.get``
    (and ``OffloadExecutor.run``, which is submit + get) must force-release
    a held group rather than block on a deadline the ManualClock will
    never reach on its own."""
    # result.get() on a submission held in a partially filled group
    clk, ex, sched = _sched(max_batch=4)
    h = sched.submit("fft", _imgs(1, (8, 8))[0])
    clk.advance(0.01)                 # deadline (0.1s) nowhere near due
    assert sched.held == 1
    v = h.get()                       # returns promptly: flush force-releases
    assert h.done() and sched.held == 0
    ref = OffloadExecutor(SPEC, max_batch=1).run("fft", _imgs(1, (8, 8))[0])
    np.testing.assert_array_equal(np.asarray(v), np.asarray(ref))
    # executor.run() while another submission sits held: the eager call's
    # flush sweeps the held group along with it
    clk, ex, sched = _sched(max_batch=4)
    held = sched.submit("fft", _imgs(1, (8, 8))[0])
    out = ex.run("fft", _imgs(1, (8, 8), seed=1)[0])
    assert held.done() and sched.held == 0 and ex.pending == 0
    np.testing.assert_array_equal(np.asarray(held.value), np.asarray(ref))
    ref1 = OffloadExecutor(SPEC, max_batch=1).run(
        "fft", _imgs(1, (8, 8), seed=1)[0])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref1))


# --- adaptive per-engine pipeline windows (router) -----------------------------


def test_replan_collapses_window_to_observed_overlap():
    """Traffic that never overlapped in flight earns no pipelined-hiding
    credit: replan writes the category's window down to its measured
    in-flight-at-dispatch occupancy.  One group per flush means occupancy
    1 at every dispatch, so the chosen window is 1."""
    ex = OffloadExecutor(SPEC, max_batch=8, pipeline_depth=2)
    router = PlanRouter(ex)
    imgs = _imgs(8, (16, 16))
    ex.telemetry.start()
    for im in imgs:
        ex.submit("fft", im, backend="host")
        ex.flush()          # one invocation per flush: no overlap ever
    ex.telemetry.stop()
    assert router.choose_windows() == {"fft": 1}
    router.replan()
    assert ex.pipeline_window_for("fft") == 1
    # deep traffic keeps the global depth: four invocations in one flush
    ex2 = OffloadExecutor(SPEC, max_batch=2, pipeline_depth=2)
    router2 = PlanRouter(ex2)
    ex2.telemetry.start()
    for im in imgs:
        ex2.submit("fft", im, backend="host")
    ex2.flush()             # 4 invocations ride the two-deep window
    ex2.telemetry.stop()
    assert router2.choose_windows()["fft"] == 2
    router2.replan()
    assert ex2.pipeline_window_for("fft") == 2


def test_operator_window_pin_bounds_adaptive_choice():
    """A window the operator pinned is a ceiling replan never exceeds —
    and never destroys: the snapshot survives the router's own writes."""
    ex = OffloadExecutor(SPEC, max_batch=2, pipeline_depth=3)
    router = PlanRouter(ex)
    ex.set_pipeline_window("fft", 1)   # operator pin below the global 3
    imgs = _imgs(6, (16, 16))
    ex.telemetry.start()
    for im in imgs:
        ex.submit("fft", im, backend="host")
    ex.flush()
    ex.telemetry.stop()
    router.replan()
    assert ex.pipeline_window_for("fft") == 1   # pin respected
    router.replan()                             # router's own write is not
    assert ex.pipeline_window_for("fft") == 1   # mistaken for an operator pin
