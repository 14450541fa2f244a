"""Offload runtime: backends, batching executor, telemetry loop, fidelity."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.accelerator import ANDERSON_MVM, PROTOTYPE_4F
from repro.core.conversion import ConverterSpec
from repro.core.planner import CategoryProfile, plan_offload
from repro.core.profiler import OpProfiler
from repro.runtime import (
    FidelityChecker,
    OffloadExecutor,
    PlanRouter,
    Quarantine,
    RuntimeTelemetry,
    available_backends,
    get_backend,
)

# Lane-parallel converters, fast links, and a per-invocation link latency:
# the §6 levers the batching executor amortizes.  4096-sample frames
# deliberately do not divide the lane count, so even pure conversion time
# amortizes (ceil residue), and the fixed handshake dominates the streaming
# interface term so batching visibly wins.
LANED_4F = dataclasses.replace(
    PROTOTYPE_4F, name="laned-4f", interface_latency_s=1.0e-3,
    dac_lanes=48, adc_lanes=48,
    slm_interface_hz=100e6, camera_interface_hz=100e6)

HI_FI_ADC = ConverterSpec(name="hifi-adc", kind="adc", bits=12,
                          rate_hz=5.0e8, power_w=0.060, enob=10.5)


def _imgs(n, shape=(64, 64), seed=0):
    key = jax.random.PRNGKey(seed)
    return [jax.random.uniform(jax.random.fold_in(key, i), shape)
            for i in range(n)]


# --- registry -------------------------------------------------------------------

def test_registry_has_three_backends():
    assert set(available_backends()) >= {"host", "optical-sim", "ideal"}
    for name in ("host", "optical-sim", "ideal"):
        assert get_backend(name).name == name
    with pytest.raises(KeyError):
        get_backend("quantum")


def test_backend_category_support_follows_spec():
    ex = OffloadExecutor(PROTOTYPE_4F)
    with pytest.raises(ValueError):
        ex.submit("matmul", jnp.ones((8, 8)), weights=jnp.ones((8, 8)))
    ex_mvm = OffloadExecutor(ANDERSON_MVM)
    with pytest.raises(ValueError):
        ex_mvm.submit("fft", jnp.ones((8, 8)))


# --- backend correctness ---------------------------------------------------------

def test_host_and_ideal_fft_match_oracle():
    (a,) = _imgs(1)
    want = jnp.abs(jnp.fft.fft2(a, norm="ortho")) ** 2
    ex = OffloadExecutor(PROTOTYPE_4F)
    np.testing.assert_array_equal(ex.run("fft", a, backend="host"), want)
    r = ex.submit("fft", a, backend="ideal")
    ex.flush()
    np.testing.assert_array_equal(r.value, want)
    # the ideal bound is exactly the zero-conversion-cost accelerator
    assert r.cost.conversion_s == 0.0
    assert r.cost.interface_s == 0.0
    assert r.cost.analog_s > 0.0


def test_optical_sim_fft_approximates_host():
    (a,) = _imgs(1)
    spec = dataclasses.replace(LANED_4F, adc=HI_FI_ADC)
    ex = OffloadExecutor(spec)
    got = ex.run("fft", a)
    want = jnp.abs(jnp.fft.fft2(a, norm="ortho")) ** 2
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel < 0.05, rel


def test_optical_sim_conv_approximates_host():
    (a,) = _imgs(1)
    k = jnp.zeros((64, 64)).at[0, 0].set(0.6).at[0, 1].set(0.3).at[2, 3].set(0.1)
    spec = dataclasses.replace(
        LANED_4F,
        dac=ConverterSpec(name="d8", kind="dac", bits=8, rate_hz=1e9,
                          power_w=0.05, enob=7.0),
        adc=HI_FI_ADC)
    ex = OffloadExecutor(spec)
    got = ex.run("conv", a, kernel=k)
    want = jnp.real(jnp.fft.ifft2(jnp.fft.fft2(a) * jnp.fft.fft2(k)))
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel < 0.05, rel


def test_optical_sim_conv_handles_signed_inputs():
    """The SLM can't encode negatives: the backend must affine-map signed
    inputs onto the aperture and undo the map (regression: zero-centered
    or all-negative inputs used to come back as garbage/zeros)."""
    key = jax.random.PRNGKey(11)
    k = jnp.zeros((64, 64)).at[:3, :3].set(0.2).at[0, 0].add(0.4)
    spec = dataclasses.replace(
        LANED_4F,
        dac=ConverterSpec(name="d8", kind="dac", bits=8, rate_hz=1e9,
                          power_w=0.05, enob=7.0),
        adc=HI_FI_ADC)
    ex = OffloadExecutor(spec)
    # pre-fix: 0.71 rel error (centered) and 1.0 (all-negative -> zeros);
    # the centered case legitimately costs more bits (a +/-4 sigma signal
    # fills the DAC range sparsely), hence the looser bound
    for a, tol in ((jax.random.normal(key, (64, 64)), 0.15),
                   (-1.0 - jax.random.uniform(key, (64, 64)), 0.05)):
        got = ex.run("conv", a, kernel=k)
        want = jnp.real(jnp.fft.ifft2(jnp.fft.fft2(a) * jnp.fft.fft2(k)))
        rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        assert rel < tol, rel


def test_optical_sim_matmul_approximates_host():
    key = jax.random.PRNGKey(3)
    a = jax.random.normal(key, (16, 32))
    w = jax.random.normal(jax.random.fold_in(key, 1), (32, 8))
    ex = OffloadExecutor(dataclasses.replace(ANDERSON_MVM, adc=HI_FI_ADC))
    got = ex.run("matmul", a, weights=w)
    want = a @ w
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel < 0.05, rel


# --- the batching lever ----------------------------------------------------------

def test_batched_results_identical_and_boundary_cheaper():
    """Coalescing K same-shape calls must not change a single bit of the
    results while strictly reducing the modeled per-call conversion and
    conversion+interface time (ISSUE acceptance criterion)."""
    imgs = _imgs(8)
    batched = OffloadExecutor(LANED_4F, max_batch=8)
    handles = [batched.submit("fft", im) for im in imgs]
    batched.flush()

    serial = OffloadExecutor(LANED_4F, max_batch=1)
    serial_handles = [serial.submit("fft", im) for im in imgs]
    serial.flush()

    for hb, hs in zip(handles, serial_handles):
        np.testing.assert_array_equal(hb.value, hs.value)
        assert hb.batch == 8 and hs.batch == 1
        # pure conversion amortizes the converter-lane ceil residue
        assert hb.cost.conversion_s < hs.cost.conversion_s
        # conversion + interface amortizes the per-invocation handshake too
        boundary_b = hb.cost.conversion_s + hb.cost.interface_s
        boundary_s = hs.cost.conversion_s + hs.cost.interface_s
        assert boundary_b < 0.5 * boundary_s
    assert batched.telemetry.stats[("fft", "optical-sim")].invocations == 1
    assert serial.telemetry.stats[("fft", "optical-sim")].invocations == 8


def test_batched_step_cost_reduces_to_step_cost():
    c1 = LANED_4F.batched_step_cost(4096, batch=1)
    c0 = LANED_4F.step_cost(4096)
    assert c1.total_s == pytest.approx(c0.total_s)
    assert c1.conversion_s == pytest.approx(c0.conversion_s)
    # batch=1 on the MVM engine too
    m1 = ANDERSON_MVM.batched_step_cost(512, 512, batch=1)
    m0 = ANDERSON_MVM.step_cost(512, 512)
    assert m1.total_s == pytest.approx(m0.total_s)


def test_planner_batched_pricing_monotone():
    prof = CategoryProfile("fft", host_s=1.0, calls=16,
                           samples_in=16 * 4096, samples_out=16 * 4096)
    serial = plan_offload([prof], LANED_4F)
    batched = plan_offload([prof], LANED_4F, max_batch=16)
    d_s = serial.decisions[0]
    d_b = batched.decisions[0]
    assert d_b.accel_s < d_s.accel_s
    assert d_b.conversion_s <= d_s.conversion_s


# --- truly-batched execution ------------------------------------------------------

@pytest.mark.parametrize("backend", ["host", "optical-sim", "ideal"])
@pytest.mark.parametrize("category", ["fft", "conv"])
def test_batched_matches_per_item_reference_ragged(backend, category):
    """ONE batched invocation per group must reproduce the per-item path on
    every backend — including the ragged tail (K=7, max_batch=3 -> 3+3+1)."""
    imgs = _imgs(7)
    k = jnp.zeros((64, 64)).at[0, 0].set(0.5).at[1, 2].set(0.25)
    spec = dataclasses.replace(LANED_4F, adc=HI_FI_ADC)
    kw = dict(kernel=k) if category == "conv" else {}
    bat = OffloadExecutor(spec, max_batch=3, default_backend=backend)
    hs = [bat.submit(category, im, **kw) for im in imgs]
    bat.flush()
    ser = OffloadExecutor(spec, max_batch=1, default_backend=backend)
    ss = [ser.submit(category, im, **kw) for im in imgs]
    ser.flush()
    for hb, hsr in zip(hs, ss):
        np.testing.assert_allclose(hb.value, hsr.value, rtol=1e-5, atol=1e-5)
    st = bat.telemetry.stats[(category, backend)]
    assert st.invocations == 3 and st.calls == 7
    assert ser.telemetry.stats[(category, backend)].invocations == 7


@pytest.mark.parametrize("backend", ["host", "optical-sim"])
def test_batched_matmul_matches_per_item_reference(backend):
    key = jax.random.PRNGKey(5)
    xs = [jax.random.normal(jax.random.fold_in(key, i), (16, 32))
          for i in range(5)]
    w = jax.random.normal(jax.random.fold_in(key, 99), (32, 8))
    spec = dataclasses.replace(ANDERSON_MVM, adc=HI_FI_ADC)
    bat = OffloadExecutor(spec, max_batch=2, default_backend=backend)
    hs = [bat.submit("matmul", x, weights=w) for x in xs]
    bat.flush()
    ser = OffloadExecutor(spec, max_batch=1, default_backend=backend)
    ss = [ser.submit("matmul", x, weights=w) for x in xs]
    ser.flush()
    for hb, hsr in zip(hs, ss):
        np.testing.assert_allclose(hb.value, hsr.value, rtol=1e-5, atol=1e-5)
    assert bat.telemetry.stats[("matmul", backend)].invocations == 3  # 2+2+1


def test_flush_async_readiness_ordering_and_drain():
    imgs = _imgs(10)
    ex = OffloadExecutor(LANED_4F, max_batch=4, pipeline_depth=2)
    hs = [ex.submit("fft", im) for im in imgs]
    done = ex.flush_async()
    # handles fill immediately (async values), in submission order
    assert done == hs
    assert all(h.ready for h in hs)
    # at most pipeline_depth invocations remain unretired
    assert ex.in_flight <= 2
    ex.drain()
    assert ex.in_flight == 0
    st = ex.telemetry.stats[("fft", "optical-sim")]
    assert st.invocations == 3 and st.calls == 10  # 4+4+2: ragged tail
    ser = OffloadExecutor(LANED_4F, max_batch=1, pipeline_depth=1)
    ss = [ser.submit("fft", im) for im in imgs]
    ser.flush()
    for hb, hsr in zip(hs, ss):
        np.testing.assert_allclose(hb.value, hsr.value, rtol=1e-5, atol=1e-7)


def test_flush_async_wait_and_done():
    imgs = _imgs(4)
    ex = OffloadExecutor(LANED_4F, max_batch=2, pipeline_depth=2)
    hs = [ex.submit("fft", im) for im in imgs]
    ex.flush_async()
    h = hs[-1]
    h.wait()             # retires its invocation: telemetry recorded
    assert h.done()
    assert ex.in_flight == 0
    assert ex.telemetry.stats[("fft", "optical-sim")].invocations == 2
    # get() on an already-filled async result also lands its telemetry
    hs2 = [ex.submit("fft", im) for im in imgs[:2]]
    ex.flush_async()
    _ = hs2[0].get()
    assert hs2[0].done()


def test_flush_async_empty_flush():
    """Flushing an empty queue is a no-op: no handles, no dispatches, no
    telemetry — and it must not disturb invocations already in flight."""
    ex = OffloadExecutor(LANED_4F, max_batch=4, pipeline_depth=2)
    assert ex.flush_async() == [] and ex.flush() == []
    assert ex.in_flight == 0 and not ex.telemetry.stats
    hs = [ex.submit("fft", im) for im in _imgs(2)]
    ex.flush_async()
    inflight_before = ex.in_flight
    assert ex.flush_async() == []           # empty: in-flight untouched
    assert ex.in_flight == inflight_before
    ex.drain()
    assert all(h.done() for h in hs)


def test_drain_called_twice_is_idempotent():
    ex = OffloadExecutor(LANED_4F, max_batch=2, pipeline_depth=2)
    [ex.submit("fft", im) for im in _imgs(4)]
    ex.flush_async()
    ex.drain()
    st = ex.telemetry.stats[("fft", "optical-sim")]
    calls, invocations = st.calls, st.invocations
    ex.drain()                               # nothing left: pure no-op
    assert ex.in_flight == 0
    assert st.calls == calls and st.invocations == invocations


def test_wait_on_already_retired_result():
    """wait() on a result whose invocation already retired must be a
    cheap no-op: no re-blocking of the pipeline, no double telemetry."""
    ex = OffloadExecutor(LANED_4F, max_batch=4, pipeline_depth=2)
    hs = [ex.submit("fft", im) for im in _imgs(4)]
    ex.flush()                               # everything retired
    st = ex.telemetry.stats[("fft", "optical-sim")]
    recorded = (st.calls, st.invocations, st.wall_s)
    for h in hs:
        assert h.wait() is h and h.done()    # idempotent, still done
        assert h.wait().value is h.value
    assert (st.calls, st.invocations, st.wall_s) == recorded


def test_interleaved_submit_during_inflight_pipeline():
    """Submitting while earlier invocations are still in flight must not
    lose, reorder, or double-retire anything."""
    imgs = _imgs(6)
    ex = OffloadExecutor(LANED_4F, max_batch=2, pipeline_depth=2)
    first = [ex.submit("fft", im) for im in imgs[:4]]
    ex.flush_async()                         # 2 invocations, <= 2 in flight
    assert ex.in_flight == 2
    # interleave: new submits while the pipeline is full
    second = [ex.submit("fft", im) for im in imgs[4:]]
    assert ex.pending == 2 and all(not h.ready for h in second)
    ex.flush_async()                         # dispatching retires the oldest
    assert ex.in_flight <= 2
    ex.drain()
    assert ex.in_flight == 0
    st = ex.telemetry.stats[("fft", "optical-sim")]
    assert st.calls == 6 and st.invocations == 3
    ser = OffloadExecutor(LANED_4F, max_batch=1, pipeline_depth=1)
    ss = [ser.submit("fft", im) for im in imgs]
    ser.flush()
    for hb, hsr in zip(first + second, ss):
        np.testing.assert_allclose(hb.value, hsr.value, rtol=1e-5, atol=1e-7)


def test_pipeline_depth_one_is_serial():
    imgs = _imgs(4)
    ex = OffloadExecutor(LANED_4F, max_batch=1, pipeline_depth=1)
    hs = [ex.submit("fft", im) for im in imgs]
    ex.flush_async()
    # depth 1: every dispatch retired the previous one; at most 1 in flight
    assert ex.in_flight <= 1
    ex.drain()
    assert ex.telemetry.stats[("fft", "optical-sim")].invocations == 4
    for h in hs:
        assert h.done()


def test_per_category_max_batch_and_warm_batched():
    imgs = _imgs(4)
    ex = OffloadExecutor(LANED_4F, max_batch=8)
    ex.set_max_batch("fft", 2)
    assert ex.max_batch_for("fft") == 2
    assert ex.max_batch_for("conv") == 8
    with pytest.raises(ValueError):
        ex.set_max_batch("fft", 0)
    # warm primes BOTH the single-item and the batched stack shapes
    # without recording telemetry (the satellite fix: the first real
    # batched flush must not pay compilation)
    ex.warm("fft", imgs[0])
    assert not ex.telemetry.stats
    hs = [ex.submit("fft", im) for im in imgs]
    ex.flush()
    assert ex.telemetry.stats[("fft", "optical-sim")].invocations == 2


# --- the pipelined cost model -----------------------------------------------------

def test_batched_step_cost_pipeline_overlap():
    n = LANED_4F.usable_pixels  # one full aperture frame per call
    plain = LANED_4F.batched_step_cost(n, batch=4)
    piped = LANED_4F.batched_step_cost(n, batch=4, pipeline_depth=2)
    # overlap strictly helps across 4 frames, but can never beat either
    # side running alone
    assert piped.total_s < plain.total_s
    write = plain.dac_s
    read = plain.adc_s + plain.analog_s
    assert piped.total_s > max(write, read)
    # nothing to overlap within a single frame; batch=1 is untouched
    one = LANED_4F.batched_step_cost(4096, batch=1, pipeline_depth=2)
    assert one.total_s == pytest.approx(LANED_4F.step_cost(4096).total_s)
    # MVM engine: double-buffered streaming beats the serial sum too
    m_plain = ANDERSON_MVM.batched_step_cost(512, 512, batch=8)
    m_piped = ANDERSON_MVM.batched_step_cost(512, 512, batch=8,
                                             pipeline_depth=2)
    assert m_piped.total_s < m_plain.total_s


# --- the telemetry -> plan loop ---------------------------------------------------

def test_telemetry_profiles_reproduce_hand_profiled_plan():
    """Executing through the runtime's host backend must yield profiles
    whose plan matches the seed repo's manual OpProfiler methodology."""
    imgs = _imgs(6)

    def host_fft(x):
        return jnp.abs(jnp.fft.fft2(x, norm="ortho")) ** 2

    # hand path (seed methodology)
    prof = OpProfiler()
    prof.start()
    for im in imgs:
        prof.run("fft", host_fft, im)
    prof.stop()
    hand = [CategoryProfile("fft", host_s=prof.seconds["fft"],
                            calls=prof.calls["fft"],
                            samples_in=prof.samples_in["fft"],
                            samples_out=prof.samples_out["fft"]),
            CategoryProfile("other",
                            host_s=prof.total_s - prof.seconds["fft"])]
    hand_plan = plan_offload(hand, PROTOTYPE_4F)

    # runtime path (telemetry as a side effect of execution)
    ex = OffloadExecutor(PROTOTYPE_4F, default_backend="host")
    ex.telemetry.start()
    for im in imgs:
        ex.run("fft", im)
    ex.telemetry.stop()
    measured = ex.telemetry.profiles()
    measured_plan = plan_offload(measured, PROTOTYPE_4F)

    # same observed traffic...
    by_name = {p.name: p for p in measured}
    assert by_name["fft"].calls == hand[0].calls
    assert by_name["fft"].samples_in == hand[0].samples_in
    assert by_name["fft"].samples_out == hand[0].samples_out
    # ...and the same offload verdict per category (the prototype's honest
    # conversion costs decline offload in both, the paper's conclusion)
    hand_d = {d.category: d.offload for d in hand_plan.decisions}
    measured_d = {d.category: d.offload for d in measured_plan.decisions}
    assert hand_d == measured_d
    assert measured_d["fft"] is False


def test_router_applies_plan_and_replans_from_telemetry():
    imgs = _imgs(4)
    ex = OffloadExecutor(LANED_4F, max_batch=4)
    router = PlanRouter(ex)
    assert router.routes == {"fft": "host", "conv": "host", "matmul": "host"}
    ex.telemetry.start()
    for im in imgs:
        router.run("fft", im)
    ex.telemetry.stop()
    plan = router.replan()
    # routing table mirrors the plan's decisions exactly
    for d in plan.decisions:
        if d.category in router.routes:
            want = "optical-sim" if d.offload else "host"
            assert router.backend_for(d.category) == want
    # executing after the replan hits the routed backends
    for im in imgs:
        router.run("fft", im)
    executed = {b for (c, b) in ex.telemetry.stats if c == "fft"}
    fft_offloaded = any(d.category == "fft" and d.offload
                        for d in plan.decisions)
    assert ("optical-sim" in executed) == fft_offloaded


def test_replan_prices_at_observed_occupancy():
    """Serial traffic earns no batching credit: replan must not divide the
    per-invocation handshake by max_batch the workload never reached."""
    imgs = _imgs(6)
    ex = OffloadExecutor(LANED_4F, default_backend="host", max_batch=16)
    router = PlanRouter(ex)
    for im in imgs:            # one call per flush -> occupancy 1
        router.run("fft", im)
    assert ex.telemetry.observed_occupancy() == 1
    serial_plan = router.replan(apply=False)
    batched_plan = router.replan(apply=False, max_batch=16)
    d1 = next(d for d in serial_plan.decisions if d.category == "fft")
    d16 = next(d for d in batched_plan.decisions if d.category == "fft")
    assert d1.accel_s > d16.accel_s  # no amortization credit when serial


def test_adaptive_replan_deadline_caps_coalescing():
    """With no deadline the adaptive ceiling follows the global cap; a
    latency deadline lowers it until the modeled batched invocation fits."""
    imgs = _imgs(8)
    ex = OffloadExecutor(LANED_4F, default_backend="host", max_batch=16)
    router = PlanRouter(ex)
    for im in imgs:
        router.run("fft", im)
    router.replan()
    assert ex.max_batch_for("fft") == 16
    n_in, n_out = ex.telemetry.samples_per_call("fft")
    assert n_in == 64 * 64
    # deadline between the batch-4 and batch-8 invocation cost: halving
    # from 16 must stop at 4
    c4 = ex.spec.batched_step_cost(n_in, n_out, batch=4,
                                   pipeline_depth=2).total_s
    c8 = ex.spec.batched_step_cost(n_in, n_out, batch=8,
                                   pipeline_depth=2).total_s
    assert c4 < c8
    deadline = 0.5 * (c4 + c8)
    chosen = router.choose_max_batch(deadline_s=deadline)
    assert chosen["fft"] == 4
    router.replan(deadline_s=deadline)
    assert ex.max_batch_for("fft") == 4
    # apply=False prices without touching the executor's ceilings
    ex2 = OffloadExecutor(LANED_4F, default_backend="host", max_batch=16)
    r2 = PlanRouter(ex2)
    for im in imgs:
        r2.run("fft", im)
    r2.replan(apply=False, deadline_s=deadline)
    assert ex2.max_batch_for("fft") == 16


def test_adaptive_replan_respects_operator_caps():
    """A per-category ceiling the operator set directly is an upper bound
    replan must not clobber back to the global cap — and must survive a
    deadline-lowered replan so a later relaxed replan can restore it."""
    imgs = _imgs(4)
    ex = OffloadExecutor(LANED_4F, default_backend="host", max_batch=16)
    router = PlanRouter(ex)
    ex.set_max_batch("fft", 8)   # operator latency bound
    for im in imgs:
        router.run("fft", im)
    router.replan()              # no deadline: adaptive pick starts at 16
    assert ex.max_batch_for("fft") == 8
    # tight deadline lowers below the operator bound...
    n_in, n_out = ex.telemetry.samples_per_call("fft")
    c2 = ex.spec.batched_step_cost(n_in, n_out, batch=2,
                                   pipeline_depth=2).total_s
    c4 = ex.spec.batched_step_cost(n_in, n_out, batch=4,
                                   pipeline_depth=2).total_s
    router.replan(deadline_s=0.5 * (c2 + c4))
    assert ex.max_batch_for("fft") == 2
    # ...and relaxing the deadline restores the operator's bound, not 16
    router.replan()
    assert ex.max_batch_for("fft") == 8


def test_choose_max_batch_prices_conv_at_four_captures():
    """The deadline check must charge conv's interferometric capture cost
    the way the backend prices it (4 reads), not the base spec's 1."""
    imgs = _imgs(4)
    ex = OffloadExecutor(LANED_4F, default_backend="host", max_batch=16)
    router = PlanRouter(ex)
    k = jnp.zeros((64, 64)).at[0, 0].set(1.0)
    for im in imgs:
        router.run("conv", im, kernel=k)
    n_in, n_out = ex.telemetry.samples_per_call("conv")
    spec4 = dataclasses.replace(LANED_4F, phase_shift_captures=4)
    # a deadline the 1-capture pricing would accept at batch 16 but the
    # true 4-capture invocation blows: the chosen depth must fit spec4
    deadline = 0.5 * (spec4.batched_step_cost(
        n_in, n_out, batch=4, pipeline_depth=2).total_s
        + spec4.batched_step_cost(n_in, n_out, batch=8,
                                  pipeline_depth=2).total_s)
    chosen = router.choose_max_batch(deadline_s=deadline)
    assert spec4.batched_step_cost(
        n_in, n_out, batch=chosen["conv"],
        pipeline_depth=2).total_s <= deadline
    assert chosen["conv"] == 4


def test_flush_async_host_results_have_valid_cost():
    """Host-routed results must honor the 'attributes valid once ready'
    contract between flush_async and drain (provisional dispatch-share
    cost, refined to the measured wall at retire)."""
    imgs = _imgs(3)
    ex = OffloadExecutor(LANED_4F, default_backend="host", max_batch=4)
    hs = [ex.submit("fft", im) for im in imgs]
    ex.flush_async()
    assert all(h.ready and h.cost is not None for h in hs)
    provisional = hs[0].cost.host_s
    assert provisional >= 0.0 and hs[0].cost.conversion_s == 0.0
    ex.drain()
    assert hs[0].cost.host_s >= provisional  # refined to full wall share


def test_deferred_retirement_does_not_bill_idle_time():
    """Host work between flush_async and drain must not be charged to the
    invocation's telemetry wall (it would poison replanning profiles)."""
    import time as _time
    imgs = _imgs(2)
    ex = OffloadExecutor(LANED_4F, max_batch=2)
    ex.warm("fft", imgs[0])  # compile time is billed to dispatch otherwise
    for im in imgs:
        ex.submit("fft", im)
    ex.flush_async()
    _time.sleep(0.05)            # unrelated host work; compute finishes
    ex.drain()
    st = ex.telemetry.stats[("fft", "optical-sim")]
    assert st.wall_s < 0.04, st.wall_s


def test_occupancy_is_per_category():
    """One category's deep batches must not credit another's serial calls
    with amortization (and vice versa)."""
    t = RuntimeTelemetry()
    for _ in range(16):   # serial: 16 invocations of 1
        t.record("matmul", "host", calls=1, samples_in=4, samples_out=4,
                 wall_s=0.001)
    t.record("fft", "host", calls=16, samples_in=64, samples_out=64,
             wall_s=0.016)  # one deep batch
    assert t.observed_occupancy("matmul") == 1
    assert t.observed_occupancy("fft") == 16


def test_warm_validates_like_submit():
    from repro.core.accelerator import ANDERSON_MVM as MVM
    ex = OffloadExecutor(MVM)
    with pytest.raises(ValueError):
        ex.warm("fft", jnp.ones((8, 8)))
    ex2 = OffloadExecutor(LANED_4F)
    with pytest.raises(ValueError):
        ex2.warm("conv", jnp.ones((8, 8)))  # kernel missing


def test_telemetry_host_rate_extrapolation():
    """A category that later ran offloaded is priced at the measured host
    rate for ALL observed calls, not just the host-executed ones."""
    t = RuntimeTelemetry()
    t.record("fft", "host", calls=4, samples_in=40, samples_out=40,
             wall_s=0.04)
    t.record("fft", "optical-sim", calls=4, samples_in=40, samples_out=40,
             wall_s=0.5, modeled=LANED_4F.step_cost(10))
    (prof,) = t.profiles(include_other=False)
    assert prof.calls == 8
    assert prof.host_s == pytest.approx(0.08)  # 0.01 s/call x 8 calls


def test_telemetry_other_bucket_ignores_post_window_traffic():
    import time as _time
    t = RuntimeTelemetry()
    t.start()
    t.record("fft", "host", calls=1, samples_in=4, samples_out=4,
             wall_s=0.005)
    _time.sleep(0.03)
    t.stop()
    # offloaded execution after the window must not eat the 'other' bucket
    t.record("fft", "optical-sim", calls=8, samples_in=32, samples_out=32,
             wall_s=5.0, modeled=LANED_4F.step_cost(4))
    other = [p for p in t.profiles() if p.name == "other"]
    assert other and other[0].host_s >= 0.02


def test_telemetry_merge_and_summary():
    a, b = RuntimeTelemetry(), RuntimeTelemetry()
    a.record("fft", "host", calls=2, samples_in=10, samples_out=10, wall_s=0.1)
    b.record("fft", "host", calls=3, samples_in=15, samples_out=15, wall_s=0.2)
    b.record("conv", "optical-sim", calls=1, samples_in=5, samples_out=5,
             wall_s=0.05, modeled=LANED_4F.step_cost(5))
    a.merge(b)
    st = a.stats[("fft", "host")]
    assert st.calls == 5 and st.samples_in == 25
    assert st.wall_s == pytest.approx(0.3)
    assert a.stats[("conv", "optical-sim")].modeled.total_s > 0
    assert "fft" in a.summary() and "conv" in a.summary()
    assert a.host_timed("fft") and not a.host_timed("conv")


# --- fidelity ---------------------------------------------------------------------

def test_fidelity_error_shrinks_with_dac_bits():
    """ISSUE acceptance: checker error is monotone nonincreasing (and
    overall strictly shrinking) as DAC resolution grows."""
    (a,) = _imgs(1)
    # 16-bit read path so the ADC error floor does not mask the DAC sweep
    adc16 = ConverterSpec(name="adc16", kind="adc", bits=16, rate_hz=1e8,
                          power_w=0.060, enob=15.0)
    errs = []
    for bits in (2, 4, 6, 8):
        dac = ConverterSpec(name=f"dac{bits}", kind="dac", bits=bits,
                            rate_hz=1e9, power_w=0.05, enob=bits - 1.0)
        spec = dataclasses.replace(LANED_4F, dac=dac, adc=adc16)
        checker = FidelityChecker()
        ex = OffloadExecutor(spec, fidelity=checker)
        ex.run("fft", a)
        errs.append(checker.reports[-1].rel_err)
    assert all(e2 <= e1 * 1.05 for e1, e2 in zip(errs, errs[1:])), errs
    assert errs[-1] < errs[0] / 4, errs


def test_fidelity_report_pairs_speedup_with_accuracy():
    (a,) = _imgs(1)
    checker = FidelityChecker()
    ex = OffloadExecutor(dataclasses.replace(LANED_4F, adc=HI_FI_ADC),
                         fidelity=checker, max_batch=4)
    handles = [ex.submit("fft", a) for _ in range(4)]
    ex.flush()
    r = handles[0]
    assert r.fidelity is not None
    assert r.fidelity.batch == 4
    assert r.fidelity.rel_err >= 0.0
    assert r.fidelity.bound > 0.0
    assert r.cost.conversion_s > 0.0  # cost and accuracy, side by side
    w = checker.worst("fft")
    assert w is not None and w.rel_err == checker.reports[0].rel_err


def test_fidelity_flags_budget_violation():
    # a 1-bit DAC cannot stay inside an 8-ENOB budget
    dac1 = ConverterSpec(name="dac1", kind="dac", bits=1, rate_hz=1e9,
                         power_w=0.05, enob=8.0)
    spec = dataclasses.replace(LANED_4F, dac=dac1, adc=HI_FI_ADC)
    checker = FidelityChecker(slack=1.0)
    ex = OffloadExecutor(spec, fidelity=checker)
    ex.run("fft", _imgs(1)[0])
    assert not checker.all_ok


def test_fidelity_vectorized_matches_per_frame_norms():
    """The batch scores in ONE reduction; the number must equal the worst
    per-frame ||got-ref|| / ||ref|| a Python loop would compute."""
    key = jax.random.PRNGKey(2)
    refs = [jax.random.uniform(jax.random.fold_in(key, i), (8, 8)) + 0.5
            for i in range(4)]
    gots = [r + 1e-3 * jax.random.normal(jax.random.fold_in(key, 50 + i),
                                         (8, 8))
            for i, r in enumerate(refs)]
    checker = FidelityChecker()
    report = checker.check("fft", "optical-sim", gots, refs, enob=8.0)
    want = max(float(np.linalg.norm(np.asarray(g - r).ravel())
                     / np.linalg.norm(np.asarray(r).ravel()))
               for g, r in zip(gots, refs))
    assert report.rel_err == pytest.approx(want, rel=1e-5)
    assert report.batch == 4


def test_fidelity_zero_norm_reference_frames():
    """A zero reference reproduced exactly scores 0 (ok); any nonzero
    output against a zero reference scores inf (always a VIOLATION) —
    neither divides by zero or reports clamp-denominator garbage."""
    z = jnp.zeros((4, 4))
    checker = FidelityChecker()
    ok = checker.check("fft", "optical-sim", [z], [z], enob=8.0)
    assert ok.rel_err == 0.0 and ok.ok
    bad = checker.check("fft", "optical-sim", [jnp.ones((4, 4))], [z],
                        enob=8.0)
    assert bad.rel_err == float("inf") and not bad.ok
    # mixed batch: the zero-norm frame must not mask the fabricated one
    mixed = checker.check("fft", "optical-sim",
                          [z, jnp.ones((4, 4))], [z, z], enob=8.0)
    assert mixed.rel_err == float("inf")


def test_fidelity_nonpositive_enob_infinite_bound():
    """enob <= 0 promises nothing: the budget is infinite and even a
    garbage result is 'within' it (the gate then never vetoes)."""
    from repro.core.conversion import enob_error_bound
    assert enob_error_bound(0.0) == float("inf")
    assert enob_error_bound(-3.0) == float("inf")
    checker = FidelityChecker()
    r = checker.check("fft", "optical-sim", [jnp.ones((4, 4))],
                      [2.0 * jnp.ones((4, 4))], enob=0.0)
    assert r.bound == float("inf") and r.ok
    # ...including the fabricated-signal inf: inf <= inf
    r2 = checker.check("fft", "optical-sim", [jnp.ones((4, 4))],
                       [jnp.zeros((4, 4))], enob=-1.0)
    assert r2.ok


def test_fidelity_sample_every_bounds_shadowing():
    """sample_every=N scores every Nth shadowed batch per category; the
    skipped batches keep the async pipeline (no forced sync retire)."""
    (a,) = _imgs(1)
    checker = FidelityChecker(sample_every=3)
    ex = OffloadExecutor(dataclasses.replace(LANED_4F, adc=HI_FI_ADC),
                         fidelity=checker, max_batch=2, pipeline_depth=2)
    handles = []
    for _ in range(6):           # 6 flushes -> 6 shadowed-batch candidates
        h = ex.submit("fft", a)
        ex.flush_async()
        handles.append(h)
    ex.drain()
    assert len(checker.reports) == 2          # batches 0 and 3 scored
    assert handles[0].fidelity is not None
    assert handles[1].fidelity is None        # skipped: no report attached
    assert handles[3].fidelity is not None
    with pytest.raises(ValueError):
        FidelityChecker(sample_every=0)


def test_fidelity_sampling_is_per_category():
    checker = FidelityChecker(sample_every=2)
    assert checker.should_check("fft")        # fft #0 -> scored
    assert checker.should_check("conv")       # conv #0 -> scored
    assert not checker.should_check("fft")    # fft #1 -> skipped
    assert checker.should_check("fft")        # fft #2 -> scored


# --- lazy handles and caches ------------------------------------------------------

def test_result_get_triggers_flush():
    ex = OffloadExecutor(LANED_4F, max_batch=8)
    h = ex.submit("fft", _imgs(1)[0])
    assert not h.ready and ex.pending == 1
    value = h.get()
    assert h.ready and ex.pending == 0
    assert value is h.value


def test_factor_and_mask_caches_are_shared():
    imgs = _imgs(2, shape=(64, 32))
    ex = OffloadExecutor(LANED_4F)
    # factor matrices are cached per (shape, resolved block layout) —
    # consumed by the batched Pallas fft path on TPU; off-TPU the backend
    # takes the fused XLA route and never touches them, so exercise the
    # cache directly.  Same size + same layout -> one shared entry; a
    # different block layout is a fresh cache KEY (the stale-kernel fix:
    # replanning tile_k must never pair a recompiled kernel with factors
    # cached under the old layout) but aliases the same arrays — the
    # values depend only on n, so layouts share one O(n^2) pair.
    blocks = (1, 64, 32, 32)
    a = ex.ctx.factors(64, blocks)
    b = ex.ctx.factors(32, blocks)
    assert ex.ctx.factors(64, blocks) is a and ex.ctx.factors(32, blocks) is b
    assert (64,) + blocks in ex.ctx.factor_cache
    assert (32,) + blocks in ex.ctx.factor_cache
    other = ex.ctx.factors(64, (2, 64, 32, 32))
    assert (64, 2, 64, 32, 32) in ex.ctx.factor_cache
    assert other is a                    # aliased, never recomputed
    k = jnp.zeros((64, 32)).at[0, 0].set(1.0)
    ex.run("conv", imgs[0], kernel=k)
    ex.run("conv", imgs[1], kernel=k)
    assert len(ex.ctx.mask_cache) == 1


# --- serving-engine hook ----------------------------------------------------------

def test_serving_engine_batches_aux_offload_work():
    """The opt-in serving hook coalesces aux FFT submissions from different
    requests into one boundary crossing per decode step."""
    from repro.configs import get_smoke_config
    from repro.models import init_params
    from repro.serving import Request, ServingEngine

    cfg = get_smoke_config("stablelm-1.6b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    ex = OffloadExecutor(LANED_4F, max_batch=8)
    router = PlanRouter(ex)
    engine = ServingEngine(cfg, params, batch_slots=2, max_len=32,
                           offload=router)
    engine.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=2))
    handles = [engine.submit_aux("fft", im) for im in _imgs(3, seed=9)]
    assert engine.pending_aux == 3
    assert not engine.idle()
    engine.run_to_completion(max_steps=8)
    assert all(h.ready for h in handles)
    # all three aux calls shared one host-backend invocation (batched)
    assert ex.telemetry.stats[("fft", "host")].invocations == 1
    assert ex.telemetry.stats[("fft", "host")].calls == 3


def test_serving_engine_rejects_aux_without_runtime():
    from repro.configs import get_smoke_config
    from repro.models import init_params
    from repro.serving import ServingEngine

    cfg = get_smoke_config("stablelm-1.6b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, batch_slots=1, max_len=16)
    with pytest.raises(RuntimeError):
        engine.submit_aux("fft", jnp.ones((8, 8)))


def test_warm_restores_runtime_context_n_devices():
    """warm() writes the category's device fan-out into the shared context
    for shard-shape priming; like the tracer and watchdog it suppresses,
    it must put the context back exactly as it found it."""
    ex = OffloadExecutor(LANED_4F, max_batch=4, n_devices=1)
    ex.set_n_devices("fft", 3)
    before = ex.ctx.n_devices
    ex.warm("fft", _imgs(1)[0])
    assert ex.ctx.n_devices == before


def test_telemetry_merge_reset_cover_every_field():
    """Field-by-field round-trip: merging a populated telemetry into a
    fresh one must reproduce EVERY attribute, and reset() must return to
    the pristine state.  The explicit name list is the tripwire — adding
    a field to RuntimeTelemetry without teaching merge()/reset() (and
    this list) about it fails here, not silently in production."""
    import collections

    expected = sorted([
        "stats", "_submits", "metrics", "tracer",
        "_t0", "_window_s", "_in_window_s",
    ])
    tel = RuntimeTelemetry()
    assert sorted(vars(tel)) == expected, (
        "RuntimeTelemetry grew a field this test (and likely merge/reset) "
        "does not cover")

    # populate every field through the public API
    tel.start()
    tel.note_submit("fft", t=0.0)
    tel.note_submit("fft", t=0.5)
    tel.record("fft", "optical-sim", calls=2, samples_in=8192,
               samples_out=8192, wall_s=0.5,
               modeled=LANED_4F.batched_step_cost(4096, batch=2),
               per_device=[(4096, 4096), (4096, 4096)],
               bytes_in=32768, bytes_out=32768)
    tel.record("conv", "host", calls=1, samples_in=4096, samples_out=4096,
               wall_s=0.1)
    tel.fault("fft", "error")
    tel.fault("fft", "straggle")
    tel.fallback("fft", backend="optical-sim", to="host",
                 reason="retry-exhausted")
    tel.quarantined(Quarantine().quarantine(("category", "fft"), 0.0,
                                            reason="retry-exhausted"))
    tel.recovered("fft", 0.25)
    tel.count("retries", category="fft", backend="optical-sim")
    tel.note_residency("fft", "hit")
    tel.note_residency("fft", "miss")
    tel.note_residency("conv", "eviction")
    tel.note_delta("fft", flip_fraction=0.125)
    tel.note_delta("fft")
    tel.note_delta("conv", flip_fraction=0.25)
    tel.note_window("fft", "optical-sim", in_flight=2)
    tel.note_window("conv", "host", in_flight=1)
    tel.stop()

    def norm(v):
        if dataclasses.is_dataclass(v) and not isinstance(v, type):
            return {f.name: norm(getattr(v, f.name))
                    for f in dataclasses.fields(v)}
        if isinstance(v, dict):
            return {k: norm(x) for k, x in sorted(v.items(), key=repr)}
        if isinstance(v, (collections.deque, list, tuple)):
            return [norm(x) for x in v]
        if hasattr(v, "__dict__") and not isinstance(v, (int, float, str)):
            return norm(vars(v))
        return v

    def snapshot(t):
        return {name: norm(val) for name, val in vars(t).items()}

    merged = RuntimeTelemetry()
    merged.merge(tel)
    assert snapshot(merged) == snapshot(tel)

    # and a second merge doubles the additive fields (spot-check)
    merged.merge(tel)
    assert merged.stats[("fft", "optical-sim")].calls == 4
    assert merged.fault_counts["fft"]["error"] == 2
    assert merged.fault_counts["fft"]["fallback"] == 2
    assert merged.metrics.total("quarantines") == 2
    assert merged.metrics.total("residency", category="fft",
                                event="hit") == 2
    assert merged.delta_rate("fft") == pytest.approx(0.5)
    assert merged.metrics.total("delta_full", category="fft") == 2
    assert merged.mean_flip_fraction("fft") == pytest.approx(0.125)
    assert merged.recovery_stats("fft")["n"] == 2
    assert merged.device_samples("fft") == {0: (8192, 8192),
                                            1: (8192, 8192)}
    assert merged.window_occupancy("fft") == 2.0

    tel.reset()
    assert snapshot(tel) == snapshot(RuntimeTelemetry())


# --- engines= composition mode (per-engine pipeline windows, priced) -----------


@pytest.mark.parametrize("spec,n_in", [(LANED_4F, 4096),
                                       (ANDERSON_MVM, 512)])
def test_single_engine_composition_equals_pipelined_price(spec, n_in):
    """One engine composed alone IS the pipelined price: the cross-engine
    collapse and the pipeline_depth collapse share one overlap discipline
    (`_compose_sides`), so a degenerate engines= call must agree exactly."""
    for depth in (1, 2):
        direct = spec.batched_step_cost(n_in, batch=8, pipeline_depth=depth)
        composed = spec.batched_step_cost(n_in, engines={
            "only": {"n_in": n_in, "batch": 8, "pipeline_depth": depth}})
        assert composed.total_s == pytest.approx(direct.total_s, rel=1e-12)
        assert composed.dac_s + composed.adc_s == \
            pytest.approx(direct.dac_s + direct.adc_s, rel=1e-12)


@pytest.mark.parametrize("spec,n_in", [(LANED_4F, 4096),
                                       (ANDERSON_MVM, 512)])
def test_multi_engine_composition_bounds(spec, n_in):
    """Two engines composed overlap reads behind writes: the composed wall
    is never more than the serial sum and never less than either engine
    alone (writes serialize on the shared host staging resource)."""
    kw_a = {"n_in": n_in, "batch": 8, "pipeline_depth": 2}
    kw_b = {"n_in": n_in, "batch": 4, "pipeline_depth": 2}
    a = spec.batched_step_cost(n_in, batch=8, pipeline_depth=2)
    b = spec.batched_step_cost(n_in, batch=4, pipeline_depth=2)
    both = spec.batched_step_cost(n_in, engines={"a": kw_a, "b": kw_b})
    assert both.total_s <= a.total_s + b.total_s + 1e-15
    assert both.total_s >= max(a.total_s, b.total_s) - 1e-15
    # pre-priced StepCost entries compose too (the executor's path when
    # the per-engine prices were already computed at dispatch)
    pre = spec.batched_step_cost(n_in, engines={"a": a, "b": b})
    assert pre.total_s <= a.total_s + b.total_s + 1e-15


def test_engines_mode_validation():
    with pytest.raises(ValueError):
        LANED_4F.batched_step_cost(4096, engines={})
    with pytest.raises(ValueError):
        LANED_4F.batched_step_cost(4096, engines={
            "a": {"n_in": 4096, "warp_factor": 9}})
    with pytest.raises(ValueError):
        ANDERSON_MVM.batched_step_cost(512, engines={
            "a": {"n_in": 512, "warp_factor": 9}})


# --- per-engine pipeline windows: executor accessors ---------------------------


def test_pipeline_window_accessors_and_validation():
    ex = OffloadExecutor(LANED_4F, pipeline_depth=3)
    assert ex.pipeline_window_for("fft") == 3     # global default
    ex.set_pipeline_window("fft", 1)
    assert ex.pipeline_window_for("fft") == 1
    assert ex.pipeline_window_for("conv") == 3    # untouched category
    assert ex.category_windows() == {"fft": 1}
    with pytest.raises(ValueError):
        ex.set_pipeline_window("fft", 0)


def test_window_occupancy_telemetry_recorded_per_engine():
    """Every dispatch notes its engine's in-flight occupancy; two engines
    in one flush land separate ``window_occupancy`` histograms."""
    imgs = _imgs(4, (16, 16))
    k = jnp.zeros((16, 16)).at[0, 0].set(1.0)
    ex = OffloadExecutor(LANED_4F, max_batch=2, pipeline_depth=2)
    for im in imgs:
        ex.submit("fft", im, backend="host")
        ex.submit("conv", im, kernel=k, backend="host")
    ex.flush()
    tel = ex.telemetry
    fft_w = tel.metrics.histogram("window_occupancy", category="fft",
                                  backend="host")
    conv_w = tel.metrics.histogram("window_occupancy", category="conv",
                                   backend="host")
    assert fft_w.n == 2 and conv_w.n == 2
    assert 1.0 <= tel.window_occupancy("fft") <= 2.0
    # no dispatch ever found its engine's window deeper than its depth
    assert fft_w.max <= ex.pipeline_window_for("fft") == 2
