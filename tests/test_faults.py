"""Fault-injected offload boundary: the chaos/equivalence harness.

The invariant this file locks down (the ISSUE's acceptance criterion):

    faulted execution == fault-free execution == looped host baseline

under every injected fault kind — transient dispatch errors, latency-spike
stragglers, ENOB drift, hard device loss mid-sharded-dispatch — at the
level the backend can guarantee: bit-for-bit on digital backends and for
host-degraded frames, within the converters' ENOB error bound for frames
the optical backend served.  Faults change *when and where* a frame
executes (retries, backoff, host fallback, survivor re-scatter), never
*what* it returns, and never whether it retires.

All fault schedules are seeded and all timing rides a ``ManualClock``
(injected straggles advance manual time, retry backoffs sleep through it),
so every failure in this file is reproducible to the dispatch index.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.distributed.straggler import TrailingMedianDeadline
from repro.runtime import (
    BATCHED_4F,
    ChaosBackend,
    Fault,
    FaultSchedule,
    FidelityChecker,
    ManualClock,
    OffloadExecutor,
    OffloadScheduler,
    Quarantine,
    RetryPolicy,
    Tracer,
    TransientDispatchError,
    enob_error_bound,
    reconcile,
    register_backend,
    register_chaos,
)

RTOL = 1e-5
ATOL = 1e-6


def _images(n, shape=(32, 32), seed=0):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.rand(*shape), jnp.float32) for _ in range(n)]


def _run_all(ex, imgs, category="fft"):
    with ex:
        handles = [ex.submit(category, im) for im in imgs]
    return handles


def _values(handles):
    return [np.asarray(h.value) for h in handles]


def _optical_reference(imgs, **kw):
    ex = OffloadExecutor(BATCHED_4F, default_backend="optical-sim",
                         clock=ManualClock(), **kw)
    return _values(_run_all(ex, imgs))


def _host_reference(imgs):
    ex = OffloadExecutor(BATCHED_4F, default_backend="host", max_batch=1)
    return _values(_run_all(ex, imgs))


# -- the schedule: deterministic injection --------------------------------


def test_fault_schedule_is_deterministic_and_fresh_rewinds():
    sched = FaultSchedule(0.4, seed=11)
    first = [sched.draw() for _ in range(64)]
    replay = [sched.fresh().draw() for _ in range(1)]  # fresh starts at 0
    again = sched.fresh()
    assert [again.draw() for _ in range(64)] == first
    assert replay[0] == first[0]
    assert any(f is not None for f in first)  # 40% over 64 draws must hit
    other = [FaultSchedule(0.4, seed=12).draw() for _ in range(64)]
    assert other != first


def test_fault_schedule_script_pins_indices_without_shifting_stream():
    script = {3: Fault("error")}
    a = FaultSchedule(0.5, seed=3, script=script)
    b = FaultSchedule(0.5, seed=3)
    for i in range(16):
        fa, fb = a.draw(), b.draw()
        if i == 3:
            assert fa == Fault("error")
        else:
            assert fa == fb  # scripted entry didn't shift later draws
    assert FaultSchedule(rate=0.0).draw() is None


def test_fault_kind_validation():
    with pytest.raises(ValueError):
        Fault("meteor-strike")
    with pytest.raises(ValueError):
        FaultSchedule(rate=1.5)


# -- the chaos wrapper -----------------------------------------------------


def test_chaos_backend_transparent_at_rate_zero():
    imgs = _images(6)
    name = register_chaos("optical-sim", name="chaos-t0", rate=0.0)
    ex = OffloadExecutor(BATCHED_4F, default_backend=name, max_batch=3,
                         clock=ManualClock())
    got = _values(_run_all(ex, imgs))
    ref = _optical_reference(imgs, max_batch=3)
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(g, r)  # bit-equal: pure delegation
    assert ex.telemetry.faults_total() == 0
    assert not ex.quarantine.events


def test_transient_error_is_retried_on_same_backend():
    imgs = _images(4)
    name = register_chaos("optical-sim", name="chaos-err",
                          script={0: Fault("error")})
    clk = ManualClock()
    tr = Tracer(clock=clk)
    ex = OffloadExecutor(BATCHED_4F, default_backend=name, max_batch=4,
                         clock=clk, tracer=tr)
    handles = _run_all(ex, imgs)
    ref = _optical_reference(imgs, max_batch=4)
    for h, r in zip(_values(handles), ref):
        np.testing.assert_array_equal(h, r)
    assert handles[0].backend == "chaos-err"      # retried, not degraded
    assert ex.telemetry.fault_counts["fft"]["error"] == 1
    names = {s.name for s in tr.spans()}
    assert "fault" in names and "retry" in names
    assert ex.telemetry.metrics.counter("retries", category="fft",
                                        backend="chaos-err").value == 1
    # the backoff elapsed on the injected clock, not a real sleep
    assert clk() > 0.0


def test_retry_exhaustion_degrades_to_host_in_submit_order():
    imgs = _images(5)
    name = register_chaos("optical-sim", name="chaos-dead",
                          script={i: Fault("error") for i in range(3)})
    clk = ManualClock()
    tr = Tracer(clock=clk)
    ex = OffloadExecutor(BATCHED_4F, default_backend=name, max_batch=8,
                         clock=clk, tracer=tr)
    handles = _run_all(ex, imgs)
    refs = _host_reference(imgs)
    for h, r in zip(_values(handles), refs):
        np.testing.assert_array_equal(h, r)   # digital fallback: bit-equal
    assert all(h.backend == "host" for h in handles)
    assert ex.telemetry.fault_counts["fft"]["error"] == 3
    assert ex.telemetry.fault_counts["fft"]["fallback"] == 1
    assert ex.telemetry.recovery_stats("fft")["n"] == 1
    assert ex.quarantine.is_quarantined(("category", "fft"), ex.now())
    names = {s.name for s in tr.spans()}
    assert {"fault", "retry", "fallback", "quarantine"} <= names


def test_quarantine_reroutes_then_readmits_after_probation():
    imgs = _images(12)
    name = register_chaos("optical-sim", name="chaos-q",
                          script={i: Fault("error") for i in range(3)})
    clk = ManualClock()
    ex = OffloadExecutor(BATCHED_4F, default_backend=name, max_batch=4,
                         clock=clk)
    # batch 1: exhausts retries, falls back, quarantines the category
    first = [ex.submit("fft", im) for im in imgs[:4]]
    ex.flush()
    assert all(h.backend == "host" for h in first)
    # batch 2: rerouted straight to host — the chaos backend is not even
    # consulted (its schedule index is frozen at the 3 consumed draws)
    second = [ex.submit("fft", im) for im in imgs[4:8]]
    ex.flush()
    assert all(h.backend == "host" for h in second)
    assert ex.telemetry.fault_counts["fft"]["reroute"] == 1
    be = ex._backend(name)
    assert be.schedule.index == 3
    # past window + probation: re-admitted, optical serves again
    clk.advance(ex.retry.quarantine_s + ex.retry.probation_s + 1e-3)
    assert not ex.quarantine.is_quarantined(("category", "fft"), ex.now())
    third = [ex.submit("fft", im) for im in imgs[8:]]
    ex.flush()
    assert all(h.backend == name for h in third)
    ref = _optical_reference(imgs[8:], max_batch=4)
    for h, r in zip(_values(third), ref):
        np.testing.assert_array_equal(h, r)


def test_straggler_detected_but_not_retried():
    imgs = _images(8)
    name = register_chaos("optical-sim", name="chaos-slow",
                          script={1: Fault("straggle", delay_s=2.0)})
    clk = ManualClock()
    ex = OffloadExecutor(BATCHED_4F, default_backend=name, max_batch=4,
                         clock=clk)
    handles = _run_all(ex, imgs)
    ref = _optical_reference(imgs, max_batch=4)
    for h, r in zip(_values(handles), ref):
        np.testing.assert_array_equal(h, r)   # slow, not wrong
    assert all(h.backend == name for h in handles)
    assert ex.telemetry.fault_counts["fft"]["straggle"] == 1
    assert "fallback" not in ex.telemetry.fault_counts["fft"]
    assert clk() >= 2.0  # the injected spike elapsed on the manual clock


def test_device_loss_mid_sharded_dispatch_recovers_on_survivor():
    imgs = _images(8)
    name = register_chaos("sharded", name="chaos-shard",
                          script={0: Fault("device_loss", device=1)})
    clk = ManualClock()
    ex = OffloadExecutor(BATCHED_4F, default_backend=name, max_batch=8,
                         n_devices=4, clock=clk)
    handles = _run_all(ex, imgs)
    ref = _optical_reference(imgs, max_batch=8)
    for h, r in zip(_values(handles), ref):
        np.testing.assert_allclose(h, r, rtol=RTOL, atol=ATOL)
    assert ex.telemetry.fault_counts["fft"]["device_loss"] == 1
    assert ex.quarantine.is_quarantined(("device", 1), ex.now())
    assert ex.quarantine.active_device_count(ex.now()) == 1
    # the next group re-scatters across the 3 survivors only
    ex.telemetry.reset()
    more = [ex.submit("fft", im) for im in imgs[:6]]
    ex.flush()
    assert ex.telemetry.devices_observed("fft") == 3
    for h, r in zip(_values(more), ref[:6]):
        np.testing.assert_allclose(h, r, rtol=RTOL, atol=ATOL)


def test_router_replan_shrinks_fanout_around_quarantined_devices():
    from repro.runtime import PlanRouter
    imgs = _images(8)
    clk = ManualClock()
    ex = OffloadExecutor(BATCHED_4F, default_backend="sharded", max_batch=8,
                         n_devices=4, clock=clk)
    router = PlanRouter(ex)
    for h in [ex.submit("fft", im) for im in imgs]:
        pass
    ex.flush()
    full = router.choose_sharding()["fft"][1]
    ex.quarantine.quarantine(("device", 2), ex.now(), reason="test")
    ex.quarantine.quarantine(("device", 3), ex.now(), reason="test")
    shrunk = router.choose_sharding()["fft"][1]
    assert shrunk == min(full, 2) and shrunk < full
    clk.advance(ex.retry.quarantine_s + ex.retry.probation_s + 1e-3)
    assert router.choose_sharding()["fft"][1] == full  # re-admitted


def test_drift_violation_corrected_from_shadow_and_quarantined():
    imgs = _images(4)
    name = register_chaos("optical-sim", name="chaos-drift",
                          script={0: Fault("drift", gain=64.0)})
    clk = ManualClock()
    ex = OffloadExecutor(BATCHED_4F, default_backend=name, max_batch=4,
                         clock=clk, fidelity=FidelityChecker())
    handles = _run_all(ex, imgs)
    refs = _host_reference(imgs)
    for h, r in zip(_values(handles), refs):
        np.testing.assert_array_equal(h, r)   # corrected: host bit-equal
    assert all(h.backend == "host" for h in handles)
    assert ex.telemetry.fault_counts["fft"]["drift"] == 1
    assert ex.fidelity.violations("fft")
    assert ex.quarantine.is_quarantined(("category", "fft"), ex.now())
    assert ex.quarantine.events[-1].reason == "fidelity-drift"


def test_fault_sequence_reproducible_under_manual_clock():
    imgs = _images(24, shape=(16, 16))

    def _run():
        name = register_chaos("optical-sim", name="chaos-repro",
                              rate=0.3, seed=7, straggle_s=0.5)
        ex = OffloadExecutor(BATCHED_4F, default_backend=name, max_batch=4,
                             clock=ManualClock(), fidelity=FidelityChecker())
        handles = _run_all(ex, imgs)
        return (_values(handles), [h.backend for h in handles],
                {k: dict(v) for k, v in ex.telemetry.fault_counts.items()},
                [(e.key, e.reason) for e in ex.quarantine.events])

    vals_a, be_a, faults_a, ev_a = _run()
    vals_b, be_b, faults_b, ev_b = _run()
    assert be_a == be_b and faults_a == faults_b and ev_a == ev_b
    assert faults_a  # a 30% rate over 24 calls must inject something
    for a, b in zip(vals_a, vals_b):
        np.testing.assert_array_equal(a, b)


# -- the 10% equivalence harness ------------------------------------------


def test_ten_percent_fault_rate_all_frames_retire_host_close():
    imgs = _images(48, shape=(16, 16))
    name = register_chaos("optical-sim", name="chaos-ten",
                          rate=0.10, seed=2)
    clk = ManualClock()
    tr = Tracer(clock=clk)
    ex = OffloadExecutor(BATCHED_4F, default_backend=name, max_batch=2,
                         clock=clk, tracer=tr, fidelity=FidelityChecker())
    handles = _run_all(ex, imgs)
    assert all(h.ready and h.value is not None for h in handles)
    refs = _host_reference(imgs)
    enob = min(BATCHED_4F.dac.effective_bits, BATCHED_4F.adc.effective_bits)
    bound = enob_error_bound(enob, 16.0)
    for h, r in zip(_values(handles), refs):
        rel = np.linalg.norm(h - r) / max(np.linalg.norm(r), 1e-12)
        assert rel <= bound
    assert ex.telemetry.faults_total("fft") > 0
    names = {s.name for s in tr.spans()}
    assert "fault" in names  # a 10% rate over 48 calls must show up
    # fault observability reconciles: the charged-time contract reads only
    # invocation trees, so fault/retry/quarantine spans cannot skew it
    assert tr.find("invocation")
    rec = reconcile(tr.spans(), 1.0)
    assert rec["attributed_s"] >= 0.0 and "coverage" in rec


@pytest.mark.parametrize("rate", [0.0, 0.01])
def test_low_fault_rates_all_frames_retire_within_enob_bound(rate):
    """At 0% and 1% injected fault rates (10% has the test above) every
    frame retires within the converters' ENOB bound of the host, and a
    zero rate injects nothing."""
    imgs = _images(48, shape=(64, 64))
    name = register_chaos("optical-sim", name=f"chaos-low-{rate}",
                          rate=rate, seed=2)
    ex = OffloadExecutor(BATCHED_4F, default_backend=name, max_batch=8,
                         clock=ManualClock(),
                         fidelity=FidelityChecker() if rate else None)
    handles = _run_all(ex, imgs)
    assert all(h.ready and h.value is not None for h in handles)
    enob = min(BATCHED_4F.dac.effective_bits, BATCHED_4F.adc.effective_bits)
    bound = enob_error_bound(enob, 16.0)
    for h, r in zip(_values(handles), _host_reference(imgs)):
        assert np.linalg.norm(h - r) / max(np.linalg.norm(r), 1e-12) \
            <= bound
    if rate == 0.0:
        assert ex.telemetry.faults_total() == 0


# -- the quarantine lifecycle ---------------------------------------------


def test_quarantine_window_probation_escalation_round_trip():
    q = Quarantine(window_s=1.0, probation_s=0.5, patience=3)
    key = ("device", 0)
    ev = q.quarantine(key, 10.0)
    assert ev.level == 0 and ev.until == 11.0
    assert q.is_quarantined(key, 10.5) and not q.is_quarantined(key, 11.0)
    assert q.on_probation(key, 11.2) and not q.on_probation(key, 11.5)
    # re-offend during probation: window doubles
    ev2 = q.quarantine(key, 11.2)
    assert ev2.level == 1 and ev2.until == pytest.approx(11.2 + 2.0)
    # survive the new probation cleanly: next quarantine starts over
    t_clean = ev2.probation_until + 0.1
    ev3 = q.quarantine(key, t_clean)
    assert ev3.level == 0 and ev3.until == pytest.approx(t_clean + 1.0)
    assert q.active(t_clean + 0.5) == (key,)
    assert q.active_device_count(t_clean + 0.5) == 1
    assert "quarantine" in q.summary(t_clean + 0.5)


def test_quarantine_straggle_strikes_and_forgiveness():
    q = Quarantine(window_s=1.0, patience=3)
    key = ("category", "fft")
    assert q.note_straggle(key, 0.0) is None
    assert q.note_straggle(key, 0.1) is None
    q.note_healthy(key)                      # streak forgiven
    assert q.note_straggle(key, 0.2) is None
    assert q.note_straggle(key, 0.3) is None
    ev = q.note_straggle(key, 0.4)           # third consecutive: quarantined
    assert ev is not None and ev.reason == "straggler"
    assert q.note_straggle(key, 0.5) is None  # already quarantined: no-op


def test_retry_policy_backoff_grows_with_jitter():
    p = RetryPolicy(backoff_s=1e-3, backoff_factor=2.0, jitter=0.5, seed=1)
    b1, b2, b3 = (p.backoff_for(i) for i in (1, 2, 3))
    assert 1e-3 <= b1 <= 1.5e-3
    assert 2e-3 <= b2 <= 3e-3
    assert 4e-3 <= b3 <= 6e-3
    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=0)


# -- the shared trailing-median deadline ----------------------------------


def test_trailing_median_deadline_cold_and_armed():
    det = TrailingMedianDeadline(factor=3.0, patience=2)
    assert det.deadline_s() == float("inf")       # no signal, no claim
    assert not det.observe(100.0)                 # cold: always healthy
    assert det.deadline_s() == pytest.approx(300.0)
    det2 = TrailingMedianDeadline(factor=3.0, floor_s=0.05)
    # a modeled baseline arms a cold detector
    assert det2.deadline_s(base_s=0.02) == pytest.approx(0.15)  # floor wins
    assert det2.observe(1.0, base_s=0.02)         # straggler on first obs
    assert det2.median == float("inf")            # excluded from history


def test_trailing_median_deadline_strikes_and_reset():
    det = TrailingMedianDeadline(factor=2.0, patience=2)
    for _ in range(4):
        assert not det.observe(1.0)
    assert det.observe(10.0) and not det.exhausted
    assert det.observe(10.0) and det.exhausted
    assert det.median == pytest.approx(1.0)       # stragglers never poison
    det.reset_strikes()
    assert not det.exhausted
    det.reset()
    assert det.deadline_s() == float("inf")


# -- lifecycle: nothing leaks on exception paths --------------------------


def test_exit_drains_held_and_inflight_groups_on_body_exception():
    imgs = _images(6)
    clk = ManualClock()
    ex = OffloadExecutor(BATCHED_4F, default_backend="optical-sim",
                         max_batch=8, clock=clk)
    with pytest.raises(ValueError, match="body"):
        with OffloadScheduler(ex, deadline_s=10.0, clock=clk) as sched:
            handles = [sched.submit("fft", im) for im in imgs]
            assert ex.pending == 6        # held: deadline far away
            raise ValueError("body")
    # the body's exception escaped AND every held frame still retired
    assert ex.pending == 0 and ex.in_flight == 0
    assert all(h.ready and h.value is not None for h in handles)
    ref = _optical_reference(imgs, max_batch=8)
    for h, r in zip(_values(handles), ref):
        np.testing.assert_array_equal(h, r)


def test_exit_does_not_mask_body_exception_with_backend_error():
    class _Exploding:
        name = "exploding"

        def supports(self, category, ctx):
            return True

        def run(self, category, xs, ctx, *, kernel=None, weights=None):
            raise RuntimeError("boom")   # NOT a FaultError: no retry

    register_backend("exploding", _Exploding)
    ex = OffloadExecutor(BATCHED_4F, default_backend="exploding",
                         clock=ManualClock())
    sched = OffloadScheduler(ex, deadline_s=10.0, clock=ex._clock)
    with pytest.raises(ValueError, match="body"):
        with sched:
            sched.submit("fft", _images(1)[0])
            raise ValueError("body")     # must win over the drain's boom
    # without a body exception, the drain's own error surfaces
    ex2 = OffloadExecutor(BATCHED_4F, default_backend="exploding",
                          clock=ManualClock())
    with pytest.raises(RuntimeError, match="boom"):
        with ex2:
            ex2.submit("fft", _images(1)[0])


def test_chaos_backend_delegates_supports_and_samples():
    sched = FaultSchedule()
    be = ChaosBackend("sharded", schedule=sched)
    assert be.inner_name == "sharded"
    assert be.name == "chaos-sharded"
    assert be.take_device_samples() is None
    with pytest.raises(TransientDispatchError):
        ChaosBackend("host", schedule=FaultSchedule(
            script={0: Fault("error")})).run("fft", [], None)


# -- per-engine pipeline windows under faults ------------------------------


def _retire_spy(ex):
    """Record every retirement's ``(wkey, call_ids)`` in retire order."""
    retired = []
    orig = ex._retire

    def spy(g):
        retired.append((g.wkey, [p.call_id for p in g.chunk]))
        orig(g)

    ex._retire = spy
    return retired


def test_chaos_straggler_does_not_stall_other_engine_window():
    """A latency spike on engine A's in-flight invocation must not force
    engine B to retire through it: per-engine pipeline windows gate each
    ``(category, backend)`` pair independently, so B dispatches while A's
    straggler is still in flight."""
    imgs = _images(8)
    k = jnp.zeros((32, 32)).at[0, 0].set(1.0)
    name = register_chaos("optical-sim", name="chaos-win-0",
                          script={0: Fault("straggle", delay_s=5.0)})
    clk = ManualClock()
    ex = OffloadExecutor(BATCHED_4F, max_batch=2, pipeline_depth=2,
                         clock=clk)
    retired = _retire_spy(ex)
    # engine A: two fft invocations through the chaos backend — the
    # first carries the injected straggle and stays in flight
    for im in imgs[:4]:
        ex.submit("fft", im, backend=name)
    ex.flush_async()
    assert [g.wkey for g in ex._inflight] == [("fft", name)] * 2
    # engine B: two conv invocations through the plain optical engine
    for im in imgs[4:]:
        ex.submit("conv", im, kernel=k, backend="optical-sim")
    ex.flush_async()
    # fft's window is full but conv's own window is empty: nothing
    # retires, all four invocations ride in flight
    assert retired == []
    assert [g.wkey for g in ex._inflight] == \
        [("fft", name)] * 2 + [("conv", "optical-sim")] * 2
    ex.drain()
    # retirement stays submit-ordered WITHIN each engine
    for wkey in {w for w, _ in retired}:
        ids = [i for w, grp in retired for i in grp if w == wkey]
        assert ids == sorted(ids)
    assert ex.telemetry.fault_counts["fft"]["straggle"] == 1
