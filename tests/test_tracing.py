"""Boundary-attributed tracing: span-tree invariants, export.

The invariants this file locks down (the ISSUE's acceptance criteria):

* every submitted call lands in exactly **one** invocation span
  (``call_ids`` partition the submission index space) under looped,
  batched, pipelined (``flush_async``), scheduler-held, sharded, and
  memory-budgeted tiled dispatch;
* sync leaf spans (stage / compute / fidelity-shadow) nest inside their
  invocation's window, and charged compute spans never overlap within the
  device lane — the charged decomposition satisfies
  ``stage + compute == wall`` exactly;
* under a shared ``ManualClock`` the scheduler's hold is traced *exactly*
  (a group held 30 ms yields a held span of exactly 0.030 s) with the
  release reason (full / due / futile) on the span;
* the Perfetto export is well-formed (metadata per lane, matched ``b``/``e``
  async ids, durations on ``X`` slices) and a traced 512x512 tiled+sharded
  flush reconciles its per-stage charged sums with the measured flush wall
  to within 10%;
* histograms: empty -> NaN, single sample -> exact, merge is associative
  and layout-checked; telemetry percentiles survive merge/reset and
  ``stop()`` is idempotent.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.accelerator import PROTOTYPE_4F
from repro.core.conversion import ConverterSpec
from repro.runtime import (
    Counter,
    FidelityChecker,
    Histogram,
    ManualClock,
    MemoryBudget,
    MetricsRegistry,
    OffloadExecutor,
    OffloadScheduler,
    RuntimeTelemetry,
    Span,
    Tracer,
    reconcile,
    stage_sums,
    summarize,
    to_trace_events,
    write_trace,
)

LANED_4F = dataclasses.replace(
    PROTOTYPE_4F, name="laned-4f", interface_latency_s=1.0e-3,
    dac_lanes=48, adc_lanes=48,
    slm_interface_hz=100e6, camera_interface_hz=100e6)

HI_FI_ADC = ConverterSpec(name="hifi-adc", kind="adc", bits=12,
                          rate_hz=5.0e8, power_w=0.060, enob=10.5)


def _imgs(n, shape=(32, 32), seed=0):
    key = jax.random.PRNGKey(seed)
    return [jax.random.uniform(jax.random.fold_in(key, i), shape)
            for i in range(n)]


def _invocations(spans):
    return [s for s in spans if s.name == "invocation"]


def _assert_tree_invariants(spans, n_calls):
    """The span-tree contract every dispatch mode must satisfy."""
    by_id = {s.span_id: s for s in spans}
    invs = _invocations(spans)
    # every call in exactly one invocation: call_ids partition 1..n
    ids = [cid for s in invs for cid in s.attrs["call_ids"]]
    assert sorted(ids) == list(range(1, n_calls + 1)), ids
    for s in spans:
        assert s.t1 is not None and s.t1 >= s.t0
        if s.parent_id is not None and s.parent_id in by_id:
            # children inherit the root's trace id
            assert s.trace_id == by_id[s.parent_id].trace_id
    for inv in invs:
        kids = [s for s in spans if s.parent_id == inv.span_id]
        names = {s.name for s in kids}
        assert "stage" in names and "compute" in names, names
        for k in kids:
            if k.kind == "sync":  # leaf spans nest inside the container
                assert k.t0 >= inv.t0 - 1e-9 and k.t1 <= inv.t1 + 1e-9, \
                    (k.name, k.t0, k.t1, inv.t0, inv.t1)
        # the charged decomposition is exact, not approximate
        assert inv.attrs["stage_s"] + inv.attrs["compute_s"] == \
            pytest.approx(inv.attrs["wall_s"], abs=1e-12)
    # charged compute spans never overlap within the device lane
    comps = sorted((s for s in spans
                    if s.name == "compute" and s.lane == "device"),
                   key=lambda s: s.t0)
    for a, b in zip(comps, comps[1:]):
        assert b.t0 >= a.t1 - 1e-12, (a.t1, b.t0)


# --- span-tree invariants across dispatch modes ---------------------------------

def test_batched_flush_span_tree():
    tracer = Tracer()
    ex = OffloadExecutor(LANED_4F, max_batch=8, tracer=tracer)
    imgs = _imgs(8)
    ex.warm("fft", imgs[0], batch=8)
    tracer.clear()  # warm must not leave orphan spans behind
    for im in imgs:
        ex.submit("fft", im)
    ex.flush()
    spans = tracer.spans()
    invs = _invocations(spans)
    assert len(invs) == 1 and invs[0].attrs["batch"] == 8
    assert invs[0].attrs["reason"] == "flush"
    assert len([s for s in spans if s.name == "submit"]) == 8
    _assert_tree_invariants(spans, 8)
    # the invocation carries the modeled decomposition the drift joins
    assert invs[0].attrs["modeled_total_s"] > 0.0


def test_looped_flushes_one_tree_per_call():
    tracer = Tracer()
    ex = OffloadExecutor(LANED_4F, max_batch=8, tracer=tracer)
    imgs = _imgs(4)
    ex.warm("fft", imgs[0])
    tracer.clear()
    for im in imgs:
        ex.submit("fft", im)
        ex.flush()
    spans = tracer.spans()
    invs = _invocations(spans)
    assert len(invs) == 4 and all(s.attrs["batch"] == 1 for s in invs)
    _assert_tree_invariants(spans, 4)


def test_pipelined_flush_async_span_tree():
    tracer = Tracer()
    ex = OffloadExecutor(LANED_4F, max_batch=4, pipeline_depth=2,
                         tracer=tracer)
    imgs = _imgs(12)
    ex.warm("fft", imgs[0], batch=4)
    tracer.clear()
    handles = [ex.submit("fft", im) for im in imgs]
    ex.flush_async()
    ex.drain()
    assert all(h.done() for h in handles)
    spans = tracer.spans()
    invs = _invocations(spans)
    assert len(invs) == 3  # 12 calls through max_batch=4
    _assert_tree_invariants(spans, 12)


def test_sharded_dispatch_emits_per_device_children():
    tracer = Tracer()
    ex = OffloadExecutor(LANED_4F, max_batch=8, n_devices=4,
                         default_backend="sharded", tracer=tracer)
    imgs = _imgs(8)
    ex.warm("fft", imgs[0], batch=8)
    tracer.clear()
    for im in imgs:
        ex.submit("fft", im)
    ex.flush()
    spans = tracer.spans()
    _assert_tree_invariants(spans, 8)
    scatters = [s for s in spans if s.name == "scatter"]
    assert sorted(s.lane for s in scatters) == \
        ["device0", "device1", "device2", "device3"]
    assert sum(s.attrs["frames"] for s in scatters) == 8
    # scatter spans nest under the stage span of THE invocation
    by_id = {s.span_id: s for s in spans}
    for sc in scatters:
        stage = by_id[sc.parent_id]
        assert stage.name == "stage"
        assert by_id[stage.parent_id].name == "invocation"
    # and each device's scatter staging takes time of its own
    assert all(sc.duration_s > 0.0 for sc in scatters)


def test_tiled_dispatch_one_invocation_per_tile():
    imgs = _imgs(8, shape=(64, 64))
    # budget sized to 2-frame tiles: the 8-call group streams as 4 tiles
    budget = MemoryBudget(2 * 2 * 64 * 64 * 4, source="manual", reserve=1.0)
    tracer = Tracer()
    ex = OffloadExecutor(LANED_4F, max_batch=8, mem_budget=budget,
                         tracer=tracer)
    ex.warm("fft", imgs[0], batch=8)
    tracer.clear()
    for im in imgs:
        ex.submit("fft", im)
    ex.flush()
    spans = tracer.spans()
    invs = _invocations(spans)
    assert len(invs) > 1, "budget did not split the group"
    tiles = sorted(s.attrs["tile"] for s in invs)
    assert tiles == list(range(len(invs)))
    assert all(s.attrs["tiles"] == len(invs) for s in invs)
    _assert_tree_invariants(spans, 8)


def test_fidelity_shadow_span_recorded():
    tracer = Tracer()
    spec = dataclasses.replace(LANED_4F, adc=HI_FI_ADC)
    ex = OffloadExecutor(spec, fidelity=FidelityChecker(), max_batch=4,
                         tracer=tracer)
    imgs = _imgs(4)
    ex.warm("fft", imgs[0], batch=4)
    tracer.clear()
    for im in imgs:
        ex.submit("fft", im)
    ex.flush()
    spans = tracer.spans()
    (inv,) = _invocations(spans)
    shadows = [s for s in spans if s.name == "fidelity-shadow"]
    assert len(shadows) == 1 and shadows[0].parent_id == inv.span_id
    assert inv.attrs["shadow_s"] > 0.0
    _assert_tree_invariants(spans, 4)


def test_warm_does_not_trace():
    tracer = Tracer()
    ex = OffloadExecutor(LANED_4F, max_batch=4, tracer=tracer)
    ex.warm("fft", _imgs(1)[0], batch=4)
    assert tracer.spans() == []


def test_untraced_executor_has_no_tracer_anywhere():
    ex = OffloadExecutor(LANED_4F, max_batch=4)
    assert ex.tracer is None and ex.ctx.tracer is None
    for im in _imgs(4):
        ex.submit("fft", im)
    ex.flush()  # no-op path: nothing to assert beyond not crashing


# --- scheduler: exact holds and release reasons under a ManualClock -------------

def test_held_span_exact_duration_and_due_reason():
    clk = ManualClock()
    tracer = Tracer(clock=clk)
    ex = OffloadExecutor(LANED_4F, max_batch=8, clock=clk, tracer=tracer)
    sched = OffloadScheduler(ex, deadline_s=0.03, clock=clk)
    imgs = _imgs(2)
    ex.warm("fft", imgs[0], batch=2)
    tracer.clear()
    sched.submit("fft", imgs[0])
    sched.submit("fft", imgs[1])
    clk.advance(0.03)
    sched.poll()          # deadline reached: due release
    (rel,) = [s for s in tracer.spans() if s.name == "release"]
    assert rel.attrs["reason"] == "due"
    (held,) = [s for s in tracer.spans() if s.name == "held"]
    # exact under the shared ManualClock: held precisely one deadline
    assert held.duration_s == pytest.approx(0.03, abs=1e-12)
    assert held.lane == "sched" and held.attrs["reason"] == "due"
    ex.drain()                   # retire: closes the invocation container
    (inv,) = _invocations(tracer.spans())
    assert held.parent_id == inv.span_id
    assert inv.attrs["hold_s"] == pytest.approx(0.03, abs=1e-12)
    assert ex.telemetry.metrics.counter("release", reason="due").value == 1


def test_release_reason_full_when_group_fills():
    clk = ManualClock()
    tracer = Tracer(clock=clk)
    ex = OffloadExecutor(LANED_4F, max_batch=2, clock=clk, tracer=tracer)
    sched = OffloadScheduler(ex, deadline_s=10.0, clock=clk)
    imgs = _imgs(2)
    ex.warm("fft", imgs[0], batch=2)
    tracer.clear()
    sched.submit("fft", imgs[0])
    clk.advance(0.01)
    sched.submit("fft", imgs[1])   # group full: released by submit
    (rel,) = [s for s in tracer.spans() if s.name == "release"]
    assert rel.attrs["reason"] == "full"
    (held,) = [s for s in tracer.spans() if s.name == "held"]
    assert held.duration_s == pytest.approx(0.01, abs=1e-12)


def test_release_reason_futile_when_arrivals_too_sparse():
    clk = ManualClock()
    tracer = Tracer(clock=clk)
    ex = OffloadExecutor(LANED_4F, max_batch=8, clock=clk, tracer=tracer)
    sched = OffloadScheduler(ex, deadline_s=0.5, clock=clk)
    imgs = _imgs(8)
    ex.warm("fft", imgs[0])
    tracer.clear()
    # teach the rate estimator arrivals are ~10x slower than the deadline
    for im in imgs[:6]:
        clk.advance(5.0)
        sched.submit("fft", im)
        sched.poll()
    reasons = {s.attrs["reason"]
               for s in tracer.spans() if s.name == "release"}
    assert "futile" in reasons, reasons


# --- tracer mechanics ------------------------------------------------------------

def test_ring_buffer_drops_oldest_and_counts():
    tr = Tracer(capacity=3)
    for i in range(5):
        tr.instant(f"e{i}")
    assert tr.dropped == 2
    assert [s.name for s in tr.spans()] == ["e2", "e3", "e4"]
    tr.clear()
    assert tr.spans() == [] and tr.dropped == 0


def test_tracer_rejects_bad_capacity():
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_lexical_nesting_and_trace_id_inheritance():
    clk = ManualClock()
    tr = Tracer(clock=clk)
    with tr.span("outer") as outer:
        clk.advance(1.0)
        with tr.span("inner", lane="device") as inner:
            clk.advance(0.5)
        assert tr.current() is outer
    assert tr.current() is None
    assert inner.parent_id == outer.span_id
    assert inner.trace_id == outer.trace_id == outer.span_id
    assert inner.duration_s == pytest.approx(0.5)
    assert outer.duration_s == pytest.approx(1.5)
    # completion order: inner closes first
    assert [s.name for s in tr.spans()] == ["inner", "outer"]


def test_end_clamps_reversed_clock():
    tr = Tracer(clock=ManualClock())
    s = tr.begin("x")
    done = tr.end(s, t1=s.t0 - 5.0)
    assert done.t1 == done.t0 and done.duration_s == 0.0


def test_record_clamps_and_commits():
    tr = Tracer()
    s = tr.record("w", 2.0, 1.0)
    assert s.t0 == 2.0 and s.t1 == 2.0
    assert tr.find("w") == [s]


# --- histograms -------------------------------------------------------------------

def test_histogram_empty_is_nan():
    h = Histogram()
    assert math.isnan(h.percentile(50)) and math.isnan(h.mean)


def test_histogram_single_sample_is_exact():
    h = Histogram()
    h.record(3.7e-4)
    # clamped to the observed [min, max]: one sample reports itself
    for p in (0.0, 50.0, 99.0, 100.0):
        assert h.percentile(p) == pytest.approx(3.7e-4, rel=0, abs=0)


def test_histogram_percentile_within_one_bin():
    h = Histogram()
    vals = [1e-4 * (1 + 0.01 * i) for i in range(100)]
    for v in vals:
        h.record(v)
    rel_err_bound = 10 ** (1 / h.bins_per_decade) - 1
    exact = sorted(vals)[49]
    assert h.percentile(50) == pytest.approx(exact, rel=rel_err_bound)
    with pytest.raises(ValueError):
        h.percentile(101.0)


def test_histogram_merge_associative_and_exact():
    rng = np.random.default_rng(7)
    samples = [rng.uniform(1e-6, 1e-2, 50) for _ in range(3)]
    hs = []
    for chunk in samples:
        h = Histogram()
        for v in chunk:
            h.record(float(v))
        hs.append(h)
    ab_c = hs[0].copy()
    ab_c.merge(hs[1])
    ab_c.merge(hs[2])
    bc = hs[1].copy()
    bc.merge(hs[2])
    a_bc = hs[0].copy()
    a_bc.merge(bc)
    assert ab_c.counts == a_bc.counts
    assert ab_c.n == a_bc.n == 150
    assert ab_c.min == a_bc.min and ab_c.max == a_bc.max
    one = Histogram()
    for chunk in samples:
        for v in chunk:
            one.record(float(v))
    assert one.counts == ab_c.counts  # merge == having seen all samples


def test_histogram_merge_rejects_layout_mismatch():
    a, b = Histogram(), Histogram(bins_per_decade=8)
    with pytest.raises(ValueError):
        a.merge(b)
    with pytest.raises(ValueError):
        Histogram(lo=1.0, hi=0.5)


def test_metrics_registry_merge_and_reset():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("release", reason="full").inc(2)
    b.counter("release", reason="full").inc(3)
    b.counter("release", reason="due").inc()
    b.histogram("wall").record(1e-3)
    a.merge(b)
    assert a.counter("release", reason="full").value == 5
    assert a.counter("release", reason="due").value == 1
    assert a.histogram("wall").n == 1
    # merged histograms are copies: mutating the source must not alias
    b.histogram("wall").record(1e-3)
    assert a.histogram("wall").n == 1
    a.reset()
    assert a.counters() == {} and a.histograms() == {}


# --- telemetry: idempotent stop + percentile round trips --------------------------

def test_stop_without_start_is_idempotent():
    t = RuntimeTelemetry()
    assert t.stop() == 0.0        # never started: no RuntimeError
    assert t.stop() == 0.0        # and again
    t.start()
    w = t.stop()
    assert w >= 0.0
    assert t.stop() == pytest.approx(w)  # repeated stop keeps the window


def test_reset_mid_window_then_stop():
    t = RuntimeTelemetry()
    t.start()
    t.reset()                     # reset while the window is open
    assert t.stop() == 0.0        # the open window died with the reset


def test_telemetry_percentiles_per_category_backend():
    t = RuntimeTelemetry()
    for w in (1e-3, 2e-3, 3e-3):
        t.record("fft", "optical-sim", calls=1, samples_in=64,
                 samples_out=64, wall_s=w)
    t.record("conv", "host", calls=1, samples_in=64, samples_out=64,
             wall_s=5e-3)
    pct = t.percentiles("fft", "optical-sim")
    assert set(pct) == {50.0, 95.0, 99.0}
    assert pct[50.0] == pytest.approx(2e-3, rel=0.2)
    assert pct[50.0] <= pct[95.0] <= pct[99.0]
    # no traffic for this pair: NaN, not KeyError
    assert math.isnan(t.percentiles("fft", "ideal")[50.0])
    # backend=None folds backends together
    assert t.latency_histogram("fft").n == 3


def test_telemetry_percentiles_merge_and_reset_round_trip():
    a, b = RuntimeTelemetry(), RuntimeTelemetry()
    for w in (1e-3, 2e-3):
        a.record("fft", "optical-sim", calls=1, samples_in=4,
                 samples_out=4, wall_s=w)
    for w in (3e-3, 4e-3):
        b.record("fft", "optical-sim", calls=1, samples_in=4,
                 samples_out=4, wall_s=w)
    a.merge(b)
    assert a.latency_histogram("fft", "optical-sim").n == 4
    assert a.percentiles("fft")[99.0] == pytest.approx(4e-3, rel=0.2)
    # merge copies: b's histogram stays 2 samples and survives a's reset
    a.reset()
    assert math.isnan(a.percentiles("fft")[50.0])
    assert b.latency_histogram("fft", "optical-sim").n == 2
    # summary mentions the percentile line once there are samples
    assert "p95" in b.summary()


def test_executor_records_latency_histograms():
    ex = OffloadExecutor(LANED_4F, max_batch=4)
    imgs = _imgs(8)
    ex.warm("fft", imgs[0], batch=4)
    for im in imgs:
        ex.submit("fft", im)
    ex.flush()
    h = ex.telemetry.latency_histogram("fft", "optical-sim")
    assert h.n == 2              # two invocations of batch 4
    assert all(v > 0.0 for v in ex.telemetry.percentiles("fft").values())


# --- Perfetto export --------------------------------------------------------------

def test_trace_events_well_formed():
    clk = ManualClock()
    tracer = Tracer(clock=clk)
    ex = OffloadExecutor(LANED_4F, max_batch=4, clock=clk, tracer=tracer)
    imgs = _imgs(4)
    ex.warm("fft", imgs[0], batch=4)
    tracer.clear()
    for im in imgs:
        ex.submit("fft", im)
    ex.flush()
    events = to_trace_events(tracer.spans())
    phases = {e["ph"] for e in events}
    assert phases == {"M", "X", "b", "e", "i"}
    # one thread_name metadata event per lane, sched first
    metas = [e for e in events if e["ph"] == "M"]
    assert [m["args"]["name"] for m in metas][:2] == ["sched", "host"]
    assert all(m["name"] == "thread_name" for m in metas)
    # async b/e pairs match on (cat, id)
    begins = {(e["cat"], e["id"]) for e in events if e["ph"] == "b"}
    ends = {(e["cat"], e["id"]) for e in events if e["ph"] == "e"}
    assert begins == ends and begins
    for e in events:
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
        if e["ph"] != "M":
            assert e["ts"] >= 0.0  # rebased to the earliest span
    # args survive the JSON flattening with ids attached
    inv_ev = [e for e in events
              if e["ph"] == "b" and e["name"] == "invocation"]
    assert inv_ev and "span_id" in inv_ev[0]["args"]


def test_to_trace_events_empty_and_summarize_empty():
    assert to_trace_events([]) == []
    assert "no spans" in summarize([])


def test_write_trace_round_trips(tmp_path):
    tr = Tracer(clock=ManualClock())
    with tr.span("stage"):
        pass
    path = tmp_path / "trace.json"
    payload = write_trace(str(path), tr.spans())
    import json
    on_disk = json.loads(path.read_text())
    assert on_disk == payload
    assert on_disk["traceEvents"] and on_disk["displayTimeUnit"] == "ms"


# --- acceptance: traced 512x512 tiled + sharded flush -----------------------------

@pytest.mark.slow
def test_traced_tiled_sharded_flush_reconciles(tmp_path):
    """A traced 512x512 tiled+sharded flush exports valid Perfetto JSON
    whose per-stage charged sums reconcile with the extent of the flush's
    own invocations to within 10%, each invocation annotated with its
    modeled price."""
    imgs = _imgs(8, shape=(512, 512))
    # budget admits one frame per device: the group streams as 2-frame
    # sub-invocations, each scattered across 2 devices
    budget = MemoryBudget(2 * 4 * 512 * 512 * 4, source="manual",
                          reserve=1.0)
    tracer = Tracer()
    ex = OffloadExecutor(LANED_4F, max_batch=8, n_devices=2,
                         default_backend="sharded", mem_budget=budget,
                         tracer=tracer)
    ex.warm("fft", imgs[0], batch=8)
    tracer.clear()
    for im in imgs:
        ex.submit("fft", im)
    ex.flush()
    spans = tracer.spans()
    invs = _invocations(spans)
    assert len(invs) > 1, "budget did not tile the group"
    assert all(s.attrs["tiles"] == len(invs) for s in invs)
    _assert_tree_invariants(spans, 8)
    assert any(s.name == "scatter" for s in spans)
    # per-stage charged sums reconcile with the charged extent, first
    # invocation dispatched to last retired (10% gate).  A host wall
    # around flush() would also count the release set-up before the first
    # dispatch and the bookkeeping after the last retirement, which no
    # invocation is charged for, and grows with the host's load
    extent = max(s.t1 for s in invs) - min(s.t0 for s in invs)
    rec = reconcile(spans, extent)
    assert rec["coverage"] == pytest.approx(1.0, abs=0.10), rec
    sums = stage_sums(spans)
    assert sums["stage"] + sums["compute"] == pytest.approx(sums["wall"])
    # every invocation carries the modeled price beside the measured one
    assert all(s.attrs["modeled_total_s"] > 0.0 for s in invs)
    # and the export is loadable trace-event JSON
    path = tmp_path / "trace.json"
    payload = write_trace(str(path), spans)
    assert {e["ph"] for e in payload["traceEvents"]} >= {"M", "X", "b", "e"}


def test_traced_results_match_untraced():
    """Attaching a tracer must never change results — only observe them."""
    imgs = _imgs(6)
    ex0 = OffloadExecutor(LANED_4F, max_batch=6)
    h0 = [ex0.submit("fft", im) for im in imgs]
    ex0.flush()
    ex1 = OffloadExecutor(LANED_4F, max_batch=6, tracer=Tracer())
    h1 = [ex1.submit("fft", im) for im in imgs]
    ex1.flush()
    for a, b in zip(h0, h1):
        np.testing.assert_array_equal(np.asarray(a.value),
                                      np.asarray(b.value))
        assert a.cost.total_s == b.cost.total_s


# --- program spans on the profiler's clock ----------------------------------------

def _host_events(log_dir):
    """(name, start_ns, end_ns) of every ``repro.*`` host event in the one
    ``.xplane.pb`` a ``jax.profiler`` trace wrote under ``log_dir``."""
    import glob
    import os

    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


def test_traced_flush_lands_on_the_profiler_trace(tmp_path):
    """Under ``jax.profiler`` a traced flush with a fidelity shadow writes
    its lexical spans as ``repro.<name>`` host events; the shadow and the
    stage of one invocation never overlap."""
    tracer = Tracer()
    spec = dataclasses.replace(LANED_4F, adc=HI_FI_ADC)
    ex = OffloadExecutor(spec, fidelity=FidelityChecker(), max_batch=4,
                         tracer=tracer)
    imgs = _imgs(4)
    ex.warm("fft", imgs[0], batch=4)
    with jax.profiler.trace(str(tmp_path)):
        for im in imgs:
            ex.submit("fft", im)
        ex.flush()
    events = _host_events(tmp_path)
    names = [n for n, _, _ in events]
    assert names.count("repro.stage") == 1
    assert names.count("repro.fidelity-shadow") == 1
    assert names.count("repro.release") == names.count("repro.flush") == 1
    (stage,) = [e for e in events if e[0] == "repro.stage"]
    (shadow,) = [e for e in events if e[0] == "repro.fidelity-shadow"]
    (release,) = [e for e in events if e[0] == "repro.release"]
    (flush,) = [e for e in events if e[0] == "repro.flush"]
    assert stage[2] <= shadow[1] or shadow[2] <= stage[1]
    # the release encloses the dispatch it made, the flush everything
    assert release[1] <= stage[1] and stage[2] <= release[2]
    assert all(flush[1] <= e[1] and e[2] <= flush[2] for e in events)
    # on the tracer, the same spans say they were annotated
    by_name = {s.name: s for s in tracer.spans()}
    assert by_name["stage"].annotated and by_name["fidelity-shadow"].annotated
    assert not by_name["invocation"].annotated
    assert not by_name["compute"].annotated


def test_only_lexical_spans_are_annotated():
    tr = Tracer(clock=ManualClock())
    with tr.span("outer") as outer:
        inner = tr.begin("container")
        tr.end(inner)
    rec = tr.record("held", 0.0, 1.0)
    point = tr.instant("submit")
    assert outer.annotated
    assert not (inner.annotated or rec.annotated or point.annotated)


def test_lexical_span_annotation_names_and_nesting(monkeypatch):
    """Each lexical span opens ``repro.<name>`` over its own scope: the
    annotations nest as the spans do, and close even when the body
    raises."""
    from repro.runtime import tracing

    log = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    monkeypatch.setattr(tracing, "TraceAnnotation", Recorder)
    tr = Tracer(clock=ManualClock())
    with pytest.raises(RuntimeError):
        with tr.span("a"):
            with tr.span("b", lane="device0"):
                raise RuntimeError("boom")
    assert log == [("enter", "repro.a"), ("enter", "repro.b"),
                   ("exit", "repro.b"), ("exit", "repro.a")]
    assert [s.name for s in tr.spans()] == ["b", "a"]


def test_untraced_executor_never_annotates(monkeypatch):
    """With no tracer attached the executor opens no profiler annotation
    (and so calls into neither the tracer nor the profiler)."""
    from repro.runtime import tracing

    def refuse(name):
        raise AssertionError(f"annotation {name} opened without a tracer")

    monkeypatch.setattr(tracing, "TraceAnnotation", refuse)
    ex = OffloadExecutor(LANED_4F, fidelity=FidelityChecker(), max_batch=4)
    hs = [ex.submit("fft", im) for im in _imgs(4)]
    ex.flush()
    assert all(h.ready for h in hs)
