"""Multi-device sharded offload: the runtime-equivalence property harness.

The invariant this file locks down (the ISSUE's acceptance criterion):

    sharded execution  ==  single-device batched  ==  looped per-frame

on all three backends, for random shapes / batch sizes / device counts,
ragged tails included.  Group sharding must be numerically *tight* (the
per-frame computations are identical, only their grouping changes); frame
sharding is exact for digital inners and within converter-quantization
tolerance for the optical simulator (each aperture's detector legitimately
auto-exposes its own tile).

Runs under hypothesis when installed (nightly CI uses the ``nightly``
profile for more examples); falls back to a fixed example grid otherwise.
"""

import dataclasses
import json
import os
import subprocess
import sys

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
from repro.distributed.sharding import shard_devices
from repro.runtime import (
    BATCHED_4F,
    OffloadExecutor,
    PlanRouter,
    RuntimeTelemetry,
    ShardedOpticalBackend,
    get_backend,
    kernel_halo,
    shard_sizes,
)

LANED_4F = dataclasses.replace(
    PROTOTYPE_4F, name="laned-4f", interface_latency_s=1.0e-3,
    dac_lanes=48, adc_lanes=48,
    slm_interface_hz=100e6, camera_interface_hz=100e6,
    device_sync_s=1.0e-5)

HI_FI_ADC = ConverterSpec(name="hifi-adc", kind="adc", bits=12,
                          rate_hz=5.0e8, power_w=0.060, enob=10.5)

SPEC = dataclasses.replace(LANED_4F, adc=HI_FI_ADC)
MVM = dataclasses.replace(ANDERSON_MVM, adc=HI_FI_ADC, device_sync_s=1.0e-6)

# inner backend -> its registered sharded wrapper
SHARDED_OF = {"host": "sharded-host", "optical-sim": "sharded",
              "ideal": "sharded-ideal"}


def _imgs(n, shape, seed=0):
    key = jax.random.PRNGKey(seed)
    return [jax.random.uniform(jax.random.fold_in(key, i), shape)
            for i in range(n)]


def _kernel(shape):
    """Small-support kernel incl. wrap-around rows (negative circular
    offsets), so overlap-save needs halo on BOTH sides of a tile."""
    h, w = shape
    return (jnp.zeros(shape)
            .at[0, 0].set(0.5).at[1, 2 % w].set(0.25)
            .at[h - 1, 1 % w].set(0.15).at[2 % h, 0].set(0.1))


def _run(backend, category, imgs, spec, *, max_batch, n_devices=1,
         shard_mode="group", kernel=None, weights=None, tile_k=None):
    ex = OffloadExecutor(spec, max_batch=max_batch, n_devices=n_devices,
                         default_backend=backend, shard_mode=shard_mode,
                         tile_k=tile_k)
    kw = {}
    if kernel is not None:
        kw["kernel"] = kernel
    if weights is not None:
        kw["weights"] = weights
    hs = [ex.submit(category, im, **kw) for im in imgs]
    ex.flush()
    return hs, ex


# --- the runtime-equivalence invariant (tentpole acceptance) ------------------


def check_group_equivalence(backend, category, shape, calls, max_batch,
                            n_devices, tile_k=None):
    """tiled == sharded == single-device batched == looped, to float
    tolerance.  ``tile_k`` forces memory-budgeted tiled dispatch on the
    sharded executor (each sub-invocation scatters across the fleet), so
    the invariant covers tiling composed with sharding."""
    imgs = _imgs(calls, shape)
    kernel = _kernel(shape) if category == "conv" else None
    sharded, exs = _run(SHARDED_OF[backend], category, imgs, SPEC,
                        max_batch=max_batch, n_devices=n_devices,
                        kernel=kernel, tile_k=tile_k)
    batched, _ = _run(backend, category, imgs, SPEC, max_batch=max_batch,
                      kernel=kernel)
    looped, _ = _run(backend, category, imgs, SPEC, max_batch=1,
                     kernel=kernel)
    for hs, hb, hl in zip(sharded, batched, looped):
        np.testing.assert_allclose(hs.value, hb.value, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(hb.value, hl.value, rtol=1e-5, atol=1e-5)
    # every device that took a shard is visible in telemetry, and the
    # shards jointly carried exactly the submitted boundary traffic
    per_dev = exs.telemetry.device_samples(category)
    chunk = min(max_batch, calls)
    tile = chunk if tile_k is None else max(1, min(tile_k, chunk))
    n_eff = min(n_devices, tile)
    assert exs.telemetry.devices_observed(category) == n_eff
    assert sum(s for s, _ in per_dev.values()) == \
        sum(int(im.size) for im in imgs)
    if tile_k is not None:
        # every dispatched stack honored the tile ceiling
        assert max(exs.telemetry.tile_sizes_observed(category)) <= tile


GROUP_CASES = [
    # (backend, category, shape, calls, max_batch, n_devices, tile_k) —
    # ragged tails (calls % max_batch != 0), shards (chunk % n_devices
    # != 0), and tile tails (chunk % tile_k != 0) throughout; tile_k=None
    # resolves from the (ample) budget = monolithic chunks.
    ("host", "fft", (16, 12), 5, 3, 2, None),
    ("host", "conv", (16, 12), 7, 4, 4, None),
    ("optical-sim", "fft", (16, 12), 7, 4, 4, None),
    ("optical-sim", "fft", (12, 8), 6, 6, 1, None),
    ("optical-sim", "conv", (16, 12), 5, 5, 2, None),
    ("optical-sim", "conv", (8, 8), 3, 3, 4, None),  # fewer items than devices
    ("ideal", "fft", (16, 12), 4, 2, 2, None),
    ("ideal", "conv", (16, 12), 6, 4, 4, None),
    # tiled: ragged tile tails, tile_k=1 (looped), tile_k>=K (monolithic),
    # and tiled+sharded combined (each tile scatters across the fleet)
    ("host", "fft", (16, 12), 7, 7, 1, 3),
    ("optical-sim", "fft", (16, 12), 7, 7, 1, 3),
    ("optical-sim", "fft", (12, 8), 5, 5, 1, 1),
    ("optical-sim", "fft", (12, 8), 5, 5, 1, 8),
    ("optical-sim", "conv", (16, 12), 6, 6, 2, 4),
    ("ideal", "conv", (12, 8), 7, 4, 2, 2),
]

if HAVE_HYPOTHESIS:

    @pytest.mark.slow
    @settings(deadline=None)
    @given(backend=st.sampled_from(["host", "optical-sim", "ideal"]),
           category=st.sampled_from(["fft", "conv"]),
           h=st.integers(min_value=4, max_value=20),
           w=st.integers(min_value=4, max_value=20),
           calls=st.integers(min_value=1, max_value=8),
           max_batch=st.integers(min_value=1, max_value=5),
           n_devices=st.sampled_from([1, 2, 4]),
           tile_k=st.one_of(st.none(), st.integers(min_value=1, max_value=6)))
    def test_group_sharded_equivalence_property(backend, category, h, w,
                                                calls, max_batch, n_devices,
                                                tile_k):
        check_group_equivalence(backend, category, (h, w), calls, max_batch,
                                n_devices, tile_k)


@pytest.mark.parametrize(
    "backend,category,shape,calls,max_batch,n_devices,tile_k", GROUP_CASES)
def test_group_sharded_equivalence_fixed(backend, category, shape, calls,
                                         max_batch, n_devices, tile_k):
    """Tier-1 anchor grid (the hypothesis sweep above is nightly/slow)."""
    check_group_equivalence(backend, category, shape, calls, max_batch,
                            n_devices, tile_k)


@pytest.mark.parametrize("backend", ["host", "optical-sim"])
@pytest.mark.parametrize("mode", ["group", "frame"])
def test_sharded_matmul_equivalence(backend, mode):
    key = jax.random.PRNGKey(5)
    xs = [jax.random.normal(jax.random.fold_in(key, i), (12, 16))
          for i in range(5)]
    w = jax.random.normal(jax.random.fold_in(key, 99), (16, 8))
    sharded, _ = _run(SHARDED_OF[backend], "matmul", xs, MVM, max_batch=5,
                      n_devices=3, shard_mode=mode, weights=w)
    batched, _ = _run(backend, "matmul", xs, MVM, max_batch=5, weights=w)
    looped, _ = _run(backend, "matmul", xs, MVM, max_batch=1, weights=w)
    for hs, hb, hl in zip(sharded, batched, looped):
        if mode == "frame" and backend == "optical-sim":
            # row tiles DAC-range per tile (each engine auto-ranges its
            # own activations): quantization-level differences, not bugs
            rel = float(jnp.linalg.norm(hs.value - hb.value)
                        / jnp.maximum(jnp.linalg.norm(hb.value), 1e-9))
            assert rel < 0.05, rel
        else:
            np.testing.assert_allclose(hs.value, hb.value, rtol=1e-5,
                                       atol=1e-5)
        np.testing.assert_allclose(hb.value, hl.value, rtol=1e-5, atol=1e-5)


# --- frame sharding (overlap-save tiling) -------------------------------------


def check_frame_conv(backend, shape, calls, n_devices):
    imgs = _imgs(calls, shape)
    kernel = _kernel(shape)
    sharded, ex = _run(SHARDED_OF[backend], "conv", imgs, SPEC,
                       max_batch=calls, n_devices=n_devices,
                       shard_mode="frame", kernel=kernel)
    unsharded, _ = _run(backend, "conv", imgs, SPEC, max_batch=calls,
                        kernel=kernel)
    for hs, hb in zip(sharded, unsharded):
        if backend == "optical-sim":
            # per-tile detector auto-exposure: quantization tolerance
            rel = float(jnp.linalg.norm(hs.value - hb.value)
                        / jnp.maximum(jnp.linalg.norm(hb.value), 1e-9))
            assert rel < 0.02, rel
        else:
            np.testing.assert_allclose(hs.value, hb.value, rtol=1e-4,
                                       atol=1e-5)
    n_eff = min(n_devices, shape[0])
    assert ex.telemetry.devices_observed("conv") == n_eff
    # halo rows are extra boundary traffic each device genuinely pays
    halo = sum(kernel_halo(kernel))
    s_in = sum(s for s, _ in ex.telemetry.device_samples("conv").values())
    assert s_in == calls * (shape[0] + n_eff * halo) * shape[1]


FRAME_CASES = [
    ("host", (16, 12), 2, 2),
    ("host", (17, 8), 1, 4),        # rows don't divide the device count
    ("ideal", (16, 12), 2, 3),
    ("optical-sim", (16, 12), 2, 2),
    ("optical-sim", (20, 8), 1, 4),
]

if HAVE_HYPOTHESIS:

    @pytest.mark.slow
    @settings(deadline=None)
    @given(backend=st.sampled_from(["host", "ideal", "optical-sim"]),
           h=st.integers(min_value=6, max_value=24),
           w=st.integers(min_value=4, max_value=16),
           calls=st.integers(min_value=1, max_value=3),
           n_devices=st.sampled_from([2, 3, 4]))
    def test_frame_sharded_conv_property(backend, h, w, calls, n_devices):
        check_frame_conv(backend, (h, w), calls, n_devices)


@pytest.mark.parametrize("backend,shape,calls,n_devices", FRAME_CASES)
def test_frame_sharded_conv_fixed(backend, shape, calls, n_devices):
    check_frame_conv(backend, shape, calls, n_devices)


def test_auto_mode_frame_shards_only_oversized_frames():
    """auto: deep groups scatter whole frames; a frame bigger than one
    aperture tiles; a shallow group of SMALL frames group-shards over
    fewer devices instead of trading tight numerics for fan-out (a ragged
    tail chunk must not silently flip to frame mode mid-flush)."""
    # 8x8 aperture: a 16x12 frame cannot fit one device -> tiling pays
    tiny = dataclasses.replace(SPEC, slm_pixels=(8, 8))
    ex = OffloadExecutor(tiny, max_batch=8, n_devices=4,
                         default_backend="sharded")  # shard_mode="auto"
    k = _kernel((16, 12))
    (im,) = _imgs(1, (16, 12))
    ex.submit("conv", im, kernel=k)
    ex.flush()
    # frame sharding: 4 devices saw row tiles of the single frame
    assert ex.telemetry.devices_observed("conv") == 4
    per_dev = ex.telemetry.device_samples("conv")
    assert all(s_out == 4 * 12 for _, s_out in per_dev.values())
    # the same lone frame on a roomy aperture stays whole (group over 1)
    ex2 = OffloadExecutor(SPEC, max_batch=8, n_devices=4,
                          default_backend="sharded")
    ex2.submit("conv", im, kernel=k)
    ex2.flush()
    assert ex2.telemetry.devices_observed("conv") == 1
    per_dev2 = ex2.telemetry.device_samples("conv")
    assert all(s_in == 16 * 12 for s_in, _ in per_dev2.values())  # no halo
    # fft never frame-shards (the DFT is global), even when oversized
    ex3 = OffloadExecutor(tiny, max_batch=8, n_devices=4,
                          default_backend="sharded")
    ex3.submit("fft", im)
    ex3.flush()
    assert ex3.telemetry.devices_observed("fft") == 1


# --- pricing: max-over-devices + sync epsilon ---------------------------------


def test_sharded_cost_matches_spec_n_devices_pricing():
    """The executed sharded invocation must be priced exactly as the cost
    model's n_devices mode (max-over-devices + per-device sync) — also
    when the group is shallower than the fleet (only the participating
    devices' sync barriers are charged, on both paths)."""
    for calls, counts in ((7, (1, 2, 4)), (3, (4,))):
        imgs = _imgs(calls, (16, 12))
        for n in counts:
            hs, _ = _run("sharded", "fft", imgs, SPEC, max_batch=8,
                         n_devices=n)
            want = SPEC.batched_step_cost(16 * 12, batch=calls,
                                          pipeline_depth=2, n_devices=n)
            got = hs[0].cost.total_s * len(imgs)
            assert got == pytest.approx(want.total_s, rel=1e-9)


def test_sharded_flush_priced_at_most_single_device():
    """A K=16 flush group of 128x128 fft frames scattered over 4 simulated
    accelerators is priced no higher a call than the same group on one:
    the crossings run side by side (max-over-devices plus sync)."""
    imgs = _imgs(16, (128, 128))
    per_call = {}
    for n in (1, 4):
        hs, _ = _run("sharded", "fft", imgs, BATCHED_4F, max_batch=16,
                     n_devices=n)
        per_call[n] = sum(h.cost.total_s for h in hs) / len(hs)
    assert per_call[4] <= per_call[1], per_call


def test_batched_step_cost_n_devices_semantics():
    n = 4096
    base = LANED_4F.batched_step_cost(n, batch=8, pipeline_depth=2)
    sharded = LANED_4F.batched_step_cost(n, batch=8, pipeline_depth=2,
                                         n_devices=4)
    per_shard = LANED_4F.batched_step_cost(n, batch=2, pipeline_depth=2)
    # max-over-devices: the largest (ceil) shard's cost plus the sync term
    assert sharded.total_s == pytest.approx(
        per_shard.total_s + 4 * LANED_4F.device_sync_s)
    assert sharded.conversion_s == pytest.approx(per_shard.conversion_s)
    # parallel crossings beat one serial deep crossing on a
    # streaming-dominated spec ...
    assert sharded.total_s < base.total_s
    # ... but each device still pays its own handshake: the per-call
    # boundary (conversion+interface) amortizes WORSE than single-device
    assert (sharded.conversion_s + sharded.interface_s) > \
        (base.conversion_s + base.interface_s) / 4
    # n_devices=1 is exactly the old pricing (no sync term)
    one = LANED_4F.batched_step_cost(n, batch=8, pipeline_depth=2,
                                     n_devices=1)
    assert one.total_s == base.total_s
    # a group shallower than the fleet occupies (and syncs) only batch
    # devices — matching the runtime's shard_sizes split
    shallow = LANED_4F.batched_step_cost(n, batch=3, n_devices=4)
    single = LANED_4F.batched_step_cost(n, batch=1)
    assert shallow.total_s == pytest.approx(
        single.total_s + 3 * LANED_4F.device_sync_s)
    with pytest.raises(ValueError):
        LANED_4F.batched_step_cost(n, batch=8, n_devices=0)
    # the MVM engine prices sharded streaming the same way
    m = ANDERSON_MVM
    m_sync = dataclasses.replace(m, device_sync_s=1e-6)
    assert m_sync.batched_step_cost(512, 512, batch=8, n_devices=2).total_s \
        == pytest.approx(m_sync.batched_step_cost(512, 512, batch=4).total_s
                         + 2e-6)


def test_shard_sizes_and_halo_helpers():
    assert shard_sizes(7, 4) == [2, 2, 2, 1]       # max == ceil(7/4)
    assert shard_sizes(3, 8) == [1, 1, 1]          # never more shards than items
    assert shard_sizes(8, 1) == [8]
    for total, n in ((1, 1), (5, 2), (16, 5), (9, 9)):
        sizes = shard_sizes(total, n)
        assert sum(sizes) == total
        assert max(sizes) - min(sizes) <= 1
    k = jnp.zeros((16, 8)).at[0, 0].set(1.0).at[2, 1].set(0.5)
    assert kernel_halo(k) == (2, 0)
    k_wrap = k.at[15, 0].set(0.25)                 # row -1 in circular terms
    assert kernel_halo(k_wrap) == (2, 1)
    assert kernel_halo(jnp.zeros((8, 8))) == (0, 0)


def test_shard_devices_sequential_fallback_on_one_device():
    # the CPU test environment has a single device: the dispatch helper
    # must hand back None (sequential fallback), never a short list
    assert shard_devices(1) is None
    if len(jax.devices()) < 4:
        assert shard_devices(4) is None


def test_sharded_backend_registry_and_supports():
    be = get_backend("sharded")
    assert isinstance(be, ShardedOpticalBackend)
    assert be.name == "sharded" and be.inner_name == "optical-sim"
    assert get_backend("sharded-host").name == "sharded-host"
    ex = OffloadExecutor(SPEC, n_devices=2, default_backend="sharded")
    with pytest.raises(ValueError):  # Fourier spec cannot serve matmul
        ex.submit("matmul", jnp.ones((8, 8)), weights=jnp.ones((8, 8)))
    with pytest.raises(ValueError):
        OffloadExecutor(SPEC, n_devices=0)
    with pytest.raises(ValueError):
        OffloadExecutor(SPEC, shard_mode="diagonal")


# --- warm() primes sharded dispatch shapes (satellite fix) --------------------


def test_warm_primes_sharded_dispatch_shapes():
    """The first sharded flush must not compile new shard-stack shapes:
    warm() must resolve the per-category device count exactly as dispatch
    does, so the per-device shard stacks it runs are the ones flush runs."""
    ex = OffloadExecutor(SPEC, max_batch=6, n_devices=4,
                         default_backend="sharded")
    ex.set_n_devices("fft", 3)  # operator fan-out != the global default
    be = ex._backend("sharded")
    seen: list[tuple] = []
    inner = be.inner
    orig = inner.run

    def spy(category, xs, ctx, **kw):
        seen.append((len(xs),) + tuple(xs[0].shape))
        return orig(category, xs, ctx, **kw)

    inner.run = spy
    try:
        (im,) = _imgs(1, (16, 12))
        ex.warm("fft", im, batch=6)
        warmed, seen[:] = set(seen), []
        assert not ex.telemetry.stats  # warm never records
        for h in [ex.submit("fft", x) for x in _imgs(6, (16, 12))]:
            h.get()
        flushed = set(seen)
    finally:
        inner.run = orig
    # every shard stack the flush dispatched was already warmed: 3 devices
    # over a 6-deep group -> (2, 16, 12) shards, plus the single-item path
    assert flushed <= warmed, (flushed, warmed)
    assert (2, 16, 12) in warmed


# --- telemetry: per-device aggregation ----------------------------------------


def test_telemetry_aggregates_and_merges_per_device_samples():
    t = RuntimeTelemetry()
    t.record("fft", "sharded", calls=4, samples_in=400, samples_out=400,
             wall_s=0.01, per_device=[(200, 200), (200, 200)])
    t.record("fft", "sharded", calls=2, samples_in=200, samples_out=200,
             wall_s=0.01, per_device=[(100, 100), (100, 100)])
    assert t.device_samples("fft") == {0: (300, 300), 1: (300, 300)}
    assert t.devices_observed("fft") == 2
    assert t.devices_observed("conv") == 1
    other = RuntimeTelemetry()
    other.record("fft", "sharded", calls=1, samples_in=50, samples_out=50,
                 wall_s=0.001, per_device=[(25, 25), (20, 20), (5, 5)])
    t.merge(other)
    assert t.devices_observed("fft") == 3
    assert t.device_samples("fft")[2] == (5, 5)
    assert "device_samples_in backend=sharded category=fft device=2: 5" \
        in t.summary()
    t.reset()
    assert t.device_samples("fft") == {} and t.devices_observed() == 1


def test_sharded_host_wall_counts_as_host_time():
    """Profiles must treat sharded-over-digital wall as honest host time."""
    t = RuntimeTelemetry()
    t.record("fft", "sharded-host", calls=4, samples_in=40, samples_out=40,
             wall_s=0.04)
    assert t.host_timed("fft")
    (prof,) = t.profiles(include_other=False)
    assert prof.host_s == pytest.approx(0.04)


# --- PlanRouter: devices chosen alongside max_batch (satellite property) ------


def _routed_executor(n_devices=4, max_batch=16):
    ex = OffloadExecutor(SPEC, default_backend="host", max_batch=max_batch,
                         n_devices=n_devices)
    router = PlanRouter(ex, offload_backend="sharded")
    for im in _imgs(8, (16, 16)):
        router.run("fft", im)
    return ex, router


def check_replan_sharding(batch_cap, dev_cap, deadlines):
    """Chosen (max_batch, n_devices, tile_k) never violate operator
    ceilings; batch and devices are monotone non-increasing as the
    deadline tightens, and the tile depth never exceeds the batch."""
    ex, router = _routed_executor()
    if batch_cap is not None:
        ex.set_max_batch("fft", batch_cap)
    if dev_cap is not None:
        ex.set_n_devices("fft", dev_cap)
    prev_k = prev_n = None
    # loosest first: no deadline, then deadlines tightening monotonically
    order = [None] + sorted(deadlines, reverse=True)
    for deadline in order:
        k, n, t = router.choose_sharding(deadline_s=deadline)["fft"]
        assert 1 <= k <= min(16, batch_cap or 16)
        assert 1 <= n <= min(4, dev_cap or 4, k)
        assert 1 <= t <= k
        if prev_k is not None:
            assert k <= prev_k and n <= prev_n
        prev_k, prev_n = k, n
        router.replan(deadline_s=deadline)  # applying must respect the caps
        assert ex.max_batch_for("fft") == k
        assert ex.n_devices_for("fft") == n
        assert ex.category_tile_ks()["fft"] == t


REPLAN_CASES = [
    (None, None, [1e-1, 1e-2, 1e-3, 1e-4]),
    (8, 2, [5e-2, 5e-3, 5e-4]),
    (4, None, [1e-2, 1e-3]),
    (None, 1, [1e-2, 2e-4]),
]

if HAVE_HYPOTHESIS:

    @pytest.mark.slow
    @settings(deadline=None)
    @given(batch_cap=st.one_of(st.none(), st.integers(1, 16)),
           dev_cap=st.one_of(st.none(), st.integers(1, 4)),
           deadlines=st.lists(
               st.floats(min_value=1e-5, max_value=1.0), min_size=1,
               max_size=5))
    def test_replan_sharding_property(batch_cap, dev_cap, deadlines):
        check_replan_sharding(batch_cap, dev_cap, deadlines)


@pytest.mark.parametrize("batch_cap,dev_cap,deadlines", REPLAN_CASES)
def test_replan_sharding_fixed(batch_cap, dev_cap, deadlines):
    check_replan_sharding(batch_cap, dev_cap, deadlines)


def test_replan_restores_operator_device_bound_after_deadline():
    """A deadline-lowered device fan-out must snap back to the operator's
    bound (not the global cap) when the deadline relaxes."""
    ex, router = _routed_executor(n_devices=4, max_batch=16)
    ex.set_n_devices("fft", 2)  # operator bound below the global 4
    router.replan()
    assert ex.n_devices_for("fft") == 2
    # deadline so tight the batch collapses to 1 -> 1 device
    router.replan(deadline_s=1e-9)
    assert ex.max_batch_for("fft") == 1
    assert ex.n_devices_for("fft") == 1
    router.replan()  # relaxed: back to the operator's 2, not the global 4
    assert ex.n_devices_for("fft") == 2
    assert ex.max_batch_for("fft") == 16


# --- real multi-device dispatch (forced host devices, subprocess) -------------

_MULTI_DEVICE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp
import numpy as np
from repro.runtime import OffloadExecutor
from repro.distributed.sharding import shard_devices

assert len(jax.devices()) == 4
assert shard_devices(4) is not None and len(shard_devices(4)) == 4

key = jax.random.PRNGKey(0)
imgs = [jax.random.uniform(jax.random.fold_in(key, i), (16, 12))
        for i in range(8)]
kern = (jnp.zeros((16, 12)).at[0, 0].set(0.5).at[1, 2].set(0.25)
        .at[15, 1].set(0.15))


def run(backend, category, xs, n_devices, shard_mode, **kw):
    ex = OffloadExecutor(max_batch=8, n_devices=n_devices,
                         default_backend=backend, shard_mode=shard_mode)
    hs = [ex.submit(category, x, **kw) for x in xs]
    ex.flush()
    return hs, ex


# group-sharded fft over the host inner: shards land on distinct devices
hs, ex = run("sharded-host", "fft", imgs, 4, "group")
ss, _ = run("host", "fft", imgs, 1, "auto")
for a, b in zip(hs, ss):
    np.testing.assert_allclose(np.asarray(a.value), np.asarray(b.value),
                               rtol=1e-5, atol=1e-6)
placements = {next(iter(h.value.devices())).id for h in hs}

# group-sharded OPTICAL conv: each device gets its own committed kernel
# copy, so the Fourier-mask cache must be device-aware (regression: a
# content-only cache key served device 0's mask to every shard)
ho, exo = run("sharded", "conv", imgs, 4, "group", kernel=kern)
so, _ = run("optical-sim", "conv", imgs, 1, "auto", kernel=kern)
for a, b in zip(ho, so):
    np.testing.assert_allclose(np.asarray(a.value), np.asarray(b.value),
                               rtol=1e-5, atol=1e-5)

# frame sharding on real devices: per-device tiles are committed to
# distinct devices and must be re-homed before reassembly (regression:
# jnp.concatenate over mixed-device operands raised)
for backend, single, tol in (("sharded-host", "host", 1e-5),
                             ("sharded", "optical-sim", None)):
    hf, _ = run(backend, "conv", imgs[:1], 4, "frame", kernel=kern)
    sf, _ = run(single, "conv", imgs[:1], 1, "auto", kernel=kern)
    got, want = np.asarray(hf[0].value), np.asarray(sf[0].value)
    if tol is not None:
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=tol)
    else:  # per-tile detector auto-exposure: quantization tolerance
        rel = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert rel < 0.05, rel

out = {"devices_used": sorted(placements),
       "per_device": {str(k): v for k, v in
                      ex.telemetry.device_samples("fft").items()},
       "optical_group_devices": len(exo.telemetry.device_samples("conv"))}
print("RESULT:" + json.dumps(out))
"""


@pytest.mark.slow
def test_sharded_dispatch_scatters_across_forced_devices():
    """With real (forced host) devices present, shards land on distinct
    devices and results still match the single-device batched path — for
    group AND frame sharding, over digital and optical inners (the
    mixed-device mask-cache and tile-reassembly regressions)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, "-c", _MULTI_DEVICE_SCRIPT],
        cwd=os.path.join(os.path.dirname(__file__), ".."), env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][0]
    out = json.loads(line[len("RESULT:"):])
    assert len(out["devices_used"]) == 4, out
    assert len(out["per_device"]) == 4
    assert out["optical_group_devices"] == 4


# --- per-engine windows + device-resident placements --------------------------


def test_warm_primes_per_engine_window_and_placed_shapes():
    """warm() must run the category at its per-engine window depth (the
    context's pipeline depth feeds the tile choice and the modeled price)
    and restore the context afterwards — and the shard stacks it warms
    must cover what a committed-placement flush dispatches: the placement
    regroups frames by the same ``shard_sizes`` split the re-scatter path
    uses, so warmed shapes ARE placed shapes by construction."""
    ex = OffloadExecutor(SPEC, max_batch=6, n_devices=3,
                         default_backend="sharded-host", residency=True)
    ex.set_pipeline_window("fft", 3)
    be = ex._backend("sharded-host")
    seen: list[tuple] = []
    depths: list[int] = []
    inner = be.inner
    orig = inner.run

    def spy(category, xs, ctx, **kw):
        seen.append((len(xs),) + tuple(xs[0].shape))
        depths.append(ctx.pipeline_depth)
        return orig(category, xs, ctx, **kw)

    inner.run = spy
    try:
        (im,) = _imgs(1, (16, 12))
        saved_depth = ex.ctx.pipeline_depth
        ex.warm("fft", im, batch=6)
        assert depths and all(d == 3 for d in depths)  # pinned window depth
        assert ex.ctx.pipeline_depth == saved_depth    # restored after warm
        warmed, seen[:] = set(seen), []
        for h in [ex.submit("fft", x) for x in _imgs(6, (16, 12))]:
            h.get()
        flushed = set(seen)
        flush_depths = list(depths[len(warmed):] or depths)
    finally:
        inner.run = orig
    assert flushed <= warmed, (flushed, warmed)
    assert ex.ctx.pipeline_depth == 3  # dispatch ran at the pinned window


def test_placement_not_committed_without_residency_or_off_mesh():
    """Placements are gated exactly like shard residency: no residency
    cache, or no real device mesh (the sequential off-mesh fallback),
    means no commit — dispatch stays on the legacy re-scatter path."""
    imgs = _imgs(6, (16, 12))
    ex = OffloadExecutor(SPEC, max_batch=6, n_devices=3,
                         default_backend="sharded-host")
    for h in [ex.submit("fft", x) for x in imgs]:
        h.get()
    assert not ex._backend("sharded-host")._placements
    # residency on, but a single-CPU mesh cannot host 3 shards: the
    # sequential fallback commits nothing (shard_devices returns None)
    ex_r = OffloadExecutor(SPEC, max_batch=6, n_devices=3,
                          default_backend="sharded-host", residency=True)
    for h in [ex_r.submit("fft", x) for x in imgs]:
        h.get()
    if shard_devices(3) is None:
        assert not ex_r._backend("sharded-host")._placements


_PLACEMENT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import time
import jax
import numpy as np
from repro.runtime import OffloadExecutor

assert len(jax.devices()) == 4

K = 16
key = jax.random.PRNGKey(3)
imgs = [jax.random.uniform(jax.random.fold_in(key, i), (16, 12))
        for i in range(K)]

# looped single-device host baseline: the equivalence anchor
base = OffloadExecutor(max_batch=1, default_backend="host")
want = [np.asarray(h.value) for h in
        ([base.submit("fft", im) for im in imgs], base.flush())[0]]


def check(handles):
    for h, w in zip(handles, want):
        np.testing.assert_array_equal(np.asarray(h.value), w)


def timed(ex, reps=3):
    best = float("inf")
    for _ in range(reps):
        hs = [ex.submit("fft", im) for im in imgs]
        t0 = time.perf_counter()
        ex.flush()
        best = min(best, time.perf_counter() - t0)
    return best, hs


# 1. commit + repeat-flush hits, bit-equal to the looped baseline
ex = OffloadExecutor(max_batch=K, n_devices=4,
                     default_backend="sharded-host", residency=True)
ex.warm("fft", imgs[0], batch=K)
hs = [ex.submit("fft", im) for im in imgs]
ex.flush()
check(hs)
be = ex._backend("sharded-host")
assert be._placements, "first flush must commit a placement"
pkey, pl = next(iter(be._placements.items()))
assert pl.pool == [0, 1, 2, 3] and pl.frames == K
hs = [ex.submit("fft", im) for im in imgs]
ex.flush()
check(hs)
hits = ex.telemetry.metrics.total("residency", category="fft", event="hit")
assert hits >= K, hits   # repeat flush rode the placement

# 2. tiled dispatch routes tile sub-stacks through the SAME placement
ex.set_tile_k("fft", 5)
hs = [ex.submit("fft", im) for im in imgs]
ex.flush()
check(hs)
assert be._placements, "tiled flush must re-commit, not abandon, placement"

# 3. device loss mid-placed-dispatch: quarantine, drop, serve from survivor
ex.set_tile_k("fft", K)
ex.ctx.lost_devices = frozenset({1})
hs = [ex.submit("fft", im) for im in imgs]
ex.flush()
ex.ctx.lost_devices = frozenset()
check(hs)                               # every frame retired, bit-equal
assert ex.quarantine.is_quarantined(("device", 1), ex.now())
assert not be._placements, "fault must drop the placement"

# 4. next flush rebuilds on the survivors only
hs = [ex.submit("fft", im) for im in imgs]
ex.flush()
check(hs)
(_, pl2), = be._placements.items()
assert pl2.pool == [0, 2, 3], pl2.pool  # quarantined device excluded

# 5. CI-smoke mirror: resident repeat-flush wall <= re-scatter wall at K=16
rescatter = OffloadExecutor(max_batch=K, n_devices=4,
                            default_backend="sharded-host")
rescatter.warm("fft", imgs[0], batch=K)
wall_rescatter, hs = timed(rescatter)
check(hs)
resident = OffloadExecutor(max_batch=K, n_devices=4,
                           default_backend="sharded-host", residency=True)
resident.warm("fft", imgs[0], batch=K)
for im in imgs:
    resident.submit("fft", im)
resident.flush()                        # priming flush commits + stages
wall_resident, hs = timed(resident)
check(hs)

print("RESULT:" + json.dumps({
    "resident_wall_s": wall_resident,
    "rescatter_wall_s": wall_rescatter,
    "hit_rate": resident.telemetry.residency_hit_rate("fft"),
}))
"""


@pytest.mark.slow
def test_placement_lifecycle_on_forced_devices():
    """Commit -> repeat-flush hits -> tiled re-commit -> device-loss drop ->
    survivor rebuild, bit-equal to the looped host baseline throughout,
    and the resident repeat-flush wall beats the re-scatter wall (the CI
    multi-device smoke's assertion, runnable locally)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, "-c", _PLACEMENT_SCRIPT],
        cwd=os.path.join(os.path.dirname(__file__), ".."), env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][0]
    out = json.loads(line[len("RESULT:"):])
    assert out["resident_wall_s"] <= out["rescatter_wall_s"], out
    assert out["hit_rate"] > 0.5, out
