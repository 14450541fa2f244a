"""Memory-budgeted tiled dispatch: unit + integration coverage.

The tentpole invariant (tiled == monolithic == looped on every backend,
sharded and scheduler-held paths included) lives in the property harnesses
of ``tests/test_sharded.py`` / ``tests/test_scheduler.py``; this file
covers the subsystem itself: budget detection and arithmetic, tile/block
choice, the cost model's ``tile_k``/``mem_budget`` mode on both
accelerator families, the executor's budget-driven dispatch + warm-up
parity, telemetry's per-tile samples and measured bytes/frame, the
block-keyed kernel caches (the stale-compile satellite), and the router's
joint ``(max_batch, n_devices, tile_k)`` choice.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.accelerator import ANDERSON_MVM, PROTOTYPE_4F
from repro.core.conversion import ConverterSpec
from repro.runtime import (
    BATCHED_4F,
    FidelityChecker,
    MemoryBudget,
    OffloadExecutor,
    PlanRouter,
    RuntimeTelemetry,
    choose_blocks,
    choose_tile,
    tile_sizes,
)
from repro.runtime.tiling import (_INTERMEDIATE_FACTOR, BYTES_F32,
                                  HBM_STAGING_SHARE)

LANED_4F = dataclasses.replace(
    PROTOTYPE_4F, name="laned-4f", interface_latency_s=1.0e-3,
    dac_lanes=48, adc_lanes=48,
    slm_interface_hz=100e6, camera_interface_hz=100e6,
    device_sync_s=1.0e-5)

HI_FI_ADC = ConverterSpec(name="hifi-adc", kind="adc", bits=12,
                          rate_hz=5.0e8, power_w=0.060, enob=10.5)

SPEC = dataclasses.replace(LANED_4F, adc=HI_FI_ADC)


def _imgs(n, shape, seed=0):
    key = jax.random.PRNGKey(seed)
    return [jax.random.uniform(jax.random.fold_in(key, i), shape)
            for i in range(n)]


def _budget_for_frames(n_samples: int, frames: int,
                       pipeline_depth: int = 2) -> MemoryBudget:
    """A manual budget sized to admit exactly ``frames`` frames of
    ``n_samples`` f32 samples under the standard working-set model."""
    bpf = int(BYTES_F32 * 2 * n_samples * _INTERMEDIATE_FACTOR)
    return MemoryBudget(bpf * pipeline_depth * frames, source="manual",
                        reserve=1.0)


# --- MemoryBudget -------------------------------------------------------------


def test_memory_budget_arithmetic():
    b = MemoryBudget(1000, reserve=0.5)
    assert b.spendable_bytes == 500
    assert b.frames_within(100) == 5
    assert b.frames_within(100, pipeline_depth=2) == 2
    # a lone frame bigger than the whole budget still dispatches
    assert b.frames_within(10_000) == 1
    with pytest.raises(ValueError):
        b.frames_within(0)
    with pytest.raises(ValueError):
        MemoryBudget(1000, reserve=0.0)
    u = MemoryBudget.unlimited()
    assert u.is_unlimited
    assert u.frames_within(10**9) is None
    assert u.tile_for(10**9) is None


class _Device:
    """A stand-in for a jax device: ``memory_stats()`` returns ``stats``."""

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


# ``memory_stats()["bytes_limit"]`` of one TPU v5e chip
V5E_BYTES_LIMIT = 16_909_336_064
V5E = _Device({"bytes_limit": V5E_BYTES_LIMIT, "bytes_in_use": 0})


def test_memory_budget_detect_off_tpu_is_llc_derived():
    b = MemoryBudget.detect(platform="cpu")
    assert b.source == "llc" and b.bytes_limit > 0
    t = MemoryBudget.detect(platform="tpu", device=V5E)
    assert t.source == "hbm" and t.bytes_limit == V5E_BYTES_LIMIT
    assert t.spendable_bytes == int(V5E_BYTES_LIMIT * HBM_STAGING_SHARE)
    # the default platform resolves without error
    assert MemoryBudget.detect().source in ("llc", "hbm", "vmem")


@pytest.mark.parametrize("device", [object(), _Device(None),
                                    _Device({"bytes_in_use": 0})],
                         ids=["no-memory-stats", "none", "no-bytes-limit"])
def test_memory_budget_detect_on_tpu_falls_back_to_vmem(device):
    """A TPU device that reports no HBM limit keeps the VMEM budget."""
    t = MemoryBudget.detect(platform="tpu", device=device)
    assert t == MemoryBudget.vmem()
    assert t.source == "vmem" and t.bytes_limit == 16 * 1024 * 1024


@pytest.mark.parametrize("device,tile", [(V5E, 8), (_Device(None), 1)],
                         ids=["hbm", "vmem-fallback"])
def test_detected_tpu_budget_stages_a_full_aperture_burst_whole(device,
                                                                tile):
    """An 8-frame 1024x768 group dispatches once under the HBM budget, and
    frame by frame under the VMEM one (25.2 MB a frame at depth 2)."""
    budget = MemoryBudget.detect(platform="tpu", device=device)
    assert choose_tile(1024 * 768, 8, budget).tile_k == tile
    assert budget.tile_for_group(1024 * 768, None, 8) == tile


# --- choose_tile / tile_sizes -------------------------------------------------


def test_tile_sizes_covers_ragged_tails():
    assert tile_sizes(7, 3) == [3, 3, 1]
    assert tile_sizes(8, 4) == [4, 4]
    assert tile_sizes(5, 1) == [1, 1, 1, 1, 1]
    assert tile_sizes(3, 9) == [3]          # tile clamps to the group
    with pytest.raises(ValueError):
        tile_sizes(0, 1)


def test_choose_tile_monolithic_under_ample_budget():
    # an explicit ample budget, not detect(): tier-1 must not depend on
    # the host machine's LLC size
    plan = choose_tile(128 * 128, 16, _budget_for_frames(128 * 128, 16))
    assert plan.monolithic and plan.tile_k == 16 and plan.tiles == 1
    plan_u = choose_tile(10**8, 64, MemoryBudget.unlimited())
    assert plan_u.monolithic


def test_choose_tile_splits_oversized_groups():
    budget = _budget_for_frames(512 * 512, 3)
    plan = choose_tile(512 * 512, 16, budget)
    # cap 3 admits the even split 2x8 (2*2 > 3): no ragged tail
    assert plan.tile_k == 2 and plan.sizes() == [2] * 8
    # a prime group depth cannot split evenly above 1: take the cap
    plan_p = choose_tile(512 * 512, 17, budget)
    assert plan_p.tile_k == 3 and plan_p.sizes()[-1] == 2
    # one frame over budget degenerates to looped
    tiny = _budget_for_frames(512 * 512, 1)
    assert choose_tile(4 * 512 * 512, 8, tiny).tile_k == 1


def test_choose_tile_monotone_in_budget():
    prev = None
    for frames in (1, 2, 4, 8, 16):
        t = choose_tile(256 * 256, 16, _budget_for_frames(256 * 256,
                                                          frames)).tile_k
        if prev is not None:
            assert t >= prev
        prev = t
    assert prev == 16


# --- choose_blocks ------------------------------------------------------------


def test_choose_blocks_defaults_without_budget():
    for budget in (None, MemoryBudget.unlimited()):
        plan = choose_blocks(16, 512, 512, 512, budget)
        assert plan.key == (1, 128, 128, 128)


def test_choose_blocks_shrinks_to_fit_and_grows_bb():
    # a tight budget shrinks the sublane block below the MXU-preferred
    # 128; the lane blocks stay 128 (a TPU block's last dim must be a
    # multiple of 128 or the whole dim), and bb gives way first
    tight = MemoryBudget(64 * 64 * 4 * 8, source="manual", reserve=1.0)
    plan = choose_blocks(16, 512, 512, 512, tight)
    assert plan.bm < 128 and plan.bm % 8 == 0
    assert (plan.bk, plan.bn) == (128, 128)
    assert plan.bb == 1
    # an ample budget keeps the 128 cube and batches frames per grid step
    ample = MemoryBudget(16 * 1024 * 1024, source="manual", reserve=0.75)
    plan_a = choose_blocks(16, 512, 512, 512, ample)
    assert (plan_a.bm, plan_a.bk, plan_a.bn) == (128, 128, 128)
    assert plan_a.bb > 1 and 16 % plan_a.bb == 0
    # blocks always divide the dims they tile
    for batch, m in ((6, 96), (5, 40)):
        p = choose_blocks(batch, m, m, m, ample)
        assert batch % p.bb == 0 and m % p.bm == 0 \
            and m % p.bk == 0 and m % p.bn == 0


def test_batched_pallas_kernels_honor_bb():
    """bb > 1 (several frames per grid step sharing one factor-block load)
    must be bit-identical to bb = 1 — interpret mode executes the same
    kernel body TPU runs."""
    from repro.kernels.optical_dft import (
        dft_matrix_factors,
        dft_stage1_batched,
        dft_stage2_batched,
        optical_dft2_intensity_batched,
    )
    h = w = 16
    a = jax.random.uniform(jax.random.PRNGKey(3), (4, h, w))
    whr, whi = dft_matrix_factors(h)
    wwr, wwi = dft_matrix_factors(w)
    tr1, ti1 = dft_stage1_batched(whr, whi, a, dac_bits=8, bb=1)
    tr2, ti2 = dft_stage1_batched(whr, whi, a, dac_bits=8, bb=2)
    np.testing.assert_allclose(tr1, tr2, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(ti1, ti2, rtol=1e-6, atol=1e-6)
    out1 = dft_stage2_batched(tr1, ti1, wwr, wwi, bb=1)
    out2 = dft_stage2_batched(tr1, ti1, wwr, wwi, bb=4)
    np.testing.assert_allclose(out1, out2, rtol=1e-6, atol=1e-6)
    full1 = optical_dft2_intensity_batched(a, dac_bits=8, use_pallas=True,
                                           bb=1)
    full2 = optical_dft2_intensity_batched(a, dac_bits=8, use_pallas=True,
                                           bb=2)
    np.testing.assert_allclose(full1, full2, rtol=1e-6, atol=1e-6)


# --- the cost model's tile mode -----------------------------------------------


@pytest.mark.parametrize("spec,n_in,n_out", [
    (SPEC, 4096, 4096),
    (dataclasses.replace(ANDERSON_MVM, adc=HI_FI_ADC), 512, 512),
])
def test_batched_step_cost_tile_mode(spec, n_in, n_out):
    mono = spec.batched_step_cost(n_in, n_out, batch=8, pipeline_depth=2)
    # tile_k >= batch (or None) is exactly the monolithic price
    same = spec.batched_step_cost(n_in, n_out, batch=8, pipeline_depth=2,
                                  tile_k=8)
    assert same.total_s == pytest.approx(mono.total_s, rel=1e-12)
    over = spec.batched_step_cost(n_in, n_out, batch=8, pipeline_depth=2,
                                  tile_k=99)
    assert over.total_s == pytest.approx(mono.total_s, rel=1e-12)
    # tiling pays per-tile prologues: the un-overlapped (depth-1) tiled
    # stream costs exactly the sum of its per-tile invocations
    tiled_serial = spec.batched_step_cost(n_in, n_out, batch=8, tile_k=3)
    per = [spec.batched_step_cost(n_in, n_out, batch=b) for b in (3, 3, 2)]
    assert tiled_serial.total_s == pytest.approx(
        sum(c.total_s for c in per), rel=1e-12)
    assert tiled_serial.conversion_s == pytest.approx(
        sum(c.conversion_s for c in per), rel=1e-12)
    # pipeline overlap across tiles strictly helps the tiled stream
    tiled_piped = spec.batched_step_cost(n_in, n_out, batch=8,
                                         pipeline_depth=2, tile_k=3)
    assert tiled_piped.total_s < tiled_serial.total_s
    # ...but each tile still pays its own handshake: tiled boundary >= mono
    assert tiled_serial.interface_s >= mono.interface_s
    with pytest.raises(ValueError):
        spec.batched_step_cost(n_in, n_out, batch=8, tile_k=0)


def test_batched_step_cost_mem_budget_duck_typing():
    """``mem_budget=`` must resolve the same tile depth ``choose_tile``
    picks under the same budget — one model, one resolution (divisor
    refinement included), two entry points."""
    n = 512 * 512
    budget = _budget_for_frames(n, 3)
    tile = choose_tile(n, 16, budget, pipeline_depth=2).tile_k
    assert tile == 2                 # the even split, NOT the raw cap of 3
    via_budget = SPEC.batched_step_cost(n, batch=16, pipeline_depth=2,
                                        mem_budget=budget)
    via_tile = SPEC.batched_step_cost(n, batch=16, pipeline_depth=2,
                                      tile_k=tile)
    assert via_budget.total_s == pytest.approx(via_tile.total_s, rel=1e-12)
    # ...and differs from pricing at the unrefined cap: the divisor split
    # dispatches more tiles, hence more prologues
    via_cap = SPEC.batched_step_cost(n, batch=16, pipeline_depth=2,
                                     tile_k=3)
    assert via_budget.total_s != pytest.approx(via_cap.total_s, rel=1e-12)
    # unlimited budget = monolithic
    mono = SPEC.batched_step_cost(n, batch=16, pipeline_depth=2)
    free = SPEC.batched_step_cost(n, batch=16, pipeline_depth=2,
                                  mem_budget=MemoryBudget.unlimited())
    assert free.total_s == pytest.approx(mono.total_s, rel=1e-12)


def test_batched_step_cost_tile_composes_with_sharding_and_hold():
    n = 4096
    # each tile scatters across the fleet and re-pays the sync barrier
    tiled_sharded = SPEC.batched_step_cost(n, batch=8, tile_k=4, n_devices=2)
    per_tile = SPEC.batched_step_cost(n, batch=4, n_devices=2)
    assert tiled_sharded.total_s == pytest.approx(2 * per_tile.total_s,
                                                 rel=1e-12)
    # hold is charged once to the whole stream, not once per tile
    held = SPEC.batched_step_cost(n, batch=8, tile_k=4, hold_s=0.25)
    base = SPEC.batched_step_cost(n, batch=8, tile_k=4)
    assert held.hold_s == 0.25
    assert held.total_s == pytest.approx(base.total_s + 0.25, rel=1e-12)


# --- executor: budget-driven dispatch -----------------------------------------


def test_executor_tiles_groups_against_the_budget():
    shape = (16, 12)
    budget = _budget_for_frames(16 * 12, 2)
    ex = OffloadExecutor(SPEC, max_batch=8, mem_budget=budget)
    imgs = _imgs(7, shape)
    hs = [ex.submit("fft", im) for im in imgs]
    ex.flush()
    st = ex.telemetry.stats[("fft", "optical-sim")]
    # 7 calls, cap 8, tile 2 -> stacks of 2,2,2,1
    assert st.invocations == 4 and st.calls == 7
    assert ex.telemetry.tile_sizes_observed("fft") == {1: 1, 2: 3}
    # each handle knows the invocation depth it actually shared
    assert sorted(h.batch for h in hs) == [1, 2, 2, 2, 2, 2, 2]
    # measured bytes/frame: f32 in + f32 out per sample
    assert ex.telemetry.bytes_per_frame("fft") == 2 * 16 * 12 * 4


def test_executor_tile_k_override_beats_budget():
    ex = OffloadExecutor(SPEC, max_batch=8, tile_k=3,
                         mem_budget=MemoryBudget.unlimited())
    imgs = _imgs(6, (8, 8))
    for h in [ex.submit("fft", im) for im in imgs]:
        pass
    ex.flush()
    assert ex.telemetry.tile_sizes_observed("fft") == {3: 2}
    # per-category pin wins over the global override
    ex2 = OffloadExecutor(SPEC, max_batch=8, tile_k=3,
                          mem_budget=MemoryBudget.unlimited())
    ex2.set_tile_k("fft", 2)
    for h in [ex2.submit("fft", im) for im in imgs]:
        pass
    ex2.flush()
    assert ex2.telemetry.tile_sizes_observed("fft") == {2: 3}
    with pytest.raises(ValueError):
        ex2.set_tile_k("fft", 0)
    with pytest.raises(ValueError):
        OffloadExecutor(SPEC, tile_k=0)


def test_resolve_tile_k_uses_matmul_output_size():
    """The working-set model must see the matmul's real result footprint
    (rows x weight cols), not assume n_out == n_in — otherwise the
    executor's tile drifts from the router's and the cost model's near
    the budget boundary."""
    import jax.numpy as jnp

    from repro.core.accelerator import ANDERSON_MVM

    mvm = dataclasses.replace(ANDERSON_MVM, adc=HI_FI_ADC)
    x = jnp.ones((64, 64))                 # n_in = 4096
    w_small = jnp.ones((64, 4))            # n_out = 256
    w_big = jnp.ones((64, 1024))           # n_out = 65536
    # budget sized so the verdict flips on the output term alone
    budget = MemoryBudget(
        int(BYTES_F32 * (4096 + 4096) * _INTERMEDIATE_FACTOR) * 2 * 4,
        source="manual", reserve=1.0)
    ex = OffloadExecutor(mvm, max_batch=8, mem_budget=budget)
    small = ex.resolve_tile_k("matmul", x, 8, weights=w_small)
    big = ex.resolve_tile_k("matmul", x, 8, weights=w_big)
    assert small > big
    # and each matches choose_tile fed the same (n_in, n_out)
    assert small == choose_tile(4096, 8, budget, n_out=256).tile_k
    assert big == choose_tile(4096, 8, budget, n_out=65536).tile_k


def test_small_frames_never_tile_under_the_detected_budget():
    """The auto-detected budget must leave the classic small-frame regime
    untouched: one group, one invocation (the pre-tiling behavior every
    older test asserts on)."""
    ex = OffloadExecutor(SPEC, max_batch=16)
    assert ex.mem_budget.source in ("llc", "hbm", "vmem")
    for h in [ex.submit("fft", im) for im in _imgs(16, (32, 32))]:
        pass
    ex.flush()
    assert ex.telemetry.stats[("fft", "optical-sim")].invocations == 1


def test_warm_primes_tiled_dispatch_shapes():
    """warm() must resolve tile_k exactly as dispatch does, so the first
    tiled flush pays no stack-shape compile (the PR 3 sharded-warm bug,
    tiled edition)."""
    budget = _budget_for_frames(16 * 12, 3)
    ex = OffloadExecutor(SPEC, max_batch=8, mem_budget=budget)
    be = ex._backend("optical-sim")
    seen: list[tuple] = []
    orig = type(be).run

    def spy(self, category, xs, ctx, **kw):
        seen.append((len(xs),) + tuple(xs[0].shape))
        return orig(self, category, xs, ctx, **kw)

    type(be).run = spy
    try:
        (im,) = _imgs(1, (16, 12))
        ex.warm("fft", im, batch=8)
        warmed, seen[:] = set(seen), []
        assert not ex.telemetry.stats       # warm never records
        for h in [ex.submit("fft", x) for x in _imgs(8, (16, 12))]:
            h.get()
        flushed = set(seen)
    finally:
        type(be).run = orig
    # every tiled stack the flush dispatched was already warmed: cap 8 at
    # tile 2 (the even split under a 3-frame budget) -> (2, 16, 12) stacks
    assert flushed <= warmed, (flushed, warmed)
    assert (2, 16, 12) in warmed


def test_block_plan_cache_keys_by_stack_and_budget():
    """The resolved-block cache must never serve a plan shaped for a
    different stack depth or budget (the stale-compile satellite)."""
    ex = OffloadExecutor(SPEC, mem_budget=MemoryBudget.unlimited())
    p16 = ex.ctx.blocks_for(16, 512, 512)
    assert ex.ctx.blocks_for(16, 512, 512) is p16     # cached
    p4 = ex.ctx.blocks_for(4, 512, 512)               # new depth, new plan
    assert len(ex.ctx.block_cache) == 2
    assert p4.key[1:] == p16.key[1:]                  # same cube, no budget
    ex.ctx.mem_budget = MemoryBudget(64 * 64 * 4 * 8, source="manual",
                                     reserve=1.0)
    tight = ex.ctx.blocks_for(16, 512, 512)           # budget change: fresh
    assert len(ex.ctx.block_cache) == 3
    assert tight.bm < 128 and (tight.bk, tight.bn) == (128, 128)


def test_blocks_follow_vmem_under_the_hbm_staging_budget():
    """The HBM staging budget never sizes a Pallas grid step: the blocks
    come from VMEM, are keyed by it, and equal the VMEM budget's own."""
    ex = OffloadExecutor(SPEC, mem_budget=MemoryBudget.detect(
        platform="tpu", device=V5E))
    vmem = MemoryBudget.vmem()
    assert ex.ctx.block_budget == vmem
    assert ex.ctx.blocks_for(1, 1024, 768).key == (1, 128, 128, 128)
    assert ex.ctx.blocks_for(8, 1024, 768).key == (8, 128, 128, 128)
    for batch in (1, 8):
        assert ex.ctx.blocks_for(batch, 1024, 768) == choose_blocks(
            batch, 1024, 768, 768, vmem)
    assert {k[3] for k in ex.ctx.block_cache} == {(vmem.bytes_limit,
                                                    vmem.reserve)}
    # every other staging budget sizes the blocks itself, as before
    for budget in (vmem, _budget_for_frames(64 * 64, 2),
                   MemoryBudget.unlimited()):
        assert OffloadExecutor(SPEC, mem_budget=budget).ctx.block_budget \
            is budget


def test_full_aperture_burst_dispatches_once_under_the_hbm_budget():
    """Eight 1024x768 fft frames flushed under the budget the HBM rule
    gives on a v5e: one invocation, the answers of one-frame tiles, and
    one fidelity report that scores all eight frames."""
    hbm = MemoryBudget.detect(platform="tpu", device=V5E)
    imgs = _imgs(8, (1024, 768))
    ex = OffloadExecutor(SPEC, mem_budget=hbm, fidelity=FidelityChecker())
    hs = [ex.submit("fft", im) for im in imgs]
    ex.flush()
    st = ex.telemetry.stats[("fft", "optical-sim")]
    assert st.calls == 8 and st.invocations == 1
    (report,) = ex.fidelity.reports
    assert report.batch == 8 and report.ok
    assert all(h.fidelity is report for h in hs)
    looped = OffloadExecutor(SPEC, mem_budget=hbm, tile_k=1)
    ls = [looped.submit("fft", im) for im in imgs]
    looped.flush()
    assert looped.telemetry.stats[("fft", "optical-sim")].invocations == 8
    for h, lo in zip(hs, ls):
        np.testing.assert_allclose(h.value, lo.value, rtol=1e-5, atol=1e-5)


# --- telemetry ----------------------------------------------------------------


def test_telemetry_tile_samples_and_bytes_merge_and_reset():
    t = RuntimeTelemetry()
    t.record("fft", "optical-sim", calls=4, samples_in=400, samples_out=400,
             wall_s=0.01, bytes_in=1600, bytes_out=1600)
    t.record("fft", "optical-sim", calls=2, samples_in=200, samples_out=200,
             wall_s=0.01, bytes_in=800, bytes_out=800)
    assert t.tile_sizes_observed("fft") == {2: 1, 4: 1}
    assert t.bytes_per_frame("fft") == (2400 + 2400) // 6
    other = RuntimeTelemetry()
    other.record("fft", "optical-sim", calls=4, samples_in=400,
                 samples_out=400, wall_s=0.01, bytes_in=1600, bytes_out=1600)
    t.merge(other)
    assert t.tile_sizes_observed("fft") == {2: 1, 4: 2}
    assert "tiles:" in t.summary()
    t.reset()
    assert t.tile_sizes_observed("fft") == {} and t.bytes_per_frame("fft") == 0


# --- router: the joint (max_batch, n_devices, tile_k) choice -------------------


def test_choose_sharding_picks_budget_tile_and_respects_operator_pin():
    budget = _budget_for_frames(16 * 16, 2)
    ex = OffloadExecutor(SPEC, default_backend="host", max_batch=16,
                         n_devices=4, mem_budget=budget)
    router = PlanRouter(ex, offload_backend="sharded")
    for im in _imgs(8, (16, 16)):
        router.run("fft", im)
    k, n, t = router.choose_sharding()["fft"]
    # the budget's per-device pick (2) on each of the 4 devices a sharded
    # tile spreads over, not the batch
    assert k == 16 and n == 4 and t == 8
    router.replan()
    assert ex.category_tile_ks()["fft"] == t
    # an operator pin below the budget's choice is a bound the router keeps
    ex.set_tile_k("fft", 1)
    k2, n2, t2 = router.choose_sharding()["fft"]
    assert t2 == 1
    router.replan()
    assert ex.category_tile_ks()["fft"] == 1


def test_choose_sharding_tile_rides_the_deadline_batch():
    """When the deadline halves the batch, the tile follows it down
    (tile <= batch always)."""
    ex = OffloadExecutor(SPEC, default_backend="host", max_batch=16,
                         mem_budget=MemoryBudget.unlimited())
    router = PlanRouter(ex)
    for im in _imgs(8, (16, 16)):
        router.run("fft", im)
    loose_k, _, loose_t = router.choose_sharding()["fft"]
    assert loose_t == loose_k == 16  # unlimited budget: tile = batch
    tight_k, _, tight_t = router.choose_sharding(deadline_s=1e-9)["fft"]
    assert tight_k == 1 and tight_t == 1
