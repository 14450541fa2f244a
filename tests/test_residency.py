"""Operand residency: the cache that stops paying the DAC for resident bytes.

The invariant this file extends (PR 5/6/7's equivalence property, one more
axis): ``cached == re-staged == looped`` — a flush served from the
residency cache retires bit-equal to one that re-staged every operand on
digital backends (the hit replays the same jitted computation on the same
staged array), and allclose on the optical sim — across plain, scheduler-
held, tiled, sharded, and chaos-wrapped dispatch.  The cost model must
*agree* with dispatch: a fully resident flush prices read-side-only
(``dac_s == 0``), and turning residency off reproduces the historical
prices bit for bit.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.accelerator import ANDERSON_MVM, PROTOTYPE_4F
from repro.core.conversion import ConverterSpec
from repro.runtime import (
    DELTA_THRESHOLD,
    Fault,
    ManualClock,
    MemoryBudget,
    OffloadExecutor,
    OffloadScheduler,
    ResidencyCache,
    ShardedOpticalBackend,
    operating_point,
    register_chaos,
    residency_key,
)

LANED_4F = dataclasses.replace(
    PROTOTYPE_4F, name="laned-4f", interface_latency_s=1.0e-3,
    dac_lanes=48, adc_lanes=48,
    slm_interface_hz=100e6, camera_interface_hz=100e6,
    device_sync_s=1.0e-5)

HI_FI_ADC = ConverterSpec(name="hifi-adc", kind="adc", bits=12,
                          rate_hz=5.0e8, power_w=0.060, enob=10.5)

SPEC = dataclasses.replace(LANED_4F, adc=HI_FI_ADC)


def _imgs(n, shape=(32, 32), seed=0):
    key = jax.random.PRNGKey(seed)
    return [jax.random.uniform(jax.random.fold_in(key, i), shape)
            for i in range(n)]


def _kernel(shape=(32, 32)):
    h, w = shape
    return (jnp.zeros(shape)
            .at[0, 0].set(0.5).at[1, 2 % w].set(0.25)
            .at[h - 1, 1 % w].set(0.15))


def _flush(ex, category, imgs, **kw):
    hs = [ex.submit(category, im, **kw) for im in imgs]
    ex.flush()
    return [np.asarray(h.value) for h in hs], [h.cost for h in hs]


# --- the equivalence invariant, extended -----------------------------------------

@pytest.mark.parametrize("backend", ["host", "optical-sim"])
def test_cached_equals_restaged_and_looped(backend):
    """A conv layer stack re-using its frames: the cached second flush
    retires bit-equal to the re-staged first flush AND to a residency-off
    executor; looped per-frame matches bit-equal on digital backends,
    allclose on the optical sim (batch-1 vs batch-K lowering)."""
    imgs, kernel = _imgs(6), _kernel()
    plain = OffloadExecutor(SPEC, max_batch=8, default_backend=backend)
    restaged, _ = _flush(plain, "conv", imgs, kernel=kernel)
    looped_ex = OffloadExecutor(SPEC, max_batch=1, default_backend=backend)
    looped, _ = _flush(looped_ex, "conv", imgs, kernel=kernel)

    ex = OffloadExecutor(SPEC, max_batch=8, default_backend=backend,
                         residency=True)
    first, _ = _flush(ex, "conv", imgs, kernel=kernel)
    cached, _ = _flush(ex, "conv", imgs, kernel=kernel)

    for c, f, r in zip(cached, first, restaged):
        np.testing.assert_array_equal(c, f)
        np.testing.assert_array_equal(c, r)
    for c, l in zip(cached, looped):
        if backend == "host":
            np.testing.assert_array_equal(c, l)
        else:
            np.testing.assert_allclose(c, l, rtol=1e-5)


def test_hit_miss_counters_and_hit_rate():
    imgs, kernel = _imgs(4), _kernel()
    ex = OffloadExecutor(SPEC, max_batch=8, residency=True)
    _flush(ex, "conv", imgs, kernel=kernel)     # frame stack + kernel miss
    _flush(ex, "conv", imgs, kernel=kernel)     # both hit
    counts = ex.residency.counts["conv"]
    assert counts["miss"] == 2 and counts["hit"] == 2
    assert ex.residency.hit_rate("conv") == 0.5
    # mirrored into telemetry: the router replans from this ledger
    assert ex.telemetry.residency_hit_rate("conv") == 0.5
    assert ex.telemetry.metrics.total("residency", category="conv",
                                      event="hit") == 2
    # the summaries surface the ledger
    assert "residency" in ex.residency.summary()
    assert "residency category=conv event=hit: 2" in ex.telemetry.summary()


def test_repeated_conv_stack_hits_and_drifting_frames_delta():
    """A conv layer stack re-using its frames and kernel, primed then
    flushed three more times, against the same stack with a quarter of
    its frames drifting ~1% a flush and against fresh frames every flush:
    repeats pay no DAC at a hit rate above one half, drifted flushes pay a
    partial write strictly between a hit and a re-stage at a mean flip
    fraction in (0, DELTA_THRESHOLD), and both retire bit-equal to a
    residency-off executor."""
    imgs, kernel, reps = _imgs(8), _kernel(), 3
    plain = OffloadExecutor(SPEC, max_batch=8)

    def flushes(ex, groups):
        for grp in groups:
            vals, costs = _flush(ex, "conv", grp, kernel=kernel)
        return vals, costs[0]

    hot = OffloadExecutor(SPEC, max_batch=8, residency=True)
    hit_vals, hit = flushes(hot, [imgs] * (1 + reps))
    cold = OffloadExecutor(SPEC, max_batch=8, residency=True)
    _, restage = flushes(cold, [_imgs(8, seed=100 + r) for r in range(reps)])
    drifted = [[im + 0.01 * jax.random.uniform(
        jax.random.fold_in(jax.random.PRNGKey(500 + r), i), im.shape)
        if i % 4 == 0 else im for i, im in enumerate(imgs)]
        for r in range(reps)]
    part = OffloadExecutor(SPEC, max_batch=8, residency=True)
    delta_vals, delta = flushes(part, [imgs] + drifted)

    assert hit.dac_s == 0.0
    assert 0.0 < delta.dac_s < restage.dac_s
    assert hot.telemetry.residency_hit_rate("conv") > 0.5
    assert 0.0 < part.telemetry.mean_flip_fraction("conv") < DELTA_THRESHOLD
    for got, want in ((hit_vals, flushes(plain, [imgs])[0]),
                      (delta_vals, flushes(plain, [drifted[-1]])[0])):
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)


def test_hit_priced_read_side_only():
    """The acceptance criterion on the cost model: a fully resident flush
    pays no write-side DAC traffic but the full read side — the ADC still
    converts every output sample whether or not the input was resident."""
    imgs, kernel = _imgs(4), _kernel()
    ex = OffloadExecutor(SPEC, max_batch=8, residency=True)
    _, first = _flush(ex, "conv", imgs, kernel=kernel)
    _, second = _flush(ex, "conv", imgs, kernel=kernel)
    assert first[0].dac_s > 0.0
    assert second[0].dac_s == 0.0
    assert second[0].adc_s == first[0].adc_s
    assert second[0].analog_s == first[0].analog_s


def test_cost_model_agrees_with_dispatch():
    """The dispatched hit cost IS ``batched_step_cost(resident_frames=K)``
    — the model and the runtime price the same thing."""
    imgs = _imgs(4)
    n = imgs[0].size
    ex = OffloadExecutor(SPEC, max_batch=8, residency=True)
    _flush(ex, "fft", imgs)
    _, costs = _flush(ex, "fft", imgs)
    want = ex.spec.batched_step_cost(n, n, batch=len(imgs),
                                     pipeline_depth=ex.pipeline_depth,
                                     resident_frames=len(imgs))
    got = costs[0]   # per-call share of the invocation's modeled cost
    assert got.dac_s == want.dac_s == 0.0
    np.testing.assert_allclose(got.total_s, want.total_s / len(imgs),
                               rtol=1e-12)


def test_batched_step_cost_residency_params():
    """Defaults reproduce the historical prices bit for bit; resident
    frames are monotone savings; negatives are rejected."""
    for spec in (LANED_4F, ANDERSON_MVM):
        base = spec.batched_step_cost(4096, batch=8)
        again = spec.batched_step_cost(4096, batch=8, resident_frames=0,
                                       weight_samples=0, resident_weights=0)
        assert base == again
        prev = base.total_s
        for r in (2, 4, 8):
            c = spec.batched_step_cost(4096, batch=8, resident_frames=r)
            assert c.total_s <= prev
            prev = c.total_s
        full = spec.batched_step_cost(4096, batch=8, resident_frames=8)
        assert full.dac_s == 0.0
        assert full.adc_s == base.adc_s
        # a resident weight panel cancels exactly the weight write charge
        w = spec.batched_step_cost(4096, batch=8, weight_samples=512)
        wr = spec.batched_step_cost(4096, batch=8, weight_samples=512,
                                    resident_weights=512)
        assert w.dac_s > base.dac_s
        assert wr == base
        with pytest.raises(ValueError):
            spec.batched_step_cost(4096, batch=8, resident_frames=-1)
        with pytest.raises(ValueError):
            spec.batched_step_cost(4096, batch=8, weight_samples=-1)


def test_matmul_weight_panel_residency():
    """MVM serving: with residency on, the first flush prices the weight
    panel write honestly (``matmul_cost(weight_write=True)``); once the
    panel is resident the weight-stationary price returns — and a fully
    resident activation flush reads back for free on the write side."""
    acts = [jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(3), i),
                              (16, 24)) for i in range(4)]
    w = jax.random.normal(jax.random.PRNGKey(9), (24, 8))
    plain = OffloadExecutor(ANDERSON_MVM, max_batch=8)
    base, base_costs = _flush(plain, "matmul", acts, weights=w)
    ex = OffloadExecutor(ANDERSON_MVM, max_batch=8, residency=True)
    first, c1 = _flush(ex, "matmul", acts, weights=w)
    second, c2 = _flush(ex, "matmul", acts, weights=w)
    for g, r in zip(first + second, base + base):
        np.testing.assert_array_equal(g, r)
    assert c1[0].dac_s > base_costs[0].dac_s      # honest panel write
    assert c2[0].dac_s == 0.0                      # panel + acts resident
    assert ANDERSON_MVM.matmul_cost(16, 24, 8, weight_write=True).dac_s \
        > ANDERSON_MVM.matmul_cost(16, 24, 8).dac_s


# --- scheduler-held / tiled / sharded / chaos-wrapped dispatch -------------------

def test_scheduler_held_cached_equivalence():
    imgs, kernel = _imgs(5), _kernel()
    plain = OffloadExecutor(SPEC, max_batch=4)
    ref, _ = _flush(plain, "conv", imgs, kernel=kernel)
    clk = ManualClock()
    ex = OffloadExecutor(SPEC, max_batch=4, clock=clk, residency=True)
    with OffloadScheduler(ex, deadline_s=0.1, clock=clk) as sched:
        for rep in range(2):
            hs = []
            for im in imgs:
                clk.advance(0.01)
                sched.poll()
                hs.append(sched.submit("conv", im, kernel=kernel))
            clk.advance(0.5)
            sched.poll()
            ex.drain()
            for h, r in zip(hs, ref):
                np.testing.assert_array_equal(np.asarray(h.value), r)
    assert ex.residency.counts["conv"]["hit"] > 0


def test_tiled_cached_equivalence():
    """Budget-forced tiled dispatch: each tile's stack is its own resident
    entry, and the cached re-flush still streams tile by tile, bit-equal."""
    imgs = _imgs(6)
    budget = MemoryBudget(bytes_limit=3 * imgs[0].nbytes * 4, reserve=1.0)
    plain = OffloadExecutor(SPEC, max_batch=8, mem_budget=budget)
    ref, _ = _flush(plain, "fft", imgs)
    ex = OffloadExecutor(SPEC, max_batch=8, mem_budget=budget,
                         residency=True)
    for _rep in range(2):
        got, _ = _flush(ex, "fft", imgs)
        for g, r in zip(got, ref):
            np.testing.assert_array_equal(g, r)
    assert ex.residency.counts["fft"]["hit"] > 0
    assert len(ex.residency) > 1          # one entry per tile, not one blob


def test_sharded_partial_residency_rescatter():
    """Half the frames change between flushes: the re-scatter ships only
    the missing half (hits AND misses both advance) and every frame still
    retires equal to a fresh re-staged baseline."""
    imgs, kernel = _imgs(6), _kernel()
    fresh = _imgs(3, seed=99) + imgs[3:]
    ex = OffloadExecutor(SPEC, max_batch=8, n_devices=3, residency=True)
    _flush(ex, "conv", imgs, kernel=kernel, backend="sharded-host")
    before = dict(ex.residency.counts["conv"])
    got, _ = _flush(ex, "conv", fresh, kernel=kernel, backend="sharded-host")
    after = ex.residency.counts["conv"]
    assert after["hit"] > before.get("hit", 0)     # unchanged shards served
    assert after["miss"] > before.get("miss", 0)   # changed shards re-shipped
    plain = OffloadExecutor(SPEC, max_batch=8, n_devices=3)
    ref, _ = _flush(plain, "conv", fresh, kernel=kernel,
                    backend="sharded-host")
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(g, r)


def test_chaos_wrapped_cached_equivalence():
    """A transient fault mid-stream neither corrupts nor bypasses the
    cache: the retried dispatch retires equal, and the re-flush hits."""
    imgs = _imgs(4)
    name = register_chaos("optical-sim", name="chaos-residency",
                          script={0: Fault("error")})
    clk = ManualClock()
    ex = OffloadExecutor(SPEC, default_backend=name, max_batch=4,
                         clock=clk, residency=True)
    first, _ = _flush(ex, "fft", imgs)
    second, _ = _flush(ex, "fft", imgs)
    plain = OffloadExecutor(SPEC, max_batch=4, clock=ManualClock())
    ref, _ = _flush(plain, "fft", imgs)
    for g, r in zip(first + second, ref + ref):
        np.testing.assert_array_equal(g, r)
    assert ex.telemetry.fault_counts["fft"]["error"] == 1
    assert ex.residency.counts["fft"]["hit"] > 0


# --- eviction, collisions, invalidation (the edge-case satellite) ----------------

def test_eviction_under_budget_pressure_mid_pipeline():
    """A capacity smaller than the working set evicts LRU entries while
    the pipeline keeps flushing — results stay correct, the ledger counts
    the evictions, and the cache never exceeds its capacity."""
    cache = ResidencyCache(capacity_bytes=2 * 32 * 32 * 4 * 4)
    ex = OffloadExecutor(SPEC, max_batch=4, residency=cache)
    plain = OffloadExecutor(SPEC, max_batch=4)
    for seed in range(4):                 # distinct groups: cache churns
        imgs = _imgs(4, seed=seed)
        got, _ = _flush(ex, "fft", imgs)
        ref, _ = _flush(plain, "fft", imgs)
        for g, r in zip(got, ref):
            np.testing.assert_array_equal(g, r)
        assert cache.resident_bytes() <= cache.capacity_bytes
    assert cache.counts["fft"]["eviction"] > 0
    # the evicted groups re-stage (miss), the survivors still hit
    imgs = _imgs(4, seed=3)
    _flush(ex, "fft", imgs)
    assert cache.counts["fft"]["hit"] > 0


def test_oversized_operand_is_not_cached():
    cache = ResidencyCache(capacity_bytes=64)
    evicted = cache.store("host", ("k",), object(), 1024,
                          category="fft", kind="frame")
    assert evicted == [] and len(cache) == 0


def test_digest_collision_distinct_shapes_never_collide():
    """Equal bytes, different shapes: the shape is part of the digest, so
    a (4, 16) zeros block can never serve a (8, 8) zeros lookup."""
    ex = OffloadExecutor(SPEC, residency=True)
    a, b = jnp.zeros((4, 16)), jnp.zeros((8, 8))
    ka = residency_key(ex.ctx, [a], "frame")
    kb = residency_key(ex.ctx, [b], "frame")
    assert ka != kb
    cache = ex.residency
    cache.store("host", ka, a, int(a.nbytes), category="fft", kind="frame")
    assert cache.lookup("host", kb, category="fft") is None
    assert cache.lookup("host", ka, category="fft") is not None


def test_operating_point_change_invalidates_resident_operands():
    """Retuning a converter (ADC ENOB here) moves the quantization grid:
    operands staged under the old operating point must stop matching."""
    assert operating_point(LANED_4F) != operating_point(SPEC)
    cache = ResidencyCache()
    imgs, kernel = _imgs(4), _kernel()
    ex1 = OffloadExecutor(LANED_4F, max_batch=8, residency=cache)
    _flush(ex1, "conv", imgs, kernel=kernel)
    hits_before = cache.counts["conv"]["hit"]
    # same cache, same operands, retuned ADC: every lookup misses
    ex2 = OffloadExecutor(SPEC, max_batch=8, residency=cache)
    got, _ = _flush(ex2, "conv", imgs, kernel=kernel)
    assert cache.counts["conv"]["hit"] == hits_before
    assert cache.counts["conv"]["miss"] >= 4
    plain = OffloadExecutor(SPEC, max_batch=8)
    ref, _ = _flush(plain, "conv", imgs, kernel=kernel)
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(g, r)


def test_invalidate_device_drops_only_that_device():
    cache = ResidencyCache()
    cache.store(("device", 0), ("a",), object(), 10, category="conv",
                kind="shard")
    cache.store(("device", 1), ("b",), object(), 20, category="conv",
                kind="shard")
    dropped = cache.invalidate_device(("device", 0))
    assert dropped == 10
    assert cache.resident_keys(("device", 0)) == []
    assert cache.resident_keys(("device", 1)) == [("b",)]
    assert cache.counts["conv"]["invalidation"] == 1


def test_quarantine_drops_device_resident_set():
    """The fault story meets the cache: quarantining a device drops its
    resident set — its bytes are not trustworthy after the fault, and
    re-admission must re-stage."""
    ex = OffloadExecutor(SPEC, max_batch=4, n_devices=2, residency=True,
                         clock=ManualClock())
    cache = ex.residency
    cache.store(("device", 1), ("stale",), object(), 10, category="conv",
                kind="shard")
    cache.store("host", ("fine",), object(), 10, category="conv",
                kind="frame")
    be = ShardedOpticalBackend(inner="host")
    be._quarantine_device(ex.ctx, 1, reason="error")
    assert cache.resident_keys(("device", 1)) == []
    assert cache.resident_keys("host") == [("fine",)]
    assert cache.counts["conv"]["invalidation"] == 1
    assert ex.quarantine.is_quarantined(("device", 1), ex.now())


# --- executor integration --------------------------------------------------------

def test_residency_opt_in_and_off_switch():
    ex_on = OffloadExecutor(SPEC, residency=True)
    assert isinstance(ex_on.residency, ResidencyCache)
    assert ex_on.ctx.residency is ex_on.residency
    for off in (None, False):
        ex_off = OffloadExecutor(SPEC, residency=off)
        assert ex_off.residency is None and ex_off.ctx.residency is None
        imgs = _imgs(2)
        got, _ = _flush(ex_off, "fft", imgs)
        ref, _ = _flush(OffloadExecutor(SPEC), "fft", imgs)
        for g, r in zip(got, ref):
            np.testing.assert_array_equal(g, r)


def test_warm_does_not_pollute_residency():
    """Priming runs are not workload: warm() must neither populate the
    cache nor advance the hit/miss ledger the router replans from."""
    ex = OffloadExecutor(SPEC, max_batch=4, residency=True)
    ex.warm("fft", _imgs(1)[0])
    assert len(ex.residency) == 0
    assert not ex.residency.counts
    assert ex.telemetry.residency_hit_rate() is None


def test_submit_reuse_token_skips_rehashing():
    """submit(reuse=) promises content stability: after the first digest
    the token seeds the digest memo, so repeat submissions of the same
    live array hit without re-hashing; a token re-used with a different
    shape is re-digested and re-bound rather than trusted."""
    imgs = _imgs(3)
    ex = OffloadExecutor(SPEC, max_batch=4, residency=True)
    for _rep in range(2):
        for i, im in enumerate(imgs):
            ex.submit("fft", im, reuse=f"frame{i}")
        ex.flush()
    assert ex.residency.counts["fft"]["hit"] >= 1
    assert id(imgs[0]) in ex.ctx._digest_memo
    # token re-bound on a shape change, not trusted
    tall = jnp.zeros((64, 16))
    k1 = ex.residency.note_token("frame0", tall, ex.ctx)
    assert k1 == ex.ctx.content_key(tall)


def test_residency_shares_the_staging_budget_with_tiles():
    """Resident bytes shrink the budget tiles spend from: as the cache
    fills, ``effective_mem_budget`` drops and the resolved tile depth
    can only shrink."""
    img = _imgs(1, (64, 64))[0]
    budget = MemoryBudget(bytes_limit=64 * img.nbytes, reserve=1.0)
    ex = OffloadExecutor(SPEC, max_batch=16, mem_budget=budget,
                         residency=True)
    t_empty = ex.resolve_tile_k("fft", img, 16)
    assert t_empty > 1
    assert ex.effective_mem_budget().bytes_limit == budget.bytes_limit
    # capacity is half the budget's spendable bytes: pin 24 frames (fits)
    ex.residency.store("host", ("pinned",), object(), 24 * img.nbytes,
                       category="fft", kind="frame")
    assert ex.effective_mem_budget().bytes_limit < budget.bytes_limit
    t_full = ex.resolve_tile_k("fft", img, 16)
    assert t_full < t_empty
    # the floor: a cache bigger than the budget leaves 1 byte, never 0
    # (0 reads as unlimited) — tile_k degrades to 1, not to monolithic
    assert budget.minus(10**9).bytes_limit == 1
    assert MemoryBudget.unlimited().minus(10**9).is_unlimited
    assert budget.minus(0) is budget


def test_residency_capacity_derives_from_budget():
    budget = MemoryBudget(bytes_limit=1 << 20, reserve=1.0)
    cache = ResidencyCache(budget)
    assert cache.capacity_bytes == int(budget.spendable_bytes * 0.5)
    assert ResidencyCache().capacity_bytes == 64 * 1024 * 1024
    assert ResidencyCache(capacity_bytes=123).capacity_bytes == 123


def test_router_replan_weighs_residency():
    """The deadline-halving loop prices the measured hit rate in: the same
    observed traffic sustains a deeper batch when the cache is absorbing
    the write side."""
    from repro.runtime import PlanRouter

    def _router(hits):
        ex = OffloadExecutor(SPEC, max_batch=16)
        ex.telemetry.record("fft", "optical-sim", calls=16,
                            samples_in=16 * 4096, samples_out=16 * 4096,
                            wall_s=0.01)
        for _ in range(hits):
            ex.telemetry.note_residency("fft", "hit")
        return PlanRouter(ex), ex

    cost = lambda k, res: SPEC.batched_step_cost(
        4096, 4096, batch=k, pipeline_depth=2, n_devices=1, tile_k=k,
        resident_frames=res)
    # a deadline only the resident price meets at full depth
    deadline = (cost(16, 16).total_s + cost(16, 0).total_s) / 2
    hot, _ = _router(hits=8)      # hit rate 1.0
    cold, _ = _router(hits=0)     # no residency traffic: rate treated as 0
    k_hot = hot.choose_sharding(deadline)["fft"][0]
    k_cold = cold.choose_sharding(deadline)["fft"][0]
    assert k_hot == 16
    assert k_cold < 16
