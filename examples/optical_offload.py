"""Run a CNN workload through the conversion-aware offload runtime.

The seed version of this example *priced* offload (profile -> plan ->
print); PR 1 *executed* the plan.  This version executes it the way the
batching story prices it:

  1. profile   — serve the conv workload through the runtime's host backend;
                 telemetry measures per-category time and boundary traffic;
  2. plan      — ``PlanRouter.replan()`` prices the measured profiles on the
                 prototype 4f engine (spoiler: the conversion boundary loses,
                 the paper's conclusion) and on a batched column-parallel
                 variant.  Replanning is *adaptive*: the router picks each
                 category's coalescing ceiling from observed traffic, and a
                 latency ``deadline_s`` caps how deep batching may go;
  3. execute   — apply the plan: conv traffic routes through the simulated
                 optical engine; same-shape calls coalesce into ONE batched
                 invocation each (stacked operands, vmapped 4f physics), and
                 ``flush_async`` double-buffers the boundary — invocation
                 k+1 stages while invocation k's analog+ADC compute is in
                 flight, with per-result ``wait()``/``done()`` readiness;
  4. verify    — every offloaded batch is shadowed by the host reference and
                 scored against the converters' ENOB budget, so the speedup
                 story is always paired with its accuracy cost.
  5. scale out — the same flush group scatters across four replicated
                 simulated apertures (``n_devices=4``, the ``sharded``
                 backend): every device pays its own DAC/ADC boundary
                 crossing, telemetry aggregates per-device samples, and the
                 modeled invocation wall drops to max-over-devices + sync.
  6. trickle   — serve a sparse Poisson arrival stream through the
                 admission-controlled ``OffloadScheduler``: partially
                 filled groups are *held open across flushes* (released
                 when full, due, or futile to keep holding per the measured
                 arrival rate), so occupancy climbs where drain-on-flush
                 would cross the boundary one frame at a time — and the
                 queueing delay that buys it is priced (``StepCost.hold_s``).
  7. tile      — large frames under a memory budget: at 512x512 the
                 monolithic stacked flush group overflows the LLC
                 (VMEM on TPU), so ``replan`` picks a sub-group ``tile_k``
                 from the detected byte budget and the released group
                 streams as tile-sized sub-invocations through the same
                 two-deep pipeline — amortization per tile, cache-resident
                 working set.
  8. observe   — attach the opt-in span tracer and re-run the conv
                 workload: one span tree per batched invocation
                 (submit -> release -> stage -> compute -> shadow), a
                 one-screen trace digest and wall percentiles per
                 category.
  9. survive   — wrap the optical backend in a seeded ``ChaosBackend``
                 (10% of dispatches fault: transient errors, stragglers,
                 ENOB drift, device loss) and serve the same frames: the
                 retry ladder re-runs transient faults, exhaustion
                 degrades gracefully to the host backend, drifted batches
                 are corrected from the fidelity shadow and the category
                 quarantined — every frame still retires, in order, within
                 the converters' error budget, with the whole fault story
                 visible in fault counters and recovery percentiles.
  10. reuse    — turn on the operand residency cache
                 (``OffloadExecutor(residency=True)``) and re-serve a conv
                 layer stack that re-uses its frames and kernel: the first
                 flush stages and quantizes everything (and registers it
                 resident), every later flush skips the write-side DAC
                 crossing entirely — priced read-side-only
                 (``cost.dac_s == 0``) and bit-equal to the re-staged
                 path, with the hit/miss ledger in telemetry.

Executors are context managers: each ``with`` block below guarantees no
pending, held, or in-flight group outlives the demo that created it.

Run:  PYTHONPATH=src python examples/optical_offload.py
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import PROTOTYPE_4F
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
    enob_error_bound,
    register_chaos,
    summarize,
)


def conv_stack(router: PlanRouter, imgs, kernels) -> list[jax.Array]:
    """3-layer circular-conv + relu stack over a batch of images.

    Convolutions go through the router (host or optical per the current
    plan); the nonlinearities stay on the host — the paper's §3 point that
    inter-layer nonlinearity forces a conversion round trip per layer.
    Dispatch is async: the flush returns with results in flight and each
    layer blocks only when the relu actually needs the values.
    """
    outs = list(imgs)
    for k in kernels:
        handles = [router.submit("conv", x, kernel=k) for x in outs]
        router.executor.flush_async()        # batched + double-buffered
        outs = [jax.nn.relu(h.wait().value) for h in handles]
    return outs


def main() -> None:
    key = jax.random.PRNGKey(0)
    # 512x512 frames: the regime where the host FFT costs real milliseconds
    # and 8 inputs still pack into one 2048x2048 SLM frame (one frame-sync).
    imgs = [jax.random.uniform(jax.random.fold_in(key, i), (512, 512))
            for i in range(8)]
    # 5x5 taps around an identity center: keeps each layer's output norm
    # comparable to its input (a near-cancelling kernel would amplify the
    # boundary's relative error — the fidelity checker flags such cases).
    kernels = [jnp.zeros((512, 512)).at[:5, :5].set(
        0.04 * jax.random.normal(jax.random.fold_in(key, 100 + i), (5, 5)))
        .at[0, 0].add(0.5) for i in range(3)]

    fidelity = FidelityChecker()
    # the executor is a context manager: nothing queued, held, or in
    # flight survives the block (results materialize, telemetry balances).
    # The budget is pinned to unlimited here: steps 1-4 demonstrate the
    # full-occupancy amortization story (one monolithic invocation per
    # group); step 7 below turns the detected budget on and shows what
    # memory-budgeted tiling changes at this frame size.
    with OffloadExecutor(BATCHED_4F, fidelity=fidelity, max_batch=16,
                         pipeline_depth=2,
                         mem_budget=MemoryBudget.unlimited()) as executor:
        run_plan_demo(executor, imgs, kernels)
    run_sharded_demo(imgs, kernels)
    run_trickle_demo()
    run_tiled_demo(imgs)
    run_traced_demo(imgs, kernels)
    run_chaos_demo()
    run_residency_demo()


def run_plan_demo(executor: OffloadExecutor, imgs, kernels) -> None:
    router = PlanRouter(executor)            # starts all-host: profiling mode

    # --- 1. profile: measured traffic, no hand-written numbers --------------
    # warm primes the single-item AND batched jit shapes, so the first real
    # flush below pays zero compilation
    executor.warm("conv", imgs[0], kernel=kernels[0], backend="host",
                  batch=len(imgs))
    executor.telemetry.start()
    host_out = conv_stack(router, imgs, kernels)
    executor.telemetry.stop()
    print(executor.telemetry.summary())

    # --- 2. plan: price the observed workload, adapt the batching ------------
    proto_plan = router.replan(spec=PROTOTYPE_4F, apply=False, max_batch=1)
    print("\n-- measured plan on the paper's prototype (Fig. 8 links) --")
    print(proto_plan.summary())
    print("paper's conclusion, reproduced from *measured* traffic: "
          f"offload chosen = {any(d.offload for d in proto_plan.decisions)}")

    # adaptive batching: the ceiling follows the workload, and a latency
    # deadline trades amortization depth against invocation wall time
    print("\n-- adaptive per-category coalescing ceilings --")
    print(f"unconstrained: {router.choose_max_batch()}")
    n_in, _ = executor.telemetry.samples_per_call("conv")
    tight = dataclasses.replace(
        BATCHED_4F, phase_shift_captures=CONV_CAPTURES).batched_step_cost(
            n_in, batch=4, pipeline_depth=2).total_s
    print(f"deadline {tight * 1e3:.1f} ms: "
          f"{router.choose_max_batch(deadline_s=tight)}")

    plan = router.replan()                   # batched-4f spec; applies routes
    print("\n-- measured plan on the batched column-parallel variant --")
    print(plan.summary())
    print(f"routes now: {router.routes}  "
          f"max_batch now: {dict(executor.category_max_batches())}")

    # --- 3. execute the plan: conv through the optical engine ----------------
    opt_out = conv_stack(router, imgs, kernels)
    rel = max(float(jnp.linalg.norm(h - o) / jnp.maximum(
        jnp.linalg.norm(h), 1e-9)) for h, o in zip(host_out, opt_out))
    conv_stats = executor.telemetry.stats.get(("conv", "optical-sim"))
    if conv_stats is not None:
        per_call = conv_stats.modeled.scaled(1.0 / max(conv_stats.calls, 1))
        single = dataclasses.replace(
            BATCHED_4F, phase_shift_captures=CONV_CAPTURES).step_cost(512 * 512)
        print(f"\nbatched boundary cost/call: conv+interface "
              f"{per_call.conversion_s + per_call.interface_s:.4g}s "
              f"(unbatched would pay {single.conversion_s + single.interface_s:.4g}s)"
              f" — {conv_stats.calls} calls in {conv_stats.invocations} "
              f"batched invocations")

    # --- 4. verify: the accuracy cost of the speedup --------------------------
    print(f"\nend-to-end stack divergence vs host: rel error {rel:.4f}")
    print(executor.fidelity.summary())


def run_sharded_demo(imgs, kernels) -> None:
    # --- 5. scale out: shard the flush group across replicated apertures ------
    # Photonic systems scale by replicating apertures, not growing one.
    # unlimited budget: sharding's claim is ONE invocation scattered whole
    # across the fleet — tiling first would scatter 2-frame tiles over 2
    # devices each and muddle the comparison (step 7 owns that story)
    with OffloadExecutor(BATCHED_4F, max_batch=16, n_devices=4,
                         default_backend="sharded",
                         mem_budget=MemoryBudget.unlimited()) as sharded:
        sharded.warm("conv", imgs[0], kernel=kernels[0], batch=len(imgs))
        handles = [sharded.submit("conv", im, kernel=kernels[0])
                   for im in imgs]
        sharded.flush()
        # runtime-equivalence invariant: sharded == host reference
        ref = [jnp.real(jnp.fft.ifft2(jnp.fft.fft2(im)
                                      * jnp.fft.fft2(kernels[0])))
               for im in imgs]
        rel_sh = max(float(jnp.linalg.norm(h.value - r) / jnp.linalg.norm(r))
                     for h, r in zip(handles, ref))
        sharded_total = sum(h.cost.total_s for h in handles)
        single_total = dataclasses.replace(
            BATCHED_4F, phase_shift_captures=CONV_CAPTURES).batched_step_cost(
                512 * 512, batch=len(imgs), pipeline_depth=2).total_s
        print("\n-- sharded offload: 4 replicated apertures, group sharding --")
        per_dev = sharded.telemetry.device_samples("conv")
        for d, (s_in, s_out) in per_dev.items():
            print(f"  device {d}: {s_in} samples through its DAC, "
                  f"{s_out} back through its ADC")
        print(f"sharded-vs-host rel error {rel_sh:.4f} (equivalence invariant)")
        print(f"modeled invocation wall: sharded {sharded_total:.4g}s "
              f"(max-over-devices + sync) vs single-device {single_total:.4g}s "
              f"-> {single_total / sharded_total:.3f}x")


def run_trickle_demo(rate_hz: float = 200.0, deadline_s: float = 0.05,
                     arrivals: int = 24) -> None:
    # --- 6. trickle traffic: admission-controlled continuous batching ---------
    # A Poisson stream too sparse to fill a batch between flushes.  The
    # pre-scheduler regime drained the queue on every flush: occupancy 1,
    # full handshake + settle per frame.  The scheduler holds partially
    # filled groups open across flushes — released when full (max_batch),
    # due (deadline), or futile (measured arrival rate says the next
    # arrival lands past the deadline) — and the modeled wall prices the
    # queueing delay it spent (StepCost.hold_s).  A ManualClock drives the
    # arrivals, so the occupancy shown is deterministic.
    frames = [jax.random.uniform(jax.random.fold_in(
        jax.random.PRNGKey(42), i), (128, 128)) for i in range(arrivals)]
    print(f"\n-- trickle arrivals ({rate_hz:.0f}/s Poisson, "
          f"{deadline_s * 1e3:.0f} ms hold deadline) --")
    for held in (False, True):
        rng = np.random.RandomState(0)       # same trace for both regimes
        clk = ManualClock()
        with OffloadExecutor(BATCHED_4F, max_batch=8, clock=clk) as ex:
            ex.warm("fft", frames[0])
            sched = OffloadScheduler(ex, deadline_s=deadline_s, clock=clk) \
                if held else None
            for i, frame in enumerate(frames):
                clk.advance(float(rng.exponential(1.0 / rate_hz)))
                if held:
                    sched.submit("fft", frame)   # polls: holds or releases
                else:
                    ex.submit("fft", frame)
                    ex.flush()                   # drain-on-flush baseline
        st = ex.telemetry.stats[("fft", "optical-sim")]
        per_call = st.modeled.scaled(1.0 / st.calls)
        label = "scheduler-held" if held else "drain-on-flush"
        print(f"  {label:>15}: {st.calls} calls in {st.invocations} "
              f"crossings (occupancy {st.calls / st.invocations:.2f}), "
              f"boundary {per_call.conversion_s + per_call.interface_s:.4g}s"
              f"/call, hold {per_call.hold_s:.4g}s/call, "
              f"modeled wall {per_call.total_s:.4g}s/call")


def run_tiled_demo(imgs) -> None:
    # --- 7. large frames: memory-budgeted tiled dispatch ----------------------
    # A 512x512 K=8 flush group's monolithic stack (frames + complex
    # intermediates + results) falls out of the CPU's last-level cache
    # off-TPU — the regime where batching measurably loses to looping.
    # The executor's memory budget (LLC-derived here, VMEM-derived on
    # TPU) makes replan pick a sub-group tile_k: the released group
    # streams as budget-sized sub-invocations through the same two-deep
    # pipeline, each tile's staging overlapped with the previous tile's
    # in-flight compute.
    budget = MemoryBudget.detect()
    print(f"\n-- large frames: memory-budgeted tiled dispatch "
          f"({budget.bytes_limit // (1024 * 1024)} MiB {budget.source} "
          f"budget, reserve {budget.reserve:.0%}) --")
    with OffloadExecutor(BATCHED_4F, max_batch=16,
                         mem_budget=budget) as ex:
        router = PlanRouter(ex)              # all-host profiling mode
        ex.warm("fft", imgs[0], backend="host", batch=len(imgs))
        ex.telemetry.start()
        for h in [router.submit("fft", im) for im in imgs]:
            h.get()
        ex.telemetry.stop()
        router.replan()                      # picks (max_batch, n_devices, tile_k)
        k, _n, t = router.choose_sharding()["fft"]
        print(f"replan chose max_batch={k}, tile_k={t} for 512x512 fft "
              f"(monolithic would stage "
              f"{k * 2 * 512 * 512 * 4 // (1024 * 1024)} MiB + intermediates)")
        n_in, n_out = ex.telemetry.samples_per_call("fft")
        mono = BATCHED_4F.batched_step_cost(n_in, n_out, batch=k,
                                            pipeline_depth=2)
        tiled = BATCHED_4F.batched_step_cost(n_in, n_out, batch=k,
                                             pipeline_depth=2, tile_k=t)
        print(f"modeled invocation wall: tiled {tiled.total_s:.4g}s vs "
              f"monolithic {mono.total_s:.4g}s — the boundary model prices "
              f"each tile's own handshake/settle honestly; tiling wins on "
              f"the MEASURED host wall (cache locality), which is what the "
              f"benchmark's large_frame row asserts")
        # drive one group through the simulated engine to show the
        # dispatch granularity the budget (via replan's set_tile_k)
        # forced — on fresh telemetry, so the printed tile counts are the
        # optical dispatches alone, not the host profiling phase's
        ex.telemetry.reset()
        ex.warm("fft", imgs[0], batch=len(imgs))
        for h in [ex.submit("fft", im, backend="optical-sim")
                  for im in imgs]:
            h.get()
        tiles = ex.telemetry.tile_sizes_observed("fft")
        print(f"dispatched tile sizes (telemetry): {tiles} — measured "
              f"{ex.telemetry.bytes_per_frame('fft') // 1024} KiB/frame "
              f"staged")


def run_traced_demo(imgs, kernels) -> None:
    # --- 8. observe: boundary-attributed tracing -------------------------------
    # The tracer is opt-in (OffloadExecutor(tracer=...)); the default is a
    # no-op with zero hot-path cost.  Each batched invocation becomes one
    # span tree — submit instants on the sched lane, the release that
    # dispatched it, the charged host staging (DAC-side) span, the charged
    # device compute (analog+ADC) span, the fidelity shadow — annotated
    # with the modeled batched_step_cost decomposition.  Under
    # jax.profiler the lexical spans also land in the profiler's trace
    # as repro.<name> host annotations, beside the device's programs.
    tracer = Tracer()
    with OffloadExecutor(BATCHED_4F, max_batch=16, tracer=tracer,
                         mem_budget=MemoryBudget.unlimited()) as ex:
        ex.warm("conv", imgs[0], kernel=kernels[0], batch=len(imgs))
        ex.telemetry.start()
        for h in [ex.submit("conv", im, kernel=kernels[0]) for im in imgs]:
            h.get()
        ex.telemetry.stop()
        print("\n-- traced: one flush group, boundary-attributed --")
        print(summarize(tracer.spans()))
        pct = ex.telemetry.percentiles("conv")
        print("conv wall percentiles: " + "  ".join(
            f"p{int(p)}={v * 1e3:.2f}ms" for p, v in pct.items()))


def run_chaos_demo(calls: int = 32, rate: float = 0.10) -> None:
    # --- 9. survive: fault-injected offload under the retry/quarantine policy --
    # A seeded ChaosBackend perturbs 10% of dispatches (transient errors,
    # latency-spike stragglers, ENOB drift, hard device loss).  The
    # executor's RetryPolicy retries transients with jittered backoff
    # (slept through the ManualClock — no real waiting), degrades to the
    # host backend when the ladder exhausts (quarantining the category so
    # later dispatches reroute instead of re-paying retries), and the
    # fidelity shadow corrects drifted batches on the spot.  The claim:
    # every frame retires, in submit order, within the ENOB error budget.
    frames = [jax.random.uniform(jax.random.fold_in(
        jax.random.PRNGKey(7), i), (64, 64)) for i in range(calls)]
    chaos = register_chaos("optical-sim", name="chaos-demo",
                           rate=rate, seed=2)
    clk = ManualClock()
    with OffloadExecutor(BATCHED_4F, default_backend=chaos, max_batch=4,
                         clock=clk, fidelity=FidelityChecker()) as ex:
        ex.warm("fft", frames[0])
        handles = [ex.submit("fft", f) for f in frames]
    with OffloadExecutor(BATCHED_4F, default_backend="host",
                         max_batch=1) as host:
        refs = [host.submit("fft", f) for f in frames]
    enob = min(BATCHED_4F.dac.effective_bits, BATCHED_4F.adc.effective_bits)
    bound = enob_error_bound(enob, 16.0)
    worst = max(float(jnp.linalg.norm(h.value - r.value)
                      / jnp.maximum(jnp.linalg.norm(r.value), 1e-12))
                for h, r in zip(handles, refs))
    served = {h.backend for h in handles}
    print(f"\n-- chaos: {rate:.0%} injected fault rate over {calls} calls --")
    print(ex.telemetry.summary())
    print(f"served by {sorted(served)}; all retired: "
          f"{all(h.ready for h in handles)}; worst rel error {worst:.2e} "
          f"(ENOB bound {bound:.2e}) -> within budget: {worst <= bound}")
    print(ex.quarantine.summary(ex.now()))


def run_residency_demo(calls: int = 8) -> None:
    # --- 10. reuse: operand residency across repeated flushes -----------------
    # A conv layer stack that re-serves the SAME frames through the SAME
    # kernel (inference over a fixed activation set, an iterative solve,
    # a re-scored beam) pays the write-side DAC crossing once.  With
    # ``residency=True`` the first flush stages + quantizes every operand
    # and registers it resident under the staging budget; the second flush
    # finds everything already on the device, skips the write side
    # entirely, and is priced read-side-only: cost.dac_s == 0 while the
    # results stay bit-equal to a residency-off executor.
    key = jax.random.PRNGKey(11)
    imgs = [jax.random.uniform(jax.random.fold_in(key, i), (128, 128))
            for i in range(calls)]
    kernel = jnp.zeros((128, 128)).at[:3, :3].set(
        0.05 * jax.random.normal(jax.random.fold_in(key, 99), (3, 3))
    ).at[0, 0].add(0.5)

    with OffloadExecutor(BATCHED_4F, max_batch=calls,
                         residency=True) as ex:
        first = [ex.submit("conv", x, kernel=kernel) for x in imgs]
        ex.flush()
        second = [ex.submit("conv", x, kernel=kernel) for x in imgs]
        ex.flush()
        hit_rate = ex.telemetry.residency_hit_rate("conv")
        ledger = ex.residency.summary()
    with OffloadExecutor(BATCHED_4F, max_batch=calls) as plain:
        refs = [plain.submit("conv", x, kernel=kernel) for x in imgs]

    bit_equal = all(bool(jnp.array_equal(s.value, r.value))
                    for s, r in zip(second, refs))
    print(f"\n-- residency: serve {calls} conv frames twice, "
          f"pay the DAC once --")
    print(f"first flush  (cold): dac {first[0].cost.dac_s * 1e6:8.2f}us/call "
          f"total {first[0].cost.total_s * 1e6:8.2f}us/call")
    print(f"second flush (hit):  dac {second[0].cost.dac_s * 1e6:8.2f}us/call "
          f"total {second[0].cost.total_s * 1e6:8.2f}us/call")
    print(f"hit rate {hit_rate:.0%}; bit-equal to residency-off: {bit_equal}")
    print(ledger)


if __name__ == "__main__":
    main()
