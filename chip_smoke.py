#!/usr/bin/env python3
"""Smoke run of the system's main path on a TPU, through its entry points.

One process drives every phase on the chip; nothing is interpreted and no
fallback is taken:

  offload  ``OffloadExecutor(PROTOTYPE_4F, default_backend="optical-sim",
           fidelity=FidelityChecker())`` with the detected memory budget
           runs flushes of full-aperture 1024x768 ``fft`` frames through the
           batched Pallas DFT kernels, and one ``conv`` group with a 4f
           kernel.  Every frame is compared with a float64 numpy reference
           (DAC quantize, ``|fft2(., norm="ortho")|^2``, ADC quantize) within
           the converters' ENOB bound.
  serve    stablelm-1.6b at its published widths behind ``ServingEngine``
           (the ``launch/serve.py --full`` path), weights drawn from the
           seed, 4 slots and a 512-token cache, with ``fft`` aux frames
           riding the same executor beside decode.  One decode step's
           logits are checked against the model's own prefill over
           prompt+token, teacher-forced, in float32.

With ``--chips 4`` only the sharded offload runs:
``OffloadExecutor(n_devices=4, default_backend="sharded", residency=True)``
on the same frames, against the one-device result from the same process;
it fails unless the shards landed on four distinct devices.

Measurements go to earlier lines of standard output; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Off the chip, outside the repository, or on any failed check the script
exits non-zero and prints no such line.

Usage:
  python chip_smoke.py [--chips 4] [--seed N]
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent

ARCH = "stablelm-1.6b"
AUX_FRAMES = 8   # fft frames submitted beside decode, half at each of two steps


@dataclasses.dataclass(frozen=True)
class Sizes:
    """How big each phase runs; :data:`FULL` is what the chip runs."""

    frame: tuple[int, int] | None = None   # None: the spec's full aperture
    depth: int = 8                         # frames per flush
    flushes: int = 3
    full_model: bool = True
    slots: int = 4
    max_len: int = 512
    requests: int = 8
    max_new: int = 4


FULL = Sizes()


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def report(phase: str, **values) -> None:
    print(f"[{phase}] " + json.dumps(values, sort_keys=True), flush=True)


# --- references on the host, float64 -----------------------------------------


def _load_reference():
    """The benchmark's float64 4f reference (DAC quantize, unitary 2-D DFT,
    square-law detector, auto-ranged ADC), loaded from its file."""
    path = ROOT / "benchmarks" / "chip" / "references" / "optical4f.py"
    spec = importlib.util.spec_from_file_location("optical4f_reference",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


fft_reference = _load_reference().fft_reference


def conv_reference(frame, kernel):
    """Exact circular 2-D convolution."""
    a = np.asarray(frame, np.float64)
    k = np.asarray(kernel, np.float64)
    return np.real(np.fft.ifft2(np.fft.fft2(a) * np.fft.fft2(k)))


def rel_err(got, ref) -> float:
    got = np.asarray(got, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def adc_code_gap(got, ref, bits: int) -> tuple[int, float]:
    """Worst ADC code difference against the reference, and the relative
    gap of the auto-ranged full scale (the frame's largest value)."""
    levels = (1 << bits) - 1
    got = np.asarray(got, np.float64)
    codes = np.round(got / got.max() * levels)
    ref_codes = np.round(ref / ref.max() * levels)
    return (int(np.abs(codes - ref_codes).max()),
            float(abs(got.max() / ref.max() - 1.0)))


# --- shared pieces -------------------------------------------------------------


def require_chip(chips: int):
    """The devices, after refusing any platform but the TPU and any
    interpreted kernel."""
    from repro.kernels import common

    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"JAX found no TPU: platform {devices[0].platform!r}")
    check(len(devices) >= chips,
          f"{chips} chips asked for, JAX sees {len(devices)}")
    check(common.INTERPRET is False, "Pallas kernels would run interpreted")
    return devices


def make_frames(spec, sizes: Sizes, n: int, seed: int):
    """``n`` uniform frames in [0, 1] from ``seed``: the host copy (for the
    references) and per-frame device arrays from one bulk transfer."""
    h, w = sizes.frame or spec.slm_pixels
    host = np.random.default_rng(seed).random((n, h, w), dtype=np.float32)
    stack = jax.device_put(host)
    return host, [stack[i] for i in range(n)]


def blur_kernel(shape):
    """A normalized 5x5 Gaussian blur centred on the origin, wrapped
    circularly into a full-aperture 4f kernel."""
    h, w = shape
    r = np.arange(-2, 3)
    g = np.exp(-(r[:, None] ** 2 + r[None, :] ** 2) / 2.0)
    k = np.zeros((h, w), np.float32)
    k[np.ix_(r % h, r % w)] = g / g.sum()
    return jnp.asarray(k)


def assert_pallas_dispatch(ex, frame, tile: int) -> None:
    """The optical backend's fft dispatch, lowered for the stack it runs,
    must hold the Pallas kernels (a ``tpu_custom_call``), not the XLA
    route."""
    be = ex._backend("optical-sim")
    # a context with caches of its own: nothing traced here may land in
    # the executor's factor or block caches
    ctx = dataclasses.replace(ex.ctx, factor_cache={}, mask_cache={},
                              block_cache={})
    stack = jax.ShapeDtypeStruct((tile,) + tuple(frame.shape), frame.dtype)
    text = jax.jit(lambda s: be._fft_batched(s, ctx)).lower(stack).as_text()
    check("tpu_custom_call" in text,
          "the fft dispatch lowered without a Pallas kernel")


def check_served(handles, backend: str) -> None:
    for h in handles:
        check(h.backend == backend,
              f"a result was served by {h.backend!r}, not {backend!r}")


def check_no_faults(ex) -> None:
    faults = {c: dict(k) for c, k in ex.telemetry.fault_counts.items() if k}
    check(ex.telemetry.faults_total() == 0,
          f"fault, drift or fallback counters are non-zero: {faults}")


def fft_errors(handles, host, spec) -> dict:
    """Worst ``rel_err`` and ADC-code gaps of fft results against the
    float64 reference."""
    worst = {"rel_err": 0.0, "code_gap": 0, "full_scale_gap": 0.0}
    for h, frame in zip(handles, host):
        ref = fft_reference(frame, (spec.dac.bits, spec.adc.bits))
        got = h.value
        worst["rel_err"] = max(worst["rel_err"], rel_err(got, ref))
        gap, scale_gap = adc_code_gap(got, ref, spec.adc.bits)
        worst["code_gap"] = max(worst["code_gap"], gap)
        worst["full_scale_gap"] = max(worst["full_scale_gap"], scale_gap)
    return worst


def check_fft_errors(worst: dict, spec) -> None:
    bound = enob_bound(spec)
    check(worst["rel_err"] <= bound,
          f"fft rel_err {worst['rel_err']} over the ENOB bound {bound}")
    lsb = 1.0 / ((1 << spec.adc.bits) - 1)
    check(worst["code_gap"] <= 1,
          f"an ADC code is {worst['code_gap']} codes off the reference")
    check(worst["full_scale_gap"] <= lsb,
          f"ADC full scale off the reference by {worst['full_scale_gap']}")


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def enob_bound(spec) -> float:
    from repro.runtime import enob_error_bound
    return enob_error_bound(min(spec.dac.effective_bits,
                                spec.adc.effective_bits))


# --- phases --------------------------------------------------------------------


def offload_phase(sizes: Sizes, seed: int, device):
    """Full-aperture fft flushes and one conv group through the optical
    backend.  Returns the executor and the fft frames (host copies and
    device arrays), for the serving phase to share."""
    from repro.core.accelerator import PROTOTYPE_4F
    from repro.runtime import FidelityChecker, OffloadExecutor

    spec = PROTOTYPE_4F
    bound = enob_bound(spec)
    n = sizes.depth * sizes.flushes
    host, frames = make_frames(spec, sizes, n + sizes.depth, seed)
    conv_host, conv_frames = host[n:], frames[n:]
    kernel = blur_kernel(host.shape[1:])
    ex = OffloadExecutor(spec, default_backend="optical-sim",
                         fidelity=FidelityChecker())
    tile = ex.resolve_tile_k("fft", frames[0], sizes.depth)
    assert_pallas_dispatch(ex, frames[0], tile)

    t0 = time.perf_counter()
    for backend in ("optical-sim", "host"):   # host: the fidelity shadow
        ex.warm("fft", frames[0], backend=backend, batch=sizes.depth)
        ex.warm("conv", conv_frames[0], kernel=kernel, backend=backend,
                batch=sizes.depth)
    compile_s = time.perf_counter() - t0

    handles, walls = [], []
    for f in range(sizes.flushes):
        t0 = time.perf_counter()
        hs = [ex.submit("fft", x)
              for x in frames[f * sizes.depth:(f + 1) * sizes.depth]]
        ex.flush()
        jax.block_until_ready([h.value for h in hs])
        walls.append(time.perf_counter() - t0)
        handles.extend(hs)
    t0 = time.perf_counter()
    conv = [ex.submit("conv", x, kernel=kernel) for x in conv_frames]
    ex.flush()
    jax.block_until_ready([h.value for h in conv])
    conv_wall = time.perf_counter() - t0

    fft_worst = fft_errors(handles, host[:n], spec)
    conv_err = max(rel_err(h.value, conv_reference(x, kernel))
                   for h, x in zip(conv, conv_host))
    stats = ex.telemetry.stats[("fft", "optical-sim")]
    fid = ex.fidelity
    report("offload", frame=list(host.shape[1:]), depth=sizes.depth,
           flushes=sizes.flushes, compile_s=compile_s,
           flush_wall_s=walls, conv_flush_wall_s=conv_wall,
           fft_dispatches=stats.invocations,
           fft_tile_sizes=ex.telemetry.tile_sizes_observed("fft"),
           fft_rel_err=fft_worst["rel_err"],
           fft_adc_code_gap=fft_worst["code_gap"],
           fft_full_scale_gap=fft_worst["full_scale_gap"],
           conv_rel_err=conv_err, enob_bound=bound,
           shadow_checks=len(fid.reports),
           shadow_worst_rel_err=fid.worst().rel_err,
           mem_budget_bytes=ex.mem_budget.bytes_limit,
           mem_budget_source=ex.mem_budget.source,
           peak_bytes_in_use=peak_bytes(device))

    check_served(handles + conv, "optical-sim")
    check_no_faults(ex)
    check(bool(fid.reports) and fid.all_ok,
          f"fidelity shadow: {fid.summary()}")
    check_fft_errors(fft_worst, spec)
    check(conv_err <= bound, f"conv rel_err {conv_err} over {bound}")
    return ex, host[:n], frames[:n]


def serve_phase(sizes: Sizes, seed: int, device, ex, host, frames) -> None:
    """stablelm-1.6b behind ServingEngine with fft aux work on ``ex``, and
    the teacher-forced decode-against-prefill check."""
    from repro.launch.serve import build_engine, make_requests
    from repro.models import LM

    t0 = time.perf_counter()
    engine = build_engine(ARCH, full=sizes.full_model, slots=sizes.slots,
                          max_len=sizes.max_len, seed=seed, offload=ex)
    jax.block_until_ready(engine.params)
    init_s = time.perf_counter() - t0
    cfg = engine.cfg
    for req in make_requests(cfg, sizes.requests, max_new=sizes.max_new,
                             seed=seed + 1):
        engine.submit(req)
    aux_frames = frames[:AUX_FRAMES]
    half = len(aux_frames) // 2
    aux, done, steps = [], [], 0
    t0 = time.perf_counter()
    while not engine.idle():
        check(steps < 10 * sizes.requests * (sizes.max_new + 1),
              "serving did not finish")
        if steps in (0, 2):   # aux frames arriving beside decode
            batch = aux_frames[:half] if steps == 0 else aux_frames[half:]
            aux.extend(engine.submit_aux("fft", x) for x in batch)
        done.extend(engine.step())
        steps += 1
    serve_s = time.perf_counter() - t0
    tokens = sum(len(r.out_tokens) for r in done)

    # teacher-forced: decode one token after prefilling the prompt, against
    # prefill over prompt+token, float32 activations at full precision
    req = min(done, key=lambda r: r.rid)
    tok = req.out_tokens[0]
    model = LM(dataclasses.replace(cfg, dtype="float32"))
    max_len = len(req.prompt) + 8
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        prefill = jax.jit(lambda p, t: model.prefill(
            p, {"tokens": t}, max_len=max_len))
        cache, _ = prefill(engine.params, jnp.asarray([req.prompt]))
        decoded, _ = jax.jit(model.decode_step)(engine.params, cache,
                                                jnp.asarray([[tok]]))
        _, full = prefill(engine.params, jnp.asarray([req.prompt + [tok]]))
    decoded = np.asarray(decoded, np.float64)
    full = np.asarray(full, np.float64)
    check_s = time.perf_counter() - t0
    scale = float(np.abs(full).max())
    logit_gap = float(np.abs(decoded - full).max())
    tol = 1e-3 * max(1.0, scale)

    worst = fft_errors(aux, host[:len(aux)], ex.spec)
    report("serve", arch=cfg.name, d_model=cfg.d_model,
           n_layers=cfg.n_layers, vocab=cfg.vocab_size,
           param_dtype=cfg.param_dtype, slots=sizes.slots,
           max_len=sizes.max_len, requests=len(done), tokens=tokens,
           steps=steps, init_s=init_s, serve_wall_s=serve_s,
           aux_frames=len(aux), aux_rel_err=worst["rel_err"],
           decode_vs_prefill_max_abs=logit_gap, logit_scale=scale,
           tolerance=tol, check_s=check_s,
           peak_bytes_in_use=peak_bytes(device))

    check(len(done) == sizes.requests, "not every request finished")
    check(all(len(r.out_tokens) == sizes.max_new for r in done),
          "a request stopped short of its token budget")
    check(all(0 <= t < cfg.vocab_size for r in done for t in r.out_tokens),
          "a served token is outside the vocabulary")
    check(np.isfinite(decoded).all() and np.isfinite(full).all(),
          "non-finite logits")
    check(logit_gap <= tol,
          f"decode logits off prefill by {logit_gap} (tolerance {tol})")
    check_served(aux, "optical-sim")
    check_no_faults(ex)
    check_fft_errors(worst, ex.spec)


def sharded_phase(sizes: Sizes, seed: int, devices) -> None:
    """The four-chip sharded offload against one device, same frames."""
    from repro.core.accelerator import PROTOTYPE_4F
    from repro.runtime import OffloadExecutor

    spec = PROTOTYPE_4F
    n = sizes.depth * sizes.flushes
    host, frames = make_frames(spec, sizes, n, seed)
    one = OffloadExecutor(spec, default_backend="optical-sim")
    four = OffloadExecutor(spec, n_devices=len(devices),
                           default_backend="sharded", residency=True)
    t0 = time.perf_counter()
    one.warm("fft", frames[0], batch=sizes.depth)
    four.warm("fft", frames[0], batch=sizes.depth)
    compile_s = time.perf_counter() - t0

    def run(ex):
        handles, walls = [], []
        for f in range(sizes.flushes):
            t0 = time.perf_counter()
            hs = [ex.submit("fft", x)
                  for x in frames[f * sizes.depth:(f + 1) * sizes.depth]]
            ex.flush()
            jax.block_until_ready([h.value for h in hs])
            walls.append(time.perf_counter() - t0)
            handles.extend(hs)
        return handles, walls

    ref_handles, one_walls = run(one)
    handles, walls = run(four)
    landed = {d for h in handles for d in h.value.devices()}
    diff = max(float(np.abs(np.asarray(a.value) - np.asarray(b.value)).max())
               for a, b in zip(handles, ref_handles))
    worst = fft_errors(handles, host, spec)
    stats = four.telemetry.stats[("fft", "sharded")]
    report("sharded", frame=list(host.shape[1:]), depth=sizes.depth,
           flushes=sizes.flushes, n_devices=len(devices),
           compile_s=compile_s, flush_wall_s=walls,
           one_device_flush_wall_s=one_walls,
           devices_landed=sorted(d.id for d in landed),
           devices_observed=four.telemetry.devices_observed("fft"),
           dispatches=stats.invocations,
           tile_sizes=four.telemetry.tile_sizes_observed("fft"),
           residency={labels["event"]: c.value for labels, c in
                      four.telemetry.metrics.select("residency",
                                                    category="fft")},
           max_abs_diff_vs_one_device=diff, fft_rel_err=worst["rel_err"],
           enob_bound=enob_bound(spec),
           peak_bytes_in_use=[peak_bytes(d) for d in devices])

    check(len(landed) == len(devices) == 4,
          f"shards landed on {len(landed)} distinct devices, not 4")
    check_served(handles, "sharded")
    check_served(ref_handles, "optical-sim")
    check_no_faults(four)
    check_no_faults(one)
    check(diff == 0.0, f"sharded results differ from one device by {diff}")
    check_fft_errors(worst, spec)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded offload across 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repository source under {ROOT}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    try:
        devices = require_chip(args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    if args.chips == 4:
        sharded_phase(FULL, args.seed, devices[:4])
    else:
        ex, host, frames = offload_phase(FULL, args.seed, devices[0])
        serve_phase(FULL, args.seed, devices[0], ex, host, frames)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
