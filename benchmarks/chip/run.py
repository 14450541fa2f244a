#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json`` (its configuration, traffic
mix, limits and metric readers, each a file found by name), sets up the
system under test, measures for ``--seconds``, compares what the timed
path produced with the plain reference, and prints one JSON object as
the last line of standard output.  With ``--trace 0`` its metrics are the
cell's end-to-end ones; with ``--trace 1`` the window runs under the
profiler and the metrics are the per-layer ones.  Each number compared
for ``correct`` is printed beside its limit, on the last lines of
standard error and under ``checks`` in the result.

Off the TPU, with fewer chips than the cell asks for, or on a device kind
the peaks table does not know, it exits 1 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is measured from process start

import argparse   # noqa: E402
import gc         # noqa: E402
import os         # noqa: E402
import pathlib    # noqa: E402
import shutil     # noqa: E402
import sys        # noqa: E402
import tempfile   # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "src"))

from benchmarks.chip import common  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy the traced run's .xplane.pb into DIR")
    return ap.parse_args(argv)


class CompileCounter:
    """Counts programs lowered (compiled, or loaded from the persistent
    cache) while ``active``."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self) -> None:
        import jax
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw) -> None:
        if self.active and name == self.EVENT:
            self.count += 1


def per_layer(cell: dict, ctx: dict, base: pathlib.Path = common.HERE) -> dict:
    """Each per-layer metric of the cell, read by the reader file named
    after it; a reader that finds nothing to read returns None and the
    metric is left out."""
    out = {}
    for m in cell["per_layer"]:
        reader = common.load_module(base / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = common.metric(value, m["unit"])
    return out


def run(args, *, require=common.require_devices, peaks_for=common.peaks_for,
        edit_cell=None, session_hook=None) -> int:
    """One run.  ``require`` finds the devices and ``peaks_for`` their
    peaks; ``edit_cell`` may change the cell before set-up and
    ``session_hook`` sees the session once set-up is done.  (Tests
    replace the first two to run without a chip, shrink the cell through
    the third and break the timed path through the fourth.)"""
    manifest = common.load_manifest()
    cell = common.cell(manifest, args.workload)
    if edit_cell is not None:
        edit_cell(cell)
    chips = cell["workload"]["chips"]
    limits = common.load_json(f"limits/{args.workload}.json", common.HERE)
    import jax
    common.enable_compile_cache()
    devices = require(chips)
    peaks = peaks_for(devices[0].device_kind)
    driver = common.load_module(
        common.HERE / "drivers" / f"{cell['config']['driver']}.py")
    counter = CompileCounter()
    session = driver.Session(cell, args.seed, devices, trace=bool(args.trace))
    if session_hook is not None:
        session_hook(session)
    setup_s = time.perf_counter() - T_START

    # a traced run measures the whole window, so that its sample for the
    # comparison holds as many requests and frames as an untraced one
    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    counter.active = True
    with jax.profiler.TraceAnnotation("bench.window"):
        session.measure(args.seconds)
    counter.active = False
    if args.trace:
        jax.profiler.stop_trace()
    device = common.device_info(devices)

    if args.trace:
        from benchmarks.chip import xplane
        path = xplane.find_trace(trace_dir)
        t_red = time.perf_counter()
        red = xplane.reduce_trace(path)
        print(f"trace of {os.path.getsize(path)} bytes reduced in "
              f"{time.perf_counter() - t_red:.3f} s", flush=True)
        if getattr(args, "keep_trace", None):
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(path, args.keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = session.layer_context()
        ctx.update(trace=red, peaks=peaks, config=cell["config"],
                   mix=cell["mix"])
        metrics = per_layer(cell, ctx)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        breakdown = xplane.breakdown(red)
    else:
        metrics = session.end_to_end()
        metrics["setup_s"] = common.metric(setup_s, "s")
        breakdown = None
    for line in session.report_lines():
        print(line, flush=True)
    print(f"programs compiled or loaded inside the window: {counter.count}",
          flush=True)

    session.release()
    gc.collect()
    t_ref = time.perf_counter()
    checks = session.checks(limits)
    failed = session.failed()
    print(f"comparison with the reference took "
          f"{time.perf_counter() - t_ref:.3f} s", flush=True)
    correct = failed == 0 and all(c["value"] <= c["limit"] for c in checks)
    for c in checks:
        print(f"check {c['name']} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(f"check failed {failed} limit 0", file=sys.stderr, flush=True)
    print(common.result_line(correct=correct, attempted=session.attempted,
                             failed=failed, metrics=metrics, device=device,
                             checks=checks, breakdown=breakdown), flush=True)
    return 0


def main(argv=None) -> int:
    args = parse(argv)
    try:
        return run(args)
    except common.BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(main())
