#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and the control's.

    python3 benchmarks/chip/control.py --workload p4f-fft-backlog \\
        --seeds 11 12 13 --seconds 10

For each seed, in one process: the cell's set-up and a window of
``--seconds`` at the cell's own load, then the numbers ``correct``
compares, read twice over the same sampled answers: once of what the
timed path produced (the lower reading's sound run), once of the
control, the plain reference put in the program's place at the
precision below the configuration's (the configuration file's
``control``).  One JSON line per seed.  The benchmark's own runs never
run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "src"))

from benchmarks.chip import common  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = common.cell(common.load_manifest(), args.workload)
    common.enable_compile_cache()
    devices = common.require_devices(cell["workload"]["chips"])
    driver = common.load_module(
        common.HERE / "drivers" / f"{cell['config']['driver']}.py")
    for seed in args.seeds:
        session = driver.Session(cell, seed, devices)
        session.measure(args.seconds)
        session.release()
        gc.collect()
        row = {"workload": args.workload, "seed": seed,
               "program": session.readings(),
               "control": session.readings(control=True),
               "failed": session.failed(), "attempted": session.attempted}
        print(json.dumps(row), flush=True)
        del session
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
