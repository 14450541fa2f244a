"""Pieces every cell of the chip benchmark shares: the manifest, loading
files by name, seeds, the device check, the peaks table, exact
statistics and the result line.

Nothing here imports the program under test (``src/repro``); the drivers
do, after the device check has passed.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import pathlib
import sys
from typing import Any

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]                    # the checkout
MANIFEST = ROOT / "BENCHMARK.json"
CACHE_DIR = ROOT / ".jax_cache"           # fixed: the path keys the cache


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, unknown device, bad
    manifest); the benchmark exits non-zero and prints no result line."""


# --- the manifest and the files it names -------------------------------------


def load_manifest(path: pathlib.Path = MANIFEST) -> dict:
    if not path.is_file():
        raise BenchError(f"no manifest at {path}")
    return json.loads(path.read_text())


def find(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in the manifest")


def load_json(rel: str, base: pathlib.Path = ROOT) -> dict:
    path = base / rel
    if not path.is_file():
        raise BenchError(f"missing file {rel}")
    return json.loads(path.read_text())


def load_module(path: pathlib.Path):
    """Import a Python file by path (metric readers, drivers and
    references live in files named after manifest entries)."""
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    mod_name = "benchchip_" + "".join(
        c if c.isalnum() else "_" for c in str(path.relative_to(path.anchor)))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell(manifest: dict, workload: str, root: pathlib.Path = ROOT) -> dict:
    """Everything one cell needs, resolved from the manifest by name: the
    workload entry, its configuration file, its traffic file and the
    metrics it reports with and without the trace."""
    wl = find(manifest["workloads"], workload, "workload")
    cfg_entry = find(manifest["configs"], wl["config"], "config")
    config = load_json(cfg_entry["file"], root)
    mix = load_json(f"benchmarks/chip/traffic/{wl['traffic']}.json", root)

    def applies(m: dict) -> bool:
        return workload in m.get("workloads", [workload])

    e2e = [m for m in manifest["end_to_end"] if applies(m)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"]
                 if applies(m) and m["moves"] in reported]
    return {"workload": wl, "config": config,
            "mix": mix, "end_to_end": e2e, "per_layer": per_layer}


# --- seeds -------------------------------------------------------------------


def seed_words(seed: int) -> tuple[int, int]:
    """A seed of up to 64 bits as two 32-bit words: jax's ``PRNGKey``
    keeps only the low 32 bits of a larger seed."""
    if seed < 0:
        raise BenchError("--seed must be a whole number >= 0")
    return seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF


def jax_key(seed: int, *path: int):
    """A jax PRNG key from the whole seed, folded with ``path``."""
    import jax
    lo, hi = seed_words(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    for p in path:
        key = jax.random.fold_in(key, p)
    return key


def np_rng(seed: int, *path: int):
    import numpy as np
    return np.random.default_rng([*seed_words(seed), *path])


# --- the device ----------------------------------------------------------------


def peaks_for(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error,
    never a default."""
    kinds = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in kinds:
        raise BenchError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(kinds)}")
    return kinds[device_kind]


def require_devices(chips: int, devices=None) -> list:
    """The first ``chips`` TPU devices.  Any other platform, fewer chips
    than the cell asks for, or a device kind without peaks is an error:
    the benchmark never falls back to the CPU."""
    if devices is None:
        import jax
        devices = jax.devices()
    if not devices or devices[0].platform != "tpu":
        plat = devices[0].platform if devices else "none"
        raise BenchError(f"JAX found no TPU (platform {plat!r})")
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX sees "
                         f"{len(devices)}")
    peaks_for(devices[0].device_kind)
    return list(devices[:chips])


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache, always ``.jax_cache`` at the
    root of the checkout, so that two checkouts never share compiled
    programs; the program, which takes ``JAX_COMPILATION_CACHE_DIR`` where
    it is set, is handed the same directory.  Every program is cached,
    however quick its compile, and none is evicted (the directory is the
    checkout's own, whatever size limit the environment sets), so only a
    checkout's first run compiles."""
    import jax
    path = str(CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def device_info(devices) -> dict:
    """Platform, kind and count as JAX reports them, and the peak memory
    of the fullest chip."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": max(peaks)}


# --- exact statistics ------------------------------------------------------------


def percentile(values, q: float) -> float:
    """Exact percentile over every sample (linear between order
    statistics, as numpy's default); never a binned estimate."""
    xs = sorted(values)
    if not xs:
        raise BenchError("no samples for a percentile")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


# --- output ------------------------------------------------------------------------


def metric(value: float, unit: str) -> dict:
    return {"value": float(value), "unit": unit}


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: list[dict],
                breakdown: dict | None = None) -> str:
    """The run's last line of standard output.  ``checks`` (each number
    compared beside its limit) comes last."""
    out: dict[str, Any] = {"correct": bool(correct),
                           "attempted": int(attempted),
                           "failed": int(failed), "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)
