"""Shared helpers of the chip benchmark's tests: they run on the CPU, at
sizes a test run holds, with the harness's look for a chip replaced."""

import argparse
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def shrink(cell: dict) -> None:
    """Cut a cell to a size the CPU runs in a second or two: small
    frames, a two-layer model of 512 wide, four slots."""
    m = cell["mix"]
    if m["kind"] == "closed_chat":
        cell["config"]["model"].update(
            n_layers=2, d_model=512, n_heads=8, n_kv_heads=8, d_ff=1024,
            vocab_size=500, vocab_pad_multiple=64)
        cell["config"]["engine"].update(batch_slots=4, max_len=64)
        m.update(clients=4, pool=64, block=8, aux_frame_shape=[32, 24])
        m["prompt"].update(median=16, min=8, max=32, multiple=8)
        # answers long enough, and a sample wide enough, that a decode
        # step which keeps its state shows on every seed
        m["output"].update(median=10, min=2, max=24)
        m["sample"].update(tokens=200, aux_every=1)
    else:
        m["frame_shape"] = [64, 48]
        m["sample"].update(bursts=2)


@pytest.fixture
def shrunk():
    """The function that cuts a cell to test size."""
    return shrink


@pytest.fixture
def cpu_run(capsys):
    """Runs the harness once on the CPU, its chip check replaced and the
    cell shrunk; returns the parsed result line."""
    import json

    import jax
    from benchmarks.chip import run

    def go(workload: str, *, seed: int = 2**33 + 5, seconds: float = 0.5,
           session_hook=None) -> dict:
        args = argparse.Namespace(workload=workload, seed=seed,
                                  seconds=seconds, trace=0, keep_trace=None)
        rc = run.run(args, require=lambda chips: jax.devices()[:1] * chips,
                     peaks_for=lambda kind: PEAKS, edit_cell=shrink,
                     session_hook=session_hook)
        assert rc == 0
        out = capsys.readouterr().out.strip().splitlines()
        return json.loads(out[-1])
    return go


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    """The harness points jax's persistent compilation cache at the
    checkout; a test worker keeps jax's defaults."""
    from benchmarks.chip import common
    monkeypatch.setattr(common, "enable_compile_cache", lambda: None)
