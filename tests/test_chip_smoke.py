"""chip_smoke.py's phases and checks, at a tiny size on the CPU.

The chip runs them at full size; here they run small, with the Pallas
dispatch check stubbed (off the TPU the optical fft takes the XLA route).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke as cs
from repro.core.accelerator import PROTOTYPE_4F

TINY = cs.Sizes(frame=(64, 48), depth=4, flushes=2, full_model=False,
                slots=2, max_len=64, requests=3, max_new=3)


def test_refuses_a_host_without_a_tpu():
    with pytest.raises(cs.SmokeFailure, match="no TPU"):
        cs.require_chip(1)


def test_offload_and_serve_phases_pass_at_tiny_size(monkeypatch, capsys):
    import jax
    monkeypatch.setattr(cs, "assert_pallas_dispatch", lambda *a: None)
    device = jax.devices()[0]
    ex, host, frames = cs.offload_phase(TINY, 0, device)
    cs.serve_phase(TINY, 0, device, ex, host, frames)
    lines = capsys.readouterr().out.splitlines()
    offload = json.loads(lines[0].removeprefix("[offload] "))
    serve = json.loads(lines[1].removeprefix("[serve] "))
    assert offload["fft_dispatches"] == TINY.flushes
    assert serve["tokens"] == TINY.requests * TINY.max_new
    assert serve["aux_frames"] == cs.AUX_FRAMES


def test_fft_reference_checks_catch_a_wrong_frame():
    frame = np.random.default_rng(0).random((32, 24), dtype=np.float32)
    ref = cs.fft_reference(frame, (PROTOTYPE_4F.dac.bits,
                                   PROTOTYPE_4F.adc.bits))

    class Handle:
        value = ref

    cs.check_fft_errors(cs.fft_errors([Handle], [frame], PROTOTYPE_4F),
                        PROTOTYPE_4F)
    Handle.value = ref * 1.01          # full scale off by over one ADC LSB
    with pytest.raises(cs.SmokeFailure, match="full scale"):
        cs.check_fft_errors(cs.fft_errors([Handle], [frame], PROTOTYPE_4F),
                            PROTOTYPE_4F)


_SHARDED = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
sys.path.insert(0, sys.argv[1])
import chip_smoke as cs
cs.sharded_phase(cs.Sizes(frame=(64, 48), depth=8, flushes=2), 0,
                 jax.devices()[:4])
"""


def test_sharded_phase_lands_on_four_devices():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(root, "src"))
    out = subprocess.run([sys.executable, "-c", _SHARDED, root], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.splitlines()[-1].removeprefix("[sharded] "))
    assert rec["devices_landed"] == [0, 1, 2, 3]
    assert rec["max_abs_diff_vs_one_device"] == 0.0
