"""The program names the benchmark's roofline tables read
(``benchmarks/chip/metrics/fft_roofline.py`` ``FFT_PROGRAMS``,
``decode_roofline.py`` ``DECODE_PROGRAMS``) are the names the programs
lower to: a rename fails here instead of silencing a metric."""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.kernels import optical_dft
from repro.models import init_params
from repro.serving import ServingEngine

METRICS = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / \
    "chip" / "metrics"


def _table(reader: str, name: str) -> tuple:
    spec = importlib.util.spec_from_file_location(
        f"program_names_{reader}", METRICS / f"{reader}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, name)


def _module_name(lowered) -> str:
    head = lowered.as_text().split("\n", 1)[0]     # "module @jit_x attributes"
    return head.split()[1].lstrip("@")


def test_fft_roofline_programs_are_the_pallas_stages():
    w = jnp.ones((8, 128))
    stage1 = optical_dft.dft_stage1_batched.lower(w, w, jnp.ones((1, 128, 128)))
    t = jnp.ones((1, 8, 128))
    stage2 = optical_dft.dft_stage2_batched.lower(t, t, jnp.ones((128, 128)),
                                                  jnp.ones((128, 128)))
    assert (_module_name(stage1), _module_name(stage2)) == \
        _table("fft_roofline", "FFT_PROGRAMS")


def test_decode_roofline_program_is_the_engine_decode_step():
    cfg = get_smoke_config("stablelm-1.6b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, batch_slots=2, max_len=16)
    lowered = engine._decode.lower(params, engine.cache, engine.last_token)
    assert (_module_name(lowered),) == _table("decode_roofline",
                                              "DECODE_PROGRAMS")
