"""Compiles for a described TPU v5e: no chip attached, nothing runs.

The chip's compiler refuses what interpret mode accepts (block shapes off
the (8, 128) tiling, more fast memory than a kernel may use, a program
larger than the chip), so the kernels of the main path are compiled here at
the sizes the chip runs, plus the stablelm-1.6b decode step at published
widths.  The topology is described inside a fixture, never at import: only
the worker that runs this file loads the TPU library.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.accelerator import PROTOTYPE_4F
from repro.kernels import adc_dac, local_attention, optical_dft
from repro.runtime.backends import BackendContext
from repro.runtime.tiling import MemoryBudget, choose_blocks

HBM_BYTES = 16 * 10**9   # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # compiles for an absent chip cannot be read back from the
        # persistent cache: keep them out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Lower Pallas kernels for the chip, not for the interpreter."""
    for mod in (adc_dac, local_attention, optical_dft):
        monkeypatch.setattr(mod, "INTERPRET", False)


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(fn, *args, **static):
    compiled = jax.jit(lambda *a: fn(*a, **static)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


class _V5E:
    """A device that reports one v5e chip's HBM, as ``memory_stats`` does."""

    @staticmethod
    def memory_stats():
        return {"bytes_limit": HBM_BYTES}


def _detected_block_budget():
    """The budget ``blocks_for`` sizes the grid step against under the
    staging budget a v5e's executor detects."""
    staging = MemoryBudget.detect("tpu", device=_V5E)
    return BackendContext(spec=PROTOTYPE_4F, mem_budget=staging).block_budget


BUDGETS = {
    "detected": _detected_block_budget,
    # under one 128-cube grid step: the lane blocks must still stay 128
    "manual-200KB": lambda: MemoryBudget(200_000, source="manual"),
}


@pytest.mark.parametrize("batch,budget", [(8, "detected"), (1, "detected"),
                                          (8, "manual-200KB")])
def test_batched_dft_stages_compile_at_full_aperture(batch, budget, one_chip,
                                                     mosaic):
    h, w = 1024, 768                      # PROTOTYPE_4F's aperture
    plan = choose_blocks(batch, h, w, w, BUDGETS[budget]())
    assert plan.bk % 128 == 0 and plan.bn % 128 == 0 and plan.bm % 8 == 0
    if budget == "detected":
        assert plan.key == (batch, 128, 128, 128)
    blocks = dict(bb=plan.bb, bm=plan.bm, bk=plan.bk, bn=plan.bn)
    _compile_kernel(optical_dft.dft_stage1_batched,
                    _sds((h, h), one_chip), _sds((h, h), one_chip),
                    _sds((batch, h, w), one_chip), dac_bits=6, **blocks)
    _compile_kernel(optical_dft.dft_stage2_batched,
                    _sds((batch, h, w), one_chip),
                    _sds((batch, h, w), one_chip),
                    _sds((w, w), one_chip), _sds((w, w), one_chip), **blocks)


def test_converter_boundary_compiles_at_full_aperture(one_chip, mosaic):
    x = _sds((1024, 768), one_chip)
    _compile_kernel(adc_dac.converter_boundary, x, x, dac_bits=6, adc_bits=8)


@pytest.mark.parametrize("window,kv_groups", [(128, 1), (0, 4)],
                         ids=["window", "gqa"])
def test_local_flash_attention_compiles(window, kv_groups, one_chip, mosaic):
    heads, length, d = 8, 512, 128
    q = _sds((heads, length, d), one_chip, jnp.bfloat16)
    kv = _sds((heads // kv_groups, length, d), one_chip, jnp.bfloat16)
    _compile_kernel(local_attention.local_flash_attention, q, kv, kv,
                    window=window, kv_groups=kv_groups)


def test_stablelm_decode_step_fits_one_chip(one_chip):
    from repro.configs import get_config
    from repro.models import LM, param_shape_structs

    cfg = get_config("stablelm-1.6b")
    model = LM(cfg)

    def place(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(place, param_shape_structs(cfg))
    cache = jax.tree_util.tree_map(
        place, jax.eval_shape(lambda: model.init_cache(4, 512)))
    tokens = _sds((4, 1), one_chip, jnp.int32)
    compiled = jax.jit(model.decode_step).lower(params, cache,
                                                tokens).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 6 * 10**9   # the f32 weights
    assert total < HBM_BYTES, total
