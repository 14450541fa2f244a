"""Work counts against values worked out by hand."""

import math

import pytest

from benchmarks.chip import workcount

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
STABLELM = dict(d_model=2048, d_ff=5632, n_heads=32, n_kv_heads=32,
                n_layers=24, padded_vocab=100352, vocab_size=100352,
                tie_embeddings=False, dtype="bfloat16",
                param_dtype="float32")


def test_fft_frame_at_full_aperture():
    w = workcount.fft_frame(1024, 768)
    assert w["bytes"] == 6_291_456                     # 8 * 786,432
    n = 1024 * 768
    assert w["flops"] == pytest.approx(2.5 * n * math.log2(n))
    # 6,291,456 B / 819 GB/s = 7.68 us: bound by memory, not by the MXU
    assert workcount.least_seconds(w, V5E) == pytest.approx(7.6819e-6,
                                                            rel=1e-4)
    assert w["flops"] / V5E["bf16_flops_per_s"] < w["bytes"] / 819e9


@pytest.mark.parametrize("key,value", [
    ("blocks", 24 * (4 * 2048 * 2048 + 3 * 2048 * 5632 + 2 * 2048)),
    ("head", 100352 * 2048),
    ("embed", 100352 * 2048),
])
def test_stablelm_parameters(key, value):
    assert workcount.lm_params(STABLELM)[key] == value


def test_stablelm_block_parameters_near_1233m():
    assert workcount.lm_params(STABLELM)["blocks"] == 1_233_223_680


def test_stablelm_token_flops():
    # 2 per block parameter + 4 * 24 * 2048 * context + logits
    got = workcount.lm_token_flops(STABLELM, 100, True)
    want = 2 * 1_233_223_680 + 4 * 24 * 2048 * 100 + 2 * 2048 * 100352
    assert got == want


def test_stablelm_decode_step_bytes():
    assert workcount.lm_kv_bytes_per_position(STABLELM) == 196_608
    # block + head + final-norm parameters in float32, plus live K/V
    params = 1_233_223_680 + 205_520_896 + 2048
    assert workcount.lm_decode_step_bytes(STABLELM, 10) == \
        params * 4 + 10 * 196_608
    assert params * 4 == pytest.approx(5.755e9, rel=1e-3)
