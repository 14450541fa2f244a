"""The benchmark measures on the chip or not at all: another platform,
too few chips or a device kind without peaks is an error, and the run
then prints no result line."""

import dataclasses

import pytest

from benchmarks.chip import common, run


@dataclasses.dataclass
class Dev:
    platform: str = "tpu"
    device_kind: str = "TPU v5 lite"


def test_non_tpu_platform_fails():
    with pytest.raises(common.BenchError, match="no TPU"):
        common.require_devices(1, [Dev(platform="cpu")])


def test_too_few_chips_fail():
    with pytest.raises(common.BenchError, match="asks for 4"):
        common.require_devices(4, [Dev()])


def test_unknown_device_kind_fails():
    with pytest.raises(common.BenchError, match="no peaks"):
        common.require_devices(1, [Dev(device_kind="TPU v99")])
    with pytest.raises(common.BenchError, match="no peaks"):
        common.peaks_for("TPU v99")


def test_known_device_kind_has_its_peaks():
    p = common.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert common.require_devices(1, [Dev(), Dev()]) == [Dev()]


def test_run_on_the_cpu_exits_nonzero_with_no_result(capsys):
    rc = run.main(["--workload", "p4f-fft-backlog", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 1
    assert "{" not in out.out
    assert "no TPU" in out.err
