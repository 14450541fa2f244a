"""The reduction from a profiler trace to busy and idle share, device
time by program and op, and idle gaps named by the host span that covers
them: on a trace recorded on a v5e chip (0.3 s of ``p4f-fft-backlog``,
committed beside this file without its ``/host:metadata`` plane, which
the reduction does not read) and on hand-made intervals."""

import pathlib

import pytest

from benchmarks.chip import xplane

TRACE = pathlib.Path(__file__).parent / "data" / "fft_window.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return xplane.reduce_trace(str(TRACE))


def test_one_device_busy_inside_the_window(red):
    assert [d.name for d in red.devices] == ["/device:TPU:0"]
    assert 0.2 < red.window_s < 1.0
    assert 0.0 < red.busy_s < red.window_s


def test_program_time_by_name(red):
    dev = red.devices[0]
    s1 = dev.modules["jit_dft_stage1_batched"]
    s2 = dev.modules["jit_dft_stage2_batched"]
    assert s1 > 0 and s2 > 0
    assert red.module_s(("jit_dft_stage1_batched",
                         "jit_dft_stage2_batched")) == pytest.approx(s1 + s2)
    # programs never overlap on one core: their sum is the busy time
    assert sum(dev.modules.values()) == pytest.approx(red.busy_s, rel=1e-6)
    assert dev.ops["dft_stage1_batched"] > 0


def test_gaps_named_by_host_spans(red):
    idle = red.window_s - red.busy_s
    assert sum(red.gaps.values()) == pytest.approx(idle, rel=1e-6)
    assert set(red.gaps) <= {"bench.submit", "bench.flush", "bench.wait",
                             "bench.make_frames", "idle"}
    assert red.gaps.get("bench.flush", 0.0) > 0.5 * idle
    assert red.longest_gaps == sorted(red.longest_gaps, key=lambda g: -g[1])


def test_breakdown_shape(red):
    b = xplane.breakdown(red)
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in b["device_ops"])


@pytest.mark.parametrize("event,name", [
    ("jit_dft_stage1_batched(839088857921671944)", "jit_dft_stage1_batched"),
    ("jit__reduce_max(87)", "jit__reduce_max"),
    ("jit_decode_step", "jit_decode_step"),
])
def test_module_names(event, name):
    assert xplane.module_name(event) == name


@pytest.mark.parametrize("event,name", [
    ("%dft_stage1_batched.1 = (f32[1,1024,768]) custom-call(%a)",
     "dft_stage1_batched"),
    ("%copy-start.5 = (f32[2048]) copy-start(%p)", "copy-start"),
    ("%fusion.9 = f32[8] fusion(%x), kind=kOutput", "fusion"),
    ("%while = (s32[]) while(%t)", "while"),
])
def test_op_names(event, name):
    assert xplane.op_name(event) == name


def test_union_merges_overlaps():
    assert xplane._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3),
                                                                (5, 8)]


def test_split_by_host_innermost_wins():
    host = [(0, 100, "bench.flush"), (20, 40, "bench.submit")]
    parts = xplane._split_by_host(10, 120, host)
    assert parts == {"bench.flush": 70, "bench.submit": 20, "idle": 20}
    assert xplane._split_by_host(200, 210, host) == {"idle": 10}
