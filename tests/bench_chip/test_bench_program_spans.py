"""The per-layer metrics that read the program's own spans: device idle
under a program span (its spans put on the trace's clock by an anchor),
the engine's admission spans, and the engine's counters against the
serve driver's own bookkeeping."""

import dataclasses
import pathlib

import pytest

from benchmarks.chip import common, program_idle, xplane

DATA = pathlib.Path(__file__).parent / "data"


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float
    annotated: bool = True


@dataclasses.dataclass
class Device:
    busy: list


@dataclasses.dataclass
class Trace:
    """Stands in for ``xplane.Reduction``: busy intervals and bench spans
    in nanoseconds on the trace's clock."""
    window_s: float
    devices: list
    host_spans: list


def reader(name):
    return common.load_module(common.HERE / "metrics" / f"{name}.py")


def spans_at(origin_s, rows, annotated=True):
    """Tracer spans from ``(name, t0_ns, t1_ns)`` rows, on a clock whose
    zero lies ``origin_s`` seconds before the trace's."""
    return [Span(n, origin_s + a * 1e-9, origin_s + b * 1e-9, annotated)
            for n, a, b in rows]


def offload_ctx(origin_s=1000.0, drop=(), annotated=True, **moved):
    """Two bursts in a 100 us window: the program's flush holds a stage
    and a shadow; the device idles 2 + 3 us under stage, 8 + 5 us under
    shadow, 5 us under the second flush alone and 10 us under nothing."""
    rows = {"flush1": ("flush", 10_000, 49_000),
            "stage1": ("stage", 10_200, 15_000),
            "shadow1": ("fidelity-shadow", 30_000, 45_000),
            "flush2": ("flush", 60_000, 88_000),
            "stage2": ("stage", 60_200, 65_000),
            "shadow2": ("fidelity-shadow", 75_000, 85_000)}
    rows.update(moved)
    spans = spans_at(origin_s, [r for k, r in rows.items() if k not in drop],
                     annotated)
    trace = Trace(100_000e-9,
                  [Device([(0, 12_000), (14_000, 32_000), (40_000, 61_000),
                           (64_000, 80_000)])],
                  [(10_000, 50_000, "bench.flush"),
                   (60_000, 90_000, "bench.flush")])
    return {"spans": spans, "trace": trace, "window_s": 100e-6}


def serve_ctx(origin_s=5.0, annotated=True):
    """Two steps: the device idles 3 + 1 us under ``engine.sample``."""
    spans = spans_at(origin_s, [
        ("engine.step", 1_000, 39_000), ("engine.admit", 1_200, 15_000),
        ("engine.decode", 20_000, 21_000), ("engine.sample", 21_000, 30_000),
        ("engine.step", 41_000, 79_000), ("engine.decode", 60_000, 61_000),
        ("engine.sample", 61_000, 70_000)], annotated)
    spans += spans_at(origin_s, [("queued", 0, 1e6), ("queued", 0, 3e6),
                                 ("queued", 0, 2e6)], annotated=False)
    trace = Trace(100_000e-9,
                  [Device([(0, 25_000), (28_000, 65_000),
                           (66_000, 100_000)])],
                  [(1_000, 40_000, "bench.step"),
                   (41_000, 80_000, "bench.step")])
    return {"spans": spans, "trace": trace, "window_s": 100e-6}


@pytest.mark.parametrize("origin_s", [0.0, 1000.0, 123456.789])
@pytest.mark.parametrize("name,want", [
    ("stage_idle_pct.backlog", 5.0),
    ("shadow_idle_pct.backlog", 13.0),
])
def test_offload_idle_readers(name, want, origin_s):
    # a perf_counter reading far from zero holds times to about 0.02 ns
    got = reader(name).read(offload_ctx(origin_s))
    assert got == pytest.approx(want, rel=1e-4)


@pytest.mark.parametrize("name,want", [
    ("sample_idle_ms_per_step.serve", 4_000e-9 / 2 * 1e3),
    ("admit_ms_per_request.serve", 13_800e-9 * 1e3),
    ("queue_wait_ms_p50.serve", 2.0),
])
def test_serve_readers(name, want):
    assert reader(name).read(serve_ctx()) == pytest.approx(want)


@pytest.mark.parametrize("name,ctx", [
    # a program whose spans open no profiler annotation
    ("stage_idle_pct.backlog", offload_ctx(annotated=False)),
    # a benchmark flush without the program's: the anchors do not pair
    ("stage_idle_pct.backlog", offload_ctx(drop=("flush2",))),
    # a flush outside its benchmark span: no offset puts both inside
    ("shadow_idle_pct.backlog", offload_ctx(flush2=("flush", 10, 20))),
    ("stage_idle_pct.backlog", offload_ctx(drop=("stage1", "stage2"))),
    ("shadow_idle_pct.backlog", offload_ctx(drop=("shadow1", "shadow2"))),
    ("sample_idle_ms_per_step.serve", serve_ctx(annotated=False)),
    ("sample_idle_ms_per_step.serve", offload_ctx()),
    ("admit_ms_per_request.serve", offload_ctx()),
    ("queue_wait_ms_p50.serve", offload_ctx()),
])
def test_silent_where_nothing_to_read(name, ctx):
    assert reader(name).read(ctx) is None


def test_innermost_program_span_takes_the_idle():
    """A program span inside ``stage`` takes the idle it covers; idle in
    the program's flush outside its children stays with the flush, and
    idle in the benchmark's flush after the program's with that."""
    ctx = offload_ctx(inner=("scatter", 13_000, 14_500))
    gaps, longest = program_idle.idle_by_span(ctx, "flush", "bench.flush")
    assert gaps["repro.scatter"] * 1e9 == pytest.approx(1_000)
    assert gaps["repro.stage"] * 1e9 == pytest.approx(4_000)
    assert gaps["repro.flush"] * 1e9 == pytest.approx(3_000)
    assert gaps["bench.flush"] * 1e9 == pytest.approx(2_000)
    assert ("repro.fidelity-shadow", pytest.approx(8e-6)) in longest


def test_clock_offset_bounds():
    host = [(100, 200, "bench.step"), (300, 400, "bench.step")]
    spans = spans_at(0.0, [("engine.step", 10, 60), ("engine.step", 230, 250)])
    # pair 1 allows [90, 140] ns, pair 2 [70, 150] ns: the earliest
    # opening of an anchor after its host span is the one taken as none
    assert program_idle.clock_offset_ns(spans, host, "engine.step",
                                        "bench.step") == pytest.approx(90)
    # an anchor that cannot close inside its host span
    late = spans_at(0.0, [("engine.step", 10, 60), ("engine.step", 230, 350)])
    assert program_idle.clock_offset_ns(late, host, "engine.step",
                                        "bench.step") is None
    assert program_idle.clock_offset_ns(spans[:1], host, "engine.step",
                                        "bench.step") is None


def test_split_by_host_lets_a_program_span_win():
    host = [(0, 100, "bench.flush"), (20, 60, "repro.stage"),
            (30, 40, "repro.scatter")]
    parts = xplane._split_by_host(0, 120, host)
    assert parts == {"bench.flush": 60, "repro.stage": 30,
                     "repro.scatter": 10, "idle": 20}
    assert sum(parts.values()) == 120


# --- the engine's counters against the serve driver's bookkeeping -------------


def test_engine_counters_equal_the_driver_steps(shrunk):
    import jax

    cell = common.cell(common.load_manifest(), "stablelm-chat")
    shrunk(cell)
    driver = common.load_module(common.HERE / "drivers" / "serve.py")
    session = driver.Session(cell, 2**33 + 5, jax.devices()[:1], trace=True)

    def counters():
        return {k[0]: v for k, v in session.tracer.metrics.counters().items()
                if k[0] in ("decode_steps", "admitted", "prefill_tokens",
                            "decode_positions")}

    before = counters()
    session.measure(0.5)
    got = {k: v - before.get(k, 0) for k, v in counters().items()}
    steps = session.steps
    assert got == {
        "decode_steps": sum(1 for st in steps if st["context"]),
        "admitted": sum(len(st["prefill"]) for st in steps),
        "prefill_tokens": sum(sum(st["prefill"]) for st in steps),
        "decode_positions": sum(sum(st["context"]) for st in steps)}
    assert got["admitted"] > 0 and got["decode_steps"] > 0
    ctx = session.layer_context()
    decodes = [s for s in ctx["spans"] if s.name == "engine.decode"]
    assert len(decodes) == got["decode_steps"]
    assert reader("admit_ms_per_request.serve").read(ctx) > 0.0
    assert reader("queue_wait_ms_p50.serve").read(ctx) >= 0.0


# --- short chip windows recorded with the program's spans ---------------------

WINDOWS = {
    # 0.4 s of p4f-fft-backlog and 0.8 s of stablelm-chat on a v5e chip,
    # recorded by `run.py --trace 1 --keep-trace`, /host:metadata stripped
    "fft": ("fft_program_window.xplane.pb", "flush", "bench.flush",
            ("stage_idle_pct.backlog", "shadow_idle_pct.backlog")),
    "chat": ("chat_program_window.xplane.pb", "engine.step", "bench.step",
             ("sample_idle_ms_per_step.serve",)),
}


def recorded(name, origin_s=777.0):
    """The window's reduction, its program spans as a tracer would hold
    them (on a clock ``origin_s`` seconds off the trace's), and its
    ``repro.*`` host events on the trace's clock."""
    import jax

    path = str(DATA / WINDOWS[name][0])
    red = xplane.reduce_trace(path)
    events, window = [], None
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                span = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                if e.name.startswith("repro."):
                    events.append(span)
                elif e.name == xplane.WINDOW_SPAN:
                    window = span
    lo, hi = window[:2]
    events = [e for e in events if lo <= e[0] and e[1] <= hi]
    spans = spans_at(origin_s, [(n[len("repro."):], a, b)
                                for a, b, n in events])
    return red, spans, events, (lo, hi)


@pytest.mark.parametrize("name", sorted(WINDOWS))
def test_recorded_window_names_idle_by_program_spans(name):
    red, spans, events, (lo, hi) = recorded(name)
    host = sorted(list(red.host_spans) + events)
    gaps, longest = xplane._gaps(red.devices, host, lo, hi, 10)
    # every idle nanosecond is named once, program spans winning inside
    # the benchmark's
    assert sum(gaps.values()) == pytest.approx(red.window_s - red.busy_s,
                                               rel=1e-6)
    assert any(n.startswith("repro.") for n, _ in longest)
    # the readers' anchor puts the tracer's spans where the trace has them
    # (off by the anchor's few microseconds of lag, a few edges a window)
    _, anchor, bench, readers = WINDOWS[name]
    ctx = {"spans": spans, "trace": red, "window_s": red.window_s}
    via, _ = program_idle.idle_by_span(ctx, anchor, bench)
    for n, s in gaps.items():
        if n.startswith("repro."):
            assert via[n] == pytest.approx(s, abs=100e-6)
    idle_pct = (1 - red.busy_s / red.window_s) * 100
    for r in readers:
        got = reader(r).read(ctx)
        assert got is not None and got > 0.0
        if r.endswith("_pct.backlog"):
            assert got < idle_pct


def test_window_without_program_spans_reads_nothing():
    """The first recorded window (``fft_window.xplane.pb``) holds no
    program span: the readers of program spans are silent there."""
    red = xplane.reduce_trace(str(DATA / "fft_window.xplane.pb"))
    ctx = {"spans": [], "trace": red, "window_s": red.window_s}
    for r in ("stage_idle_pct.backlog", "shadow_idle_pct.backlog",
              "sample_idle_ms_per_step.serve"):
        assert reader(r).read(ctx) is None
