"""Each per-layer metric reader, on a hand-made context: the number it
reads, and silence where it finds nothing to read."""

import dataclasses

import pytest

from benchmarks.chip import common, workcount

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
STABLELM = dict(d_model=2048, d_ff=5632, n_heads=32, n_kv_heads=32,
                n_layers=24, padded_vocab=100352, vocab_size=100352,
                tie_embeddings=False, dtype="bfloat16",
                param_dtype="float32")


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float


class Trace:
    """Stands in for ``xplane.Reduction``: busy time and program time."""

    def __init__(self, busy_s, window_s, modules):
        self.busy_s, self.window_s, self.modules = busy_s, window_s, modules

    def module_s(self, names):
        return sum(s for n, s in self.modules.items() if n in names)


def reader(name):
    return common.load_module(common.HERE / "metrics" / f"{name}.py")


def offload_ctx(**kw):
    ctx = {"window_s": 2.0, "frames": 200, "calls": 200, "invocations": 50,
           "category": "fft", "frame_shape": (1024, 768), "chips": 1,
           "peaks": V5E,
           "spans": [Span("stage", 0.0, 0.1), Span("stage", 0.5, 0.6),
                     Span("fidelity-shadow", 1.0, 1.5)],
           "trace": Trace(0.5, 2.0, {"jit_dft_stage1_batched": 0.02,
                                     "jit_dft_stage2_batched": 0.01,
                                     "jit_clip": 0.002, "jit_other": 1.0})}
    ctx.update(kw)
    return ctx


LEAST = 6_291_456 / 819e9          # one 1024x768 frame, bound by memory


@pytest.mark.parametrize("name,want", [
    ("dispatches_per_frame.backlog", 0.25),
    ("stage_ms_per_frame.backlog", 0.2 / 200 * 1e3),
    ("shadow_share_pct.backlog", 25.0),
    ("device_idle_pct.backlog", 75.0),
    # the eager ADC programs, which the shadow runs too, are not counted
    ("fft_roofline", 200 * LEAST / 0.03 * 100),
    ("offload_mfu_pct", 200 * LEAST / 2.0 * 100),
])
def test_offload_readers(name, want):
    assert reader(name).read(offload_ctx()) == pytest.approx(want)


@pytest.mark.parametrize("name,ctx", [
    ("shadow_share_pct.backlog", offload_ctx(spans=[])),
    ("fft_roofline", offload_ctx(trace=Trace(0.5, 2.0, {"jit_x": 1.0}))),
    ("fft_roofline", offload_ctx(category="conv")),
    ("offload_mfu_pct", offload_ctx(frames=0)),
    ("dispatches_per_frame.backlog", offload_ctx(calls=0)),
    ("decode_roofline", {"steps": []}),
    ("serve_mfu_pct", {"steps": []}),
])
def test_silent_where_nothing_to_read(name, ctx):
    assert reader(name).read(ctx) is None


def test_offload_mfu_per_chip():
    one = reader("offload_mfu_pct").read(offload_ctx())
    four = reader("offload_mfu_pct").read(offload_ctx(chips=4))
    assert four == pytest.approx(one / 4)


def serve_ctx():
    # two steps: one admits a 128-token prompt, both decode two lanes
    steps = [{"prefill": [128], "context": [128, 300]},
             {"prefill": [], "context": [129, 301]}]
    return {"steps": steps, "model": STABLELM, "window_s": 1.0, "chips": 1,
            "peaks": V5E,
            "trace": Trace(0.6, 1.0, {"jit_decode_step": 0.1})}


def test_decode_roofline():
    live = (128 + 300) + (129 + 301)
    least = 2 * workcount.lm_decode_step_bytes(STABLELM, 0) \
        + live * workcount.lm_kv_bytes_per_position(STABLELM)
    got = reader("decode_roofline").read(serve_ctx())
    assert got == pytest.approx(least / 819e9 / 0.1 * 100)


def test_serve_mfu():
    m = STABLELM
    flops = sum(workcount.lm_token_flops(m, p + 1, False) for p in range(128))
    flops += 2.0 * m["d_model"] * m["vocab_size"]
    for c in (128, 300, 129, 301):
        flops += workcount.lm_token_flops(m, c, True)
    got = reader("serve_mfu_pct").read(serve_ctx())
    assert got == pytest.approx(flops / 197e12 * 100)


def test_idle_of_the_serving_device():
    assert reader("device_idle_pct.serve").read(serve_ctx()) == \
        pytest.approx(40.0)
