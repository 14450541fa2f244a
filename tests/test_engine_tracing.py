"""Spans and counters of the serving engine: what a traced engine records,
that it serves the same tokens as an untraced one, and that without a
tracer it calls into neither the tracer nor the profiler."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.core.accelerator import PROTOTYPE_4F
from repro.models import init_params
from repro.runtime import OffloadExecutor, Tracer
from repro.serving import Request, ServingEngine

PROMPTS = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 10], [11, 12, 13, 14], [15]]
MAX_NEW = [4, 2, 5, 3, 6]


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config("stablelm-1.6b")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _serve(model, *, tracer=None, offload=None, bucket=1):
    cfg, params = model
    engine = ServingEngine(cfg, params, batch_slots=2, max_len=32,
                           prompt_bucket=bucket, offload=offload,
                           tracer=tracer)
    reqs = [Request(rid=i, prompt=list(p), max_new_tokens=n)
            for i, (p, n) in enumerate(zip(PROMPTS, MAX_NEW))]
    for r in reqs:
        engine.submit(r)
    engine.run_to_completion(max_steps=64)
    assert all(r.done for r in reqs)
    return engine, reqs


def _counters(tracer):
    return {k[0]: v for k, v in tracer.metrics.counters().items()}


def test_traced_engine_serves_the_same_tokens(model):
    _, plain = _serve(model)
    _, traced = _serve(model, tracer=Tracer())
    assert [r.out_tokens for r in traced] == [r.out_tokens for r in plain]


def test_engine_spans_nest_as_documented(model):
    tracer = Tracer()
    _serve(model, tracer=tracer)
    spans = tracer.spans()
    by_id = {s.span_id: s for s in spans}

    def parent(s):
        return by_id[s.parent_id].name if s.parent_id in by_id else None

    names = {s.name for s in spans}
    assert names == {"engine.step", "engine.admit", "engine.prefill",
                     "engine.splice", "engine.aux", "engine.decode",
                     "engine.sample", "queued"}
    assert all(s.lane == "engine" for s in spans)
    for s in spans:
        if s.name in ("engine.prefill", "engine.splice"):
            assert parent(s) == "engine.admit"
        elif s.name in ("engine.admit", "engine.aux", "engine.decode",
                        "engine.sample"):
            assert parent(s) == "engine.step"
        if s.parent_id in by_id and s.kind == "sync":
            p = by_id[s.parent_id]
            assert p.t0 <= s.t0 and s.t1 <= p.t1
    admits = [s for s in spans if s.name == "engine.admit"]
    queued = sorted((s for s in spans if s.name == "queued"),
                    key=lambda s: s.attrs["rid"])
    assert len(admits) == len(queued) == len(PROMPTS)
    assert [s.attrs["rid"] for s in queued] == list(range(len(PROMPTS)))
    # a request waits from submit until its admission opens
    for q, a in zip(queued, sorted(admits, key=lambda s: s.t0)):
        assert q.t1 <= a.t0
    # only the lexical spans reach the profiler
    assert all(s.annotated for s in spans if s.name != "queued")
    assert not any(s.annotated for s in queued)


@pytest.mark.parametrize("bucket", [1, 4])
def test_engine_counters(model, bucket):
    tracer = Tracer()
    _, reqs = _serve(model, tracer=tracer, bucket=bucket)
    got = _counters(tracer)
    padded = [len(r.prompt) + (-len(r.prompt)) % bucket for r in reqs]
    # decode k of a request attends its padded prompt and k tokens
    positions = sum(p + k for p, r in zip(padded, reqs)
                    for k in range(1, len(r.out_tokens)))
    decodes = [s for s in tracer.spans() if s.name == "engine.decode"]
    # every token after a request's first comes from the batched sampler
    sampled = sum(len(r.out_tokens) - 1 for r in reqs)
    assert got == {"admitted": len(reqs), "prefill_tokens": sum(padded),
                   "decode_steps": len(decodes),
                   "decode_positions": positions,
                   "sampled_lanes": sampled}


def test_engine_records_on_its_offload_runtime_tracer(model):
    """With no tracer of its own the engine takes its offload runtime's,
    so the executor's spans opened by a step nest under ``engine.aux``."""
    tracer = Tracer()
    ex = OffloadExecutor(PROTOTYPE_4F, max_batch=4, tracer=tracer)
    cfg, params = model
    engine = ServingEngine(cfg, params, batch_slots=2, max_len=32,
                           offload=ex)
    assert engine.tracer is tracer
    engine.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=2))
    h = engine.submit_aux("fft", jnp.ones((16, 16)))
    engine.run_to_completion(max_steps=8)
    assert h.ready
    by_id = {s.span_id: s for s in tracer.spans()}
    (flush,) = [s for s in by_id.values() if s.name == "flush"]
    (release,) = [s for s in by_id.values() if s.name == "release"]
    assert by_id[flush.parent_id].name == "engine.aux"
    assert release.parent_id == flush.span_id
    assert _counters(tracer)["admitted"] == 1


def test_untraced_engine_never_calls_the_tracer(model, monkeypatch):
    from repro.runtime import metrics, tracing

    def refuse(*a, **k):
        raise AssertionError("tracer or profiler called without a tracer")

    monkeypatch.setattr(tracing, "TraceAnnotation", refuse)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    for method in ("now", "span", "begin", "record"):
        monkeypatch.setattr(tracing.Tracer, method, refuse)
    monkeypatch.setattr(metrics.MetricsRegistry, "counter", refuse)
    engine, reqs = _serve(model)
    assert engine.tracer is None
    assert all(r.out_tokens for r in reqs)


def test_spans_add_no_host_device_sync(model, monkeypatch):
    """A traced engine fetches from the device exactly as often as an
    untraced one."""
    calls = {"n": 0}
    real = jax.device_get

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    counts = []
    for tracer in (None, Tracer()):
        calls["n"] = 0
        _serve(model, tracer=tracer)
        counts.append(calls["n"])
    assert counts[0] == counts[1] > 0
