"""Batched greedy sampling in the serving engine: one ``sample_greedy``
program and one fetch a step serve exactly the tokens of a per-lane loop
(an argmax, a fetch and a ``last_token`` update per live lane), and leave
every dead lane's decode input as it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import init_params
from repro.runtime import Tracer
from repro.serving import Request, ServingEngine
from repro.serving import engine as engine_mod

# five requests on four slots: prompts of different lengths, so live lanes
# sit at different depths; the fifth waits for a free slot
PROMPTS = [[1, 2, 3], [4, 5, 6, 7, 8, 9, 10], [11, 12], [13, 14, 15, 16],
           [17]]
MAX_NEW = [5, 9, 3, 7, 6]
SLOTS = 4


@pytest.fixture(scope="module", params=["stablelm-1.6b", "moonlight-16b-a3b"])
def model(request):
    cfg = get_smoke_config(request.param)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _engine(model, **kw):
    cfg, params = model
    engine = ServingEngine(cfg, params, batch_slots=SLOTS, max_len=32, **kw)
    reqs = [Request(rid=i, prompt=list(p), max_new_tokens=n)
            for i, (p, n) in enumerate(zip(PROMPTS, MAX_NEW))]
    for r in reqs:
        engine.submit(r)
    return engine, reqs


def _per_lane_step(engine):
    """The step as a per-lane host loop: for each live lane an argmax of
    its logits, a fetch of the token and a ``last_token`` update."""
    engine._admit()
    if not engine.active:
        return []
    logits, engine.cache = engine._decode(engine.params, engine.cache,
                                          engine.last_token)
    pos = jax.device_get(engine.cache["pos"])
    finished = []
    for slot, req in list(engine.active.items()):
        nxt = int(jnp.argmax(logits[slot]))
        req.out_tokens.append(nxt)
        engine.last_token = engine.last_token.at[slot, 0].set(nxt)
        if (engine.eos_id is not None and nxt == engine.eos_id) \
                or len(req.out_tokens) >= req.max_new_tokens \
                or int(pos[slot]) >= engine.max_len - 1:
            req.done = True
            finished.append(req)
            del engine.active[slot]
            engine.live[slot] = False
    return finished


def _same_tree(a, b) -> bool:
    return jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda x, y: np.array_equal(np.asarray(x), np.asarray(y)), a, b))


def test_batched_sampling_serves_the_per_lane_tokens(model):
    # the end-of-sequence token: the second decoded token of the longest
    # request, so that lane ends mid-batch while the others decode on
    probe, probe_reqs = _engine(model)
    probe.run_to_completion(max_steps=64)
    eos = probe_reqs[1].out_tokens[2]

    batched, got = _engine(model, eos_id=eos)
    looped, want = _engine(model, eos_id=eos)
    lives, depths, eos_ends = [], [], []
    for _ in range(64):
        fin = batched.step()
        fin_ref = _per_lane_step(looped)
        assert [r.rid for r in fin] == [r.rid for r in fin_ref]
        assert np.array_equal(np.asarray(batched.last_token),
                              np.asarray(looped.last_token))
        # dead lanes decoded the same inputs: caches (expert loads
        # included) are bit-identical
        assert _same_tree(batched.cache, looped.cache)
        assert batched.live == looped.live
        lives.append(list(batched.live))
        pos = np.asarray(batched.cache["pos"])
        depths.append({int(pos[s]) for s in batched.active})
        eos_ends += [r.rid for r in fin if r.out_tokens[-1] == eos
                     and len(r.out_tokens) < r.max_new_tokens
                     and batched.active]
        if batched.idle():
            break
    assert looped.idle()
    assert all(r.done for r in got)
    assert [r.out_tokens for r in got] == [r.out_tokens for r in want]
    # the traffic covers what the batched path must get right
    assert eos_ends, "no lane ended on EOS while others stayed live"
    assert any(len(d) > 1 for d in depths), "live lanes never at two depths"
    assert any(not a[s] and not b[s] and any(b)
               for a, b in zip(lives, lives[1:]) for s in range(SLOTS)), \
        "no lane stayed dead across two decoding steps"


@pytest.mark.parametrize("n_live", [1, 3])
def test_one_step_samples_in_one_program_and_one_fetch(model, monkeypatch,
                                                        n_live):
    cfg, params = model
    tracer = Tracer()
    engine = ServingEngine(cfg, params, batch_slots=SLOTS, max_len=32,
                           tracer=tracer)
    for i in range(n_live):
        engine.submit(Request(rid=i, prompt=list(PROMPTS[i]),
                              max_new_tokens=8))
    engine.step()                       # admissions fetch on their own
    assert len(engine.active) == n_live

    calls = {"sample": 0, "device_get": 0}
    real_sample, real_get = engine._sample, jax.device_get

    def sample(*a):
        calls["sample"] += 1
        return real_sample(*a)

    def device_get(x):
        calls["device_get"] += 1
        return real_get(x)

    engine._sample = sample
    monkeypatch.setattr(engine_mod.jax, "device_get", device_get)
    before = tracer.metrics.counter("sampled_lanes").value
    engine.step()
    assert calls == {"sample": 1, "device_get": 1}
    assert tracer.metrics.counter("sampled_lanes").value - before == n_live
