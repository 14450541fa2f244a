"""The expert layer and its router, by config: selection by the biased
score and weights by the score, a dropless layer that computes every
(token, held expert) pair, the expert load it reports through the model
and the engine, and configurations read from JSON."""

import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.models import LM, MLAConfig, ModelConfig, MoEConfig, init_params
from repro.models.moe import moe_apply, route
from repro.runtime import Tracer
from repro.serving import Request, ServingEngine

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _layer(cfg, key=0):
    """One expert layer's parameters of ``cfg`` (the first stacked one)."""
    p = init_params(cfg, jax.random.PRNGKey(key))["stack"]["0_attn"]["mlp"]
    return jax.tree_util.tree_map(lambda a: a[0], p)


def _expert(p, e, x):
    h = jax.nn.silu(x @ p["we_in"][e]) * (x @ p["we_gate"][e])
    return h @ p["we_out"][e]


def test_router_selects_by_biased_score_and_weights_by_score():
    cfg = get_smoke_config("moonlight-16b-a3b")
    m = cfg.moe
    p = _layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
    s = jax.nn.sigmoid(x @ p["router"])
    # a bias that reorders the top-k: it lifts the experts each token
    # scores lowest above all the others
    bias = -2.0 * jnp.argsort(jnp.argsort(s.mean(0)))
    p = dict(p, router_bias=bias.astype(jnp.float32))
    w, idx, _ = route(cfg, p, x)
    by_score = jax.lax.top_k(s, m.top_k)[1]
    by_choice = jax.lax.top_k(s + bias, m.top_k)[1]
    assert not np.array_equal(np.sort(by_score, -1), np.sort(by_choice, -1))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(by_choice))
    want = jnp.take_along_axis(s, by_choice, -1)
    want = want / want.sum(-1, keepdims=True) * m.routed_scale
    np.testing.assert_allclose(np.asarray(w), np.asarray(want), rtol=1e-5)


def test_softmax_router_without_bias():
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    assert cfg.moe.scoring == "softmax" and not cfg.moe.selection_bias
    p = _layer(cfg)
    assert "router_bias" not in p
    x = jax.random.normal(jax.random.PRNGKey(1), (16, cfg.d_model))
    w, idx, _ = route(cfg, p, x)
    s = jax.nn.softmax(x @ p["router"], axis=-1)
    top, want = jax.lax.top_k(s, cfg.moe.top_k)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want))
    np.testing.assert_allclose(np.asarray(w),
                               np.asarray(top / top.sum(-1, keepdims=True)),
                               rtol=1e-5)


def _all_to_experts(cfg, p, experts):
    """Router weights of zero and a bias that sends every token to
    ``experts``."""
    bias = jnp.full((cfg.moe.n_routed,), -1.0).at[jnp.asarray(experts)].set(1.0)
    return dict(p, router=jnp.zeros_like(p["router"]), router_bias=bias)


@pytest.mark.parametrize("first_held", [0, 4])
def test_dropless_when_every_token_picks_one_held_expert(first_held):
    """Every token routed to held expert 0 (and to one other expert):
    expert 0 takes all of them, far past any capacity, and each still gets
    its full contribution."""
    cfg = get_smoke_config("moonlight-16b-a3b")
    share = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, first_held=first_held, n_held=4))
    p = _layer(share)
    p = _all_to_experts(share, p, [first_held, (first_held + 4) % 8])
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 10, cfg.d_model))
    out, _, load = moe_apply(share, {k: v for k, v in p.items()
                                     if not k.startswith("ws_")}, x)
    np.testing.assert_array_equal(np.asarray(load), [30, 0, 0, 0])
    # sigmoid(0) = 1/2 on both chosen experts: each weighs routed_scale / 2
    want = 0.5 * cfg.moe.routed_scale * _expert(p, 0, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4,
                               rtol=1e-4)


def test_capacity_factor_drops_past_capacity():
    """The same load under a capacity factor keeps each batch row's first
    ``capacity`` tokens of the expert and drops the rest."""
    cfg = get_smoke_config("moonlight-16b-a3b")
    capped = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=1.0))
    p = _all_to_experts(cfg, _layer(cfg), [0, 1])
    p = {k: v for k, v in p.items() if not k.startswith("ws_")}
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 10, cfg.d_model))
    out, _, load = moe_apply(capped, p, x)
    cap = -(-10 * 2 // 8)                        # ceil(S * k * 1.0 / E)
    np.testing.assert_array_equal(np.asarray(load)[:2], [3 * cap] * 2)
    kept = jnp.arange(10) < cap
    want = 0.5 * cfg.moe.routed_scale * (_expert(p, 0, x) + _expert(p, 1, x))
    want = jnp.where(kept[None, :, None], want, 0.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4,
                               rtol=1e-4)


@pytest.mark.parametrize("sizes,axes,rows,groups", [
    ((2, 2, 4), ("pod", "data", "model"), 8, 4),
    ((2, 4), ("data", "model"), 6, 2),
    ((2, 4), ("data", "model"), 3, 1),     # 2 shards do not divide 3 rows
    ((8,), ("model",), 8, 1),
])
def test_dispatch_groups_are_the_mesh_batch_shards(sizes, axes, rows, groups):
    from repro.models.moe import _dispatch_groups
    assert _dispatch_groups(rows) == 1                    # off-mesh
    with jax.sharding.use_abstract_mesh(jax.sharding.AbstractMesh(sizes, axes)):
        assert _dispatch_groups(rows) == groups


@pytest.mark.parametrize("capacity_factor", [None, 1.0])
def test_dispatch_groups_compute_what_one_group_does(monkeypatch,
                                                     capacity_factor):
    """Sorting each batch shard's pairs on its own changes no output and
    no load, dropless or under a capacity."""
    import repro.models.moe as moe
    cfg = get_smoke_config("moonlight-16b-a3b")
    share = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, n_held=4, capacity_factor=capacity_factor))
    p = _layer(share)
    x = jax.random.normal(jax.random.PRNGKey(5), (6, 10, cfg.d_model))
    one = moe_apply(share, p, x)
    monkeypatch.setattr(moe, "_dispatch_groups", lambda b: 3)
    three = moe_apply(share, p, x)
    np.testing.assert_array_equal(np.asarray(three[2]), np.asarray(one[2]))
    np.testing.assert_allclose(np.asarray(three[0]), np.asarray(one[0]),
                               atol=1e-6, rtol=1e-6)


def test_model_config_from_json_dicts():
    cfg = json.loads((ROOT / "benchmarks" / "chip" / "configs"
                      / "moonlight-16b-a3b.json").read_text())
    got = ModelConfig(**cfg["model"])
    assert isinstance(got.moe, MoEConfig) and isinstance(got.mla, MLAConfig)
    assert got.moe.held == 8 and got.mla.q_lora_rank is None
    assert got.pattern == ("attn",)
    full = get_config("moonlight-16b-a3b")
    assert got == dataclasses.replace(
        full, moe=dataclasses.replace(full.moe, n_held=8))
    assert ModelConfig(**dict(cfg["model"], pattern=["attn"])) == got


def test_moonlight_parameters_as_published():
    from repro.models import param_counts
    total, active = param_counts(get_config("moonlight-16b-a3b"))
    assert abs(total - 15.96e9) < 0.01e9       # "16B"
    assert 2.5e9 < active < 3.5e9              # "A3B"


def test_prefill_and_decode_report_the_expert_load():
    """Every expert held: each expert layer computes top_k pairs a token."""
    cfg = get_smoke_config("moonlight-16b-a3b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    model = LM(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, 100)
    cache, _ = model.prefill(params, {"tokens": toks[:, :8]}, max_len=16)
    load = np.asarray(cache["expert_tokens"])
    assert load.shape == (cfg.n_moe_layers, cfg.moe.held)
    assert (load.sum(1) == 2 * 8 * cfg.moe.top_k).all()
    _, cache = model.decode_step(params, cache, toks[:, 8:])
    assert (np.asarray(cache["expert_tokens"]).sum(1)
            == 2 * cfg.moe.top_k).all()
    assert model.init_cache(2, 16)["expert_tokens"].shape == load.shape


def test_engine_annotates_spans_and_counts_the_load():
    cfg = get_smoke_config("moonlight-16b-a3b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    tracer = Tracer()
    engine = ServingEngine(cfg, params, batch_slots=3, max_len=24,
                           tracer=tracer)
    reqs = [Request(rid=i, prompt=list(range(1, 2 + i)), max_new_tokens=3)
            for i in range(4)]
    for r in reqs:
        engine.submit(r)
    engine.run_to_completion(max_steps=32)
    k, layers = cfg.moe.top_k, cfg.n_moe_layers
    spans = tracer.spans()
    prefills = [s for s in spans if s.name == "engine.prefill"]
    decodes = [s for s in spans if s.name == "engine.decode"]
    assert [s.attrs["expert_tokens"] for s in prefills] == [
        len(r.prompt) * k * layers for r in reqs]
    # a decode step computes top_k pairs a lane in each layer, the free
    # lanes' included
    assert all(s.attrs["expert_tokens"] == 3 * k * layers for s in decodes)
    for s in prefills + decodes:
        assert 0 < s.attrs["experts_reached"] <= layers * cfg.moe.held
        assert 0 < s.attrs["max_expert_tokens"] <= s.attrs["expert_tokens"]
    counters = {n[0]: v for n, v in tracer.metrics.counters().items()}
    for name in ("expert_tokens", "experts_reached", "max_expert_tokens"):
        assert counters[name] == sum(s.attrs[name] for s in prefills + decodes)


def test_untraced_engine_records_no_load():
    cfg = get_smoke_config("moonlight-16b-a3b")
    engine = ServingEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                           batch_slots=2, max_len=16)
    engine.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=2))
    engine.run_to_completion(max_steps=8)
    assert engine.tracer is None and engine._load(engine.cache) is None
