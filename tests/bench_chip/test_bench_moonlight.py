"""The ``moonlight-chat`` cell on the CPU: the plain reference against the
program at a small size, the expert shares against the uncut layer, the
harness's ``correct`` with the cell cut by a shrink of its own (the
shared one leaves latent ranks and experts at full size), and the work
counts and readers of its per-layer metrics."""

import argparse
import dataclasses
import gc
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import common, run, workcount_moe
from benchmarks.chip.drivers.serve import model_dims

WORKLOAD = "moonlight-chat"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def ref():
    return common.load_module(common.HERE / "references" / "mla_moe_lm.py")


def reader(name):
    return common.load_module(common.HERE / "metrics" / f"{name}.py")


def smoke(**kw):
    from repro.configs import get_smoke_config
    return dataclasses.replace(get_smoke_config("moonlight-16b-a3b"), **kw)


def dims_of(cfg) -> dict:
    d = dataclasses.asdict(cfg)
    d["padded_vocab"] = cfg.padded_vocab
    return d


# --- the plain reference against the program --------------------------------


def test_reference_draws_the_programs_weights():
    from repro.models import init_params
    cfg = smoke(param_dtype="bfloat16")
    key = jax.random.PRNGKey(11)
    prog = {"/".join(k.key for k in path): v for path, v in
            jax.tree_util.tree_flatten_with_path(init_params(cfg, key))[0]}
    mine = ref().init_weights(dims_of(cfg), key)
    assert sorted(prog) == sorted(mine)
    for k, v in prog.items():
        assert v.dtype == mine[k].dtype, k
        np.testing.assert_array_equal(np.asarray(v), np.asarray(mine[k]))
    assert prog["stack/0_attn/mlp/router_bias"].dtype == jnp.float32


# float32 activations: both sides compute the same float32 products, so
# they differ only by summation order (the absorbed decode contracts the
# latent where the reference up-projects K and V): 1e-4 of logits whose
# spread is about 0.16.  bfloat16 activations, as the cell runs: each
# block's input rounds to 8 bits of mantissa, 2e-2 of the same logits.
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4),
                                       ("bfloat16", 2e-2)])
def test_prefill_then_cached_decode_match_the_reference(dtype, tol):
    from repro.models import LM, init_params
    cfg = smoke(dtype=dtype)
    key = jax.random.PRNGKey(5)
    params = init_params(cfg, key)
    model = LM(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 20), 0,
                              cfg.vocab_size)
    cache, lg = jax.jit(lambda p, b: model.prefill(p, b, max_len=24))(
        params, {"tokens": toks[:, :14]})
    got = [lg]
    step = jax.jit(model.decode_step)
    for i in range(14, 20):
        lg, cache = step(params, cache, toks[:, i:i + 1])
        got.append(lg)
    got = np.stack([np.asarray(g) for g in got], axis=1)    # (B, 7, V)
    r = ref()
    dims = dims_of(cfg)
    w = r.init_weights(dims, key)
    for b in range(2):
        want = r.logits_at(dims, w, toks[b], jnp.arange(13, 20))
        np.testing.assert_allclose(got[b], np.asarray(want), atol=tol,
                                   rtol=0)


def test_expert_shares_sum_to_the_uncut_layer():
    """8 experts over 4 ranks of 2: the routed parts of every rank's share,
    and the shared experts once, give the uncut reference layer."""
    from repro.models import init_params
    from repro.models.moe import moe_apply
    cfg = smoke()
    assert cfg.moe.n_routed == 8
    p = init_params(cfg, jax.random.PRNGKey(2))["stack"]["0_attn"]["mlp"]
    p = jax.tree_util.tree_map(lambda a: a[0], p)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, cfg.d_model))
    routed = {k: v for k, v in p.items() if not k.startswith("ws_")}
    total = jnp.zeros_like(x)
    for rank in range(4):
        share = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, first_held=2 * rank, n_held=2))
        part = dict(routed, **{k: v[2 * rank:2 * rank + 2] for k, v in
                               routed.items() if k.startswith("we_")})
        out, _, load = moe_apply(share, part, x)
        assert load.shape == (2,)
        total = total + out
    total = total + moe_apply(cfg, p, x)[0] - moe_apply(cfg, routed, x)[0]
    weights = {f"mlp/{k}": v for k, v in p.items()}
    want = jax.vmap(lambda xb: ref().expert_layer(dims_of(cfg), weights,
                                                  xb))(x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-4, rtol=0)


# --- the harness's `correct`, at a size the CPU runs --------------------------


def shrink(cell: dict) -> None:
    """The cell cut to run in seconds on the CPU: six layers (one dense,
    five of experts) at the published model width, so that its logits
    spread as the chip's do; 8 experts of 256, 4 held, one a token, so
    that a held expert weighs on its tokens as much as the test of a
    fault needs; an 8192-token vocabulary, since a logit gap grows with
    how far the best logit stands out of the vocabulary (at 500 tokens the
    zeroed expert's gap, 1.97, fell under the limit the chip's readings
    set at the published 163840); four slots (five stacked expert layers,
    so no leaf mistakes a layer dimension for the lanes).
    Activations are float32: a sound run then departs from the reference
    by summation order alone, and no routing near-tie flips an expert
    (with one expert a token, a flip moves a logit as far as the fault);
    the chip's readings set the limit for bfloat16."""
    m = cell["config"]["model"]
    m.update(n_layers=6, d_ff=1024, vocab_size=8192, vocab_pad_multiple=64,
             dtype="float32")
    m["moe"].update(n_routed=8, n_held=4, top_k=1, d_expert=256,
                    d_shared=256)
    cell["config"]["engine"].update(batch_slots=4, max_len=64)
    mix = cell["mix"]
    mix.update(clients=4, pool=64, block=8, aux_frame_shape=[32, 24])
    mix["prompt"].update(median=16, min=8, max=32, multiple=8)
    mix["output"].update(median=16, min=8, max=24)
    mix["sample"].update(tokens=200, aux_every=1)


def cpu_run(capsys, session_hook=None) -> dict:
    args = argparse.Namespace(workload=WORKLOAD, seed=2**33 + 5, seconds=4.0,
                              trace=0, keep_trace=None)
    rc = run.run(args, require=lambda chips: jax.devices()[:1] * chips,
                 peaks_for=lambda kind: PEAKS, edit_cell=shrink,
                 session_hook=session_hook)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def limits():
    return common.load_json(f"limits/{WORKLOAD}.json", common.HERE)


def test_sound_run_is_correct(capsys):
    res = cpu_run(capsys)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert {c["name"] for c in res["checks"]} == set(limits())


def test_control_fails_a_number():
    cell = common.cell(common.load_manifest(), WORKLOAD)
    shrink(cell)
    driver = common.load_module(common.HERE / "drivers" / "serve.py")
    session = driver.Session(cell, 7, jax.devices()[:1])
    session.measure(4.0)
    session.release()
    gc.collect()
    lim = limits()
    program = session.readings()
    control = session.readings(control=True)
    assert all(program[n] <= lim[n] for n in lim), program
    assert any(control[n] > lim[n] for n in lim), control


def _zero_one_held_expert(session):
    """Held expert 0 of every expert layer answers zero, in the weights
    the engine's prefill and decode run with."""
    mlp = session.engine.params["stack"]["0_attn"]["mlp"]
    mlp["we_out"] = mlp["we_out"].at[:, 0].set(0)


def test_zeroed_held_expert_makes_run_incorrect(capsys):
    res = cpu_run(capsys, session_hook=_zero_one_held_expert)
    assert res["correct"] is False


# --- work counts and readers ----------------------------------------------------


def published() -> dict:
    cfg = common.load_json("benchmarks/chip/configs/moonlight-16b-a3b.json")
    return model_dims(cfg)


def test_work_counts_at_published_widths():
    m = published()
    assert m["moe"]["n_held"] == 8
    # the EP8 share: 3.36 B parameters; the uncut model: the published 16 B
    assert workcount_moe.share_params(m) == 3_364_615_296
    assert abs(workcount_moe.uncut_params(m) - 15.96e9) < 0.01e9
    assert workcount_moe.latent_bytes_per_position(m) == 31_104
    # a decode step that reaches every held expert reads about 6.06 GB
    full = workcount_moe.decode_step_bytes(m, 0, 26 * 8)
    assert abs(full - 6.06e9) < 0.01e9


def test_uncut_count_matches_the_program():
    from repro.configs import get_config
    from repro.models import param_counts
    from repro.models.config import ModelConfig
    m = published()
    share, _ = param_counts(ModelConfig(**{k: v for k, v in m.items()
                                           if k != "padded_vocab"}))
    assert share == workcount_moe.share_params(m)
    uncut, _ = param_counts(get_config("moonlight-16b-a3b"))
    assert uncut == workcount_moe.uncut_params(m)


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float
    attrs: dict = dataclasses.field(default_factory=dict)


class Trace:
    def __init__(self, modules):
        self.modules = modules

    def module_s(self, names):
        return sum(s for n, s in self.modules.items() if n in names)


def moe_ctx(**kw):
    """Two decode steps of 4 lanes and one admission of 8 tokens."""
    load = {"expert_tokens": 104, "experts_reached": 100,
            "max_expert_tokens": 2}
    ctx = {"model": published(), "window_s": 0.5, "chips": 1,
           "peaks": PEAKS,
           "steps": [{"prefill": [8], "context": [100, 200, 300, 400]},
                     {"prefill": [], "context": [101, 201, 301, 401]}],
           "spans": [Span("engine.prefill", 0.0, 0.1,
                          {"expert_tokens": 8 * 6 * 26 // 8}),
                     Span("engine.decode", 0.1, 0.2, dict(load)),
                     Span("engine.decode", 0.3, 0.4, dict(load))],
           "trace": Trace({"jit_decode_step": 0.02, "jit_other": 1.0})}
    ctx.update(kw)
    return ctx


def test_expert_tokens_per_step():
    # 104 pairs a step over 26 layers of 8 held experts
    assert reader("expert_tokens_per_step.moe").read(moe_ctx()) == 0.5


def test_moe_decode_roofline():
    m = published()
    least = (workcount_moe.decode_step_bytes(m, 1000, 100)
             + workcount_moe.decode_step_bytes(m, 1004, 100))
    got = reader("moe_decode_roofline").read(moe_ctx())
    assert got == pytest.approx(least / 819e9 / 0.02 * 100)
    assert 0.0 < got < 100.0


def test_moe_serve_mfu():
    m = published()
    flops = (workcount_moe.prefill_flops(m, 8)
             + sum(workcount_moe.token_flops(m, c, True, True)
                   for c in (100, 200, 300, 400, 101, 201, 301, 401))
             + (8 * 6 * 26 // 8 + 2 * 104) * workcount_moe.expert_pair_flops(m))
    got = reader("moe_serve_mfu_pct").read(moe_ctx())
    assert got == pytest.approx(flops / (0.5 * 197e12) * 100)
    # a prefill token's attention is counted on up-projected K and V, a
    # decoded token's in the latent space
    assert workcount_moe.prefill_flops(m, 1) == pytest.approx(
        workcount_moe.token_flops(m, 1, True, False))


@pytest.mark.parametrize("name", ["expert_tokens_per_step.moe",
                                  "moe_decode_roofline",
                                  "moe_serve_mfu_pct"])
@pytest.mark.parametrize("ctx", [
    # a program that reports no expert load (as one without the layer)
    moe_ctx(spans=[Span("engine.decode", 0.1, 0.2),
                   Span("engine.prefill", 0.0, 0.1)]),
    moe_ctx(spans=[]),
], ids=["no-load", "no-spans"])
def test_silent_where_nothing_to_read(name, ctx):
    assert reader(name).read(ctx) is None
