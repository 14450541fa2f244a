"""``correct`` at a size the CPU holds: a sound run passes, the control
(the reference at the precision below the configuration's) fails one of
the cell's numbers, and so does each fault the cell can have, planted in
the timed path underneath a run whose chip check is replaced."""

import gc

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import common

CELLS = ["p4f-fft-backlog", "stablelm-chat"]


def limits(workload):
    return common.load_json(f"limits/{workload}.json", common.HERE)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(cpu_run, workload):
    res = cpu_run(workload)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert {c["name"] for c in res["checks"]} == set(limits(workload))
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_a_number(shrunk, workload):
    cell = common.cell(common.load_manifest(), workload)
    shrunk(cell)
    driver = common.load_module(
        common.HERE / "drivers" / f"{cell['config']['driver']}.py")
    session = driver.Session(cell, 7, jax.devices()[:1])
    session.measure(0.5)
    session.release()
    gc.collect()
    lim = limits(workload)
    program = session.readings()
    control = session.readings(control=True)
    assert all(program[n] <= lim[n] for n in lim), program
    assert any(control[n] > lim[n] for n in lim), control


def _bend(o):
    """One value of an answer, next to its corner, lifted by twice the
    answer's peak: a bright spot that was not there."""
    return o.at[..., 0, 1].add(2.0 * jnp.max(jnp.abs(o)) + 1.0)


def _alter_answers(session):
    """The optical backend's answers altered where they are produced."""
    be = session.ex._backend("optical-sim")
    real = be.run

    def run(category, xs, ctx, **kw):
        outs, cost = real(category, xs, ctx, **kw)
        return [_bend(o) for o in outs], cost
    be.run = run


def _alter_tokens(session):
    """Each served token altered as the engine appends it."""
    engine = session.engine
    real = engine.step
    vocab = engine.cfg.vocab_size

    def step():
        fin = real()
        for r in list(engine.active.values()) + fin:
            if r.out_tokens and not getattr(r, "_bent", 0) == len(r.out_tokens):
                r.out_tokens[-1] = (r.out_tokens[-1] + 1) % vocab
                r._bent = len(r.out_tokens)
        return fin
    engine.step = step


def _drop_half_the_batch(session):
    """Half of each dispatched group left out: the second half of the
    group is answered with the first half's results."""
    be = session.ex._backend("optical-sim")
    real = be.run

    def run(category, xs, ctx, **kw):
        keep = (len(xs) + 1) // 2
        outs, cost = real(category, xs[:keep], ctx, **kw)
        return [outs[i % keep] for i in range(len(xs))], cost
    be.run = run


def _stale_decode_state(session):
    """A decode step that returns the cache unchanged: every step decodes
    from the state the engine held before it."""
    engine = session.engine
    real = engine._decode

    def decode(params, cache, last):
        logits, _ = real(params, cache, last)
        return logits, cache
    engine._decode = decode


@pytest.mark.parametrize("workload,fault", [
    ("p4f-fft-backlog", _alter_answers),
    ("p4f-fft-backlog", _drop_half_the_batch),
    ("stablelm-chat", _alter_tokens),
    ("stablelm-chat", _stale_decode_state),
])
def test_fault_makes_run_incorrect(cpu_run, workload, fault):
    res = cpu_run(workload, session_hook=fault)
    assert res["correct"] is False
