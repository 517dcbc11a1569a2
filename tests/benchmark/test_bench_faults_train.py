"""A training cell's run comes out not correct when the timed train step
or round is broken underneath, and correct when it is not: the whole of
`run.py` past its look for a chip, on a tiny cell on the CPU."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

import bench_tiny as T

copy = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))


def _none(x):
    return x is None


def unchanged(plan):
    """A step that returns its state unchanged."""
    def step(state, batch):
        _, m = plan.step_fn(copy(state), batch)
        return state, m
    return dataclasses.replace(plan, step_fn=step)


def half_batch(plan):
    """Half of each cohort's batch left out, the mean over the rest."""
    def step(state, batch):
        b = batch["tokens"]
        return plan.step_fn(state, {"tokens": b[:, :b.shape[1] // 2]})
    return dataclasses.replace(plan, step_fn=step)


def answer_altered(plan):
    """The step's loss altered where the step produces it."""
    def step(state, batch):
        state, m = plan.step_fn(state, batch)
        return state, dict(m, loss=m["loss"] * 1.001)
    return dataclasses.replace(plan, step_fn=step)


def no_exchange(plan):
    """The round folds no cohort's mask into another's."""
    def rnd(state):
        a, _ = plan.round_fn(copy(state), jnp.array([1.0, 0.0]))
        b, m = plan.round_fn(state, jnp.array([0.0, 1.0]))
        sc = jax.tree_util.tree_map(
            lambda x, y: None if x is None else x.at[1:].set(y[1:]),
            a["scores"], b["scores"], is_leaf=_none)
        return dict(b, scores=sc), m
    return dataclasses.replace(plan, round_fn=rnd)


@pytest.mark.parametrize("family", ["dense"])
def test_sound_training_run_is_correct(monkeypatch, family):
    res = T.run_main(monkeypatch, T.cell(family, "fedtrain-r10"))
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["compiles"]["window"] == 0, res["compiles"]


@pytest.mark.parametrize("fault", [unchanged, half_batch, answer_altered,
                                   no_exchange],
                         ids=lambda f: f.__name__)
def test_broken_training_run_is_not_correct(monkeypatch, fault):
    res = T.run_main(monkeypatch, T.cell("dense", "fedtrain-r10"),
                     wrap=fault)
    assert not res["correct"], res["checks"]
