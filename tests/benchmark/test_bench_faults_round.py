"""The round cell's run comes out not correct when the timed round step
is broken underneath, and correct when it is not: the whole of `run.py`
past its look for a chip, on a tiny cell on the CPU."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

import bench_tiny as T

copy = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))


def _none(x):
    return x is None


def _with_round(plan, rnd):
    return dataclasses.replace(plan, round_fn=rnd)


def unchanged(plan):
    """A round that returns its state unchanged."""
    def rnd(state):
        _, m = plan.round_fn(copy(state))
        return state, m
    return _with_round(plan, rnd)


def half_batch(plan):
    """Half of the cohorts left out, theta the mean over the rest."""
    return _with_round(plan, lambda s: plan.round_fn(
        s, jnp.array([1.0, 0.0])))


def no_exchange(plan):
    """No cohort's mask reaches another: each keeps its own."""
    def rnd(state):
        a, _ = plan.round_fn(copy(state), jnp.array([1.0, 0.0]))
        b, m = plan.round_fn(state, jnp.array([0.0, 1.0]))
        sc = jax.tree_util.tree_map(
            lambda x, y: None if x is None else x.at[1:].set(y[1:]),
            a["scores"], b["scores"], is_leaf=_none)
        return dict(b, scores=sc), m
    return _with_round(plan, rnd)


def answer_altered(plan):
    """One parameter's new score altered where the round produces it."""
    def rnd(state):
        state, m = plan.round_fn(state)
        leaves, tdef = jax.tree_util.tree_flatten(state["scores"],
                                                  is_leaf=_none)
        i = next(k for k, x in enumerate(leaves) if x is not None)
        x = leaves[i]
        leaves[i] = x.at[(0,) * x.ndim].add(1.0)
        return dict(state, scores=jax.tree_util.tree_unflatten(
            tdef, leaves)), m
    return _with_round(plan, rnd)


def test_sound_round_run_is_correct(monkeypatch):
    res = T.run_main(monkeypatch, T.cell("dense", "round-only"))
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == {"round_mismatch", "round_bits_gap"}
    assert res["compiles"]["window"] == 0, res["compiles"]


@pytest.mark.parametrize("fault", [unchanged, half_batch, no_exchange,
                                   answer_altered],
                         ids=lambda f: f.__name__)
def test_broken_round_run_is_not_correct(monkeypatch, fault):
    res = T.run_main(monkeypatch, T.cell("dense", "round-only"),
                     wrap=fault)
    assert not res["correct"], res["checks"]
