"""Closed loop of the cross-pod mask round: the program's round step on
a (pod, data, model) mesh, one cohort per chip, back to back on one
federated state, the next issued as the last's metrics reach the host.
Latency of one round is from its dispatch to its metrics on the host.

Set-up: the program's launch plan on the traffic's mesh (built under
`jax.eval_shape`, as `harness.launch_plan` builds the one-chip plans),
the state made from the seed by the harness's leaf maker and laid out
as `harness.state_maker` lays it out, but made directly in the plan's
shardings (the program's `steps.fed_state_shardings`): each chip makes
its own cohorts' rows, where the one-chip maker would hold all four
cohorts' state on the first chip at once; and one round on it (the
round's check, and its warm-up).  The window continues from the state
that round produced.
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.chip import checks
from benchmarks.chip import harness as H
from benchmarks.chip.loops import rounds

MESH_AXES = ("pod", "data", "model")


def pod_plan(cell, mesh):
    """The program's launch plan for the cell's algorithm on `mesh`, and
    the shapes of its state."""
    from repro import api as fedapi
    from repro.launch import plans  # noqa: F401  (registers the plans)
    api = H.program_model(cell)
    scfg = H.step_config(cell)
    t = cell.traffic
    held = {}

    def build(key):
        held["plan"] = fedapi.get_launch_plan(t["algo"])(
            api, scfg, key=key, cohorts=t["cohorts"],
            optimizer=t["optimizer"], codec=t["codec"], mesh=mesh)
        return held["plan"].state

    shapes = jax.eval_shape(build, jax.random.PRNGKey(0))
    return held["plan"], shapes


def place_state(cell, shapes, shardings, key):
    """`harness.state_maker`'s state for `key`, made in `shardings`."""
    import jax.numpy as jnp
    H.state_maker(cell, shapes)      # refuses a tree that disagrees
    specs = cell.family.specs(cell.config)
    C = cell.traffic["cohorts"]
    wdef = jax.tree_util.tree_structure(shapes["weights"],
                                        is_leaf=lambda x: x is None)
    order = [p for p, _ in H.program_paths(shapes["weights"])]

    def make(vals, scores):
        rep = lambda a: jnp.broadcast_to(a[None], (C,) + a.shape)
        tree = lambda f: jax.tree_util.tree_unflatten(
            wdef, [f(p) for p in order])
        masked = lambda p: specs[p][2] == "masked"
        sc = tree(lambda p: rep(scores[p]) if masked(p) else None)
        return {"weights": tree(lambda p: vals[p] if masked(p) else None),
                "scores": sc,
                "floats": tree(lambda p: None if masked(p)
                               else rep(vals[p])),
                "opt_m": jax.tree_util.tree_map(
                    lambda a: None if a is None else jnp.zeros_like(a),
                    sc, is_leaf=lambda x: x is None),
                "step": jnp.zeros((), jnp.int32)}

    return jax.jit(make, out_shardings=shardings)(*cell.leaf_maker(key))


class Loop(rounds.Loop):
    def setup(self):
        lap = H.Laps()
        # automatic axes: the checks read the sharded state as GSPMD
        # arrays (cohort 0's row, rows against it)
        mesh = jax.make_mesh(tuple(self.cell.traffic["mesh"]), MESH_AXES,
                             (jax.sharding.AxisType.Auto,) * 3)
        self.plan, shapes = pod_plan(self.cell, mesh)
        self.state = place_state(self.cell, shapes, self.plan.shardings,
                                 H.keys(self.seed)["params"])
        jax.block_until_ready(self.state)
        lap("plan_and_state")
        self.state, rm = self.plan.round_fn(self.state)
        self.round_metrics = {k: float(v) for k, v in rm.items()}
        self.round_levels, self.round_rows = checks.capture_round(
            self.cell, self.state["scores"])
        jax.block_until_ready(self.state)
        lap("round_checked")
        self.setup_laps = lap.laps
        if not all(np.isfinite(list(self.round_metrics.values()))):
            raise H.BenchError(f"round metrics {self.round_metrics}")
