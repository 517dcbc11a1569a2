"""Pod-scale launch plans: registry names -> lowered production steps.

The host-sim registry (`repro.api`) resolves an algorithm name to a
`FedAlgorithm`; at pod scale the same name resolves — through
`api.get_launch_plan` — to a `LaunchPlan` bundling the lowered state,
train step, round step, and batch layout for `repro.launch.train`.
Importing this module populates the launch side of the registry, so the
launcher has no per-algorithm if/else: adding an algorithm here makes
`--algo <name>` work.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro import api
from repro.core import masking
from repro.launch import steps as steplib


@dataclasses.dataclass
class LaunchPlan:
    """Everything the launcher needs, resolved from one registry name."""
    name: str
    state: Any
    step_fn: Callable                 # (state, batch) -> (state, metrics)
    round_fn: Optional[Callable]      # (state) -> (state, metrics) | None
    #                                   both donate `state`
    make_batch: Callable              # (key, tokens, batch, seq) -> batch
    shardings: Any = None             # the state's on a mesh, else None


def _cohort_batch(cohorts: int):
    def make_batch(key, toks, batch, seq):
        idx = jax.random.randint(key, (cohorts, batch), 0,
                                 toks.shape[0] - seq - 1)
        return {"tokens": jax.vmap(jax.vmap(
            lambda i: jax.lax.dynamic_slice(toks, (i,), (seq,))))(idx)}
    return make_batch


def _flat_batch(key, toks, batch, seq):
    idx = jax.random.randint(key, (batch,), 0, toks.shape[0] - seq - 1)
    return {"tokens": jax.vmap(
        lambda i: jax.lax.dynamic_slice(toks, (i,), (seq,)))(idx)}


def _mask_plan(name, *, force_lam=None, mask_mode=None):
    """Mask-training plans (fedpm_reg / fedpm / fedmask): cohort-axis
    state, fused masked-execution train step, bitpacked round.  `codec`
    picks the wire codec the round step meters uplinks with (`--codec`
    in `repro.launch.train`); `mask_mode="threshold"` is the FedMask
    variant — the forward differentiates through the fused threshold
    kernels and the uplink packs the deterministic mask.

    With a pod `mesh` ("pod", "data", "model"; data and model 1), the
    state is placed by `steps.fed_state_shardings` (cohorts on "pod",
    each chip holding its cohorts' whole leaves), and both steps run
    under shard_map: the round all-gathers the packed words over
    "pod"."""
    def plan(model_api, scfg: steplib.StepConfig, *, key, cohorts,
             spec=None, optimizer="momentum", codec=None,
             mesh=None) -> LaunchPlan:
        if force_lam is not None:
            scfg = dataclasses.replace(scfg, lam=force_lam)
        if mask_mode is not None:
            scfg = dataclasses.replace(scfg, mask_mode=mask_mode)
        spec = masking.MaskSpec() if spec is None else spec
        state = steplib.init_fed_state(key, model_api, spec, C=cohorts,
                                       optimizer=optimizer)
        sh = None
        if mesh is not None:
            sh = steplib.fed_state_shardings(state, mesh)
            state = jax.device_put(state, sh)
        # both steps donate the state: the old and the new one are
        # never live together, which is what lets a full-width model
        # fit one chip
        out = {} if sh is None else {"out_shardings": (sh, None)}
        return LaunchPlan(
            name=name, state=state,
            step_fn=jax.jit(steplib.make_train_step(
                model_api, scfg, mesh=mesh, state_sh=sh),
                donate_argnums=0, **out),
            round_fn=jax.jit(steplib.make_round_step(
                model_api, scfg, mesh=mesh, state_sh=sh, codec=codec),
                donate_argnums=0, **out),
            make_batch=_cohort_batch(cohorts), shardings=sh)
    return plan


def _fedavg_plan(model_api, scfg: steplib.StepConfig, *, key, cohorts,
                 spec=None, optimizer="momentum",
                 codec=None, mesh=None) -> LaunchPlan:
    if mesh is not None:
        raise ValueError("fedavg has no pod-mesh plan")
    state = steplib.init_fedavg_state(key, model_api)
    return LaunchPlan(
        name="fedavg", state=state,
        step_fn=jax.jit(steplib.make_fedavg_step(model_api, scfg),
                        donate_argnums=0),
        round_fn=None, make_batch=_flat_batch)


# per-algorithm StepConfig overrides for the mask-round algorithms —
# the single source both the launch registrations below and the
# analysis engines (repro.analysis.comm_model / collective_lint) build
# their round-step configs from, so the linted jaxpr is the launched
# jaxpr
MASK_ALGOS = {
    "fedpm_reg": {},
    "fedpm": {"lam": 0.0},
    "fedmask": {"lam": 0.0, "mask_mode": "threshold"},
}

for _name, _kw in MASK_ALGOS.items():
    api.register_launch(_name, _mask_plan(
        _name, force_lam=_kw.get("lam"),
        mask_mode=_kw.get("mask_mode")))
api.register_launch("fedavg", _fedavg_plan)
