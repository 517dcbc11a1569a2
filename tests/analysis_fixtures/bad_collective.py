"""Fixture round-uplink bodies that VIOLATE the collective wire-purity
rules (`repro.analysis.collective_lint`).

Each builder returns a shard-mapped callable whose jaxpr contains
exactly the collective the named rule must flag.  `tests/
test_collective.py` traces each one on the debug pod mesh and asserts
the rule fires — a rule with no firing fixture is a dead gate.
"""
import jax
import jax.numpy as jnp

P = jax.sharding.PartitionSpec


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def f32_score_all_gather(mesh):
    """Ships the raw f32 score tensor across pods.

    Must fire ``collective-f32-weight``: a weight-shaped float operand
    crossing the uplink collective."""
    def body(scores):
        return jax.lax.all_gather(scores, "pod")
    return _shard_map(body, mesh, (P(),), P("pod"))


def u8_mask_all_gather(mesh):
    """Gathers the sampled mask as one byte per parameter (8x the
    packed wire size).

    Must fire ``collective-unpacked-mask``: an integer mask crossing a
    collective without bitpacking."""
    def body(scores):
        mask = (scores > 0).astype(jnp.uint8)
        return jax.lax.all_gather(mask, "pod")
    return _shard_map(body, mesh, (P(),), P("pod"))


def bf16_mask_pmean(mesh):
    """Averages bf16 mask indicators across pods — the pre-bitpack
    baseline aggregation (16 bits per parameter on the wire).

    Must fire ``collective-f32-weight``: a non-sidecar float operand
    in a cross-pod psum."""
    def body(scores):
        mask = (scores > 0).astype(jnp.bfloat16)
        return jax.lax.pmean(mask, "pod")
    return _shard_map(body, mesh, (P(),), P())


ALL = {
    "collective-f32-weight": f32_score_all_gather,
    "collective-unpacked-mask": u8_mask_all_gather,
    "collective-f32-weight/pmean": bf16_mask_pmean,
}
