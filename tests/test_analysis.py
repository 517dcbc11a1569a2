"""Tier-1 tests for the repro.analysis static-analysis subsystem:
every rule is demonstrated by a committed failing fixture (or an
in-test corrupted structure) AND shown clean on the repo at HEAD."""
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import jaxpr_lint, source_lint, stream_cover
from repro.core import masking
from repro.kernels import ops, ref

REPO = pathlib.Path(__file__).resolve().parents[1]
FIX = pathlib.Path(__file__).parent / "analysis_fixtures"
SRC = REPO / "src" / "repro"


# ---------------------------------------------------------------------------
# jaxpr engine
# ---------------------------------------------------------------------------


def _operands(M=128, K=128, N=128):
    x = jnp.zeros((M, K), jnp.bfloat16)
    w = jnp.zeros((K, N), jnp.bfloat16)
    s = jnp.zeros((K, N), jnp.float32)
    g = jnp.zeros((M, N), jnp.bfloat16)
    return x, w, s, g


def test_weight_f32_rule_fires_on_naive_not_on_fused():
    """The promoted counter: the jnp oracle materializes weight-shaped
    f32 temporaries, the fused kernel path defines none — and the
    compat wrapper agrees with the rule-based walker."""
    x, w, s, _ = _operands()
    K, N = w.shape
    naive_jx = jax.make_jaxpr(
        lambda x, w, s: ref.masked_matmul(x, w, s, 0))(x, w, s)
    fused_jx = jax.make_jaxpr(
        lambda x, w, s: ops.masked_dense(x, w, s, 0))(x, w, s)
    rule = jaxpr_lint.weight_f32_temporaries((K, N))
    naive_f = jaxpr_lint.lint_jaxpr(naive_jx, [rule])
    assert naive_f and all(f.rule == "weight-f32-temporary"
                           for f in naive_f)
    assert jaxpr_lint.lint_jaxpr(fused_jx, [rule]) == []
    # the compat counter is the same rule through the same walker
    assert jaxpr_lint.count_weight_f32_defs_jaxpr(
        naive_jx, (K, N)) == len(naive_f)
    assert jaxpr_lint.count_weight_f32_defs_jaxpr(
        fused_jx, (K, N)) == 0


def test_mask_materialization_rule():
    """materialize_leaf defines a weight-shaped bool mask; the fused
    fwd+bwd never does."""
    x, w, s, g = _operands()
    K, N = w.shape
    leaf = masking.MaskedLeaf.build(w, s, 7)
    rule = jaxpr_lint.mask_materialization((K, N))
    mat_jx = jax.make_jaxpr(masking.materialize_leaf)(leaf)
    found = jaxpr_lint.lint_jaxpr(mat_jx, [rule])
    assert found and all(f.rule == "mask-materialization"
                         for f in found)

    def fused(x, w, s, g):
        y, vjp = jax.vjp(lambda x_, s_: ops.masked_dense(x_, w, s_, 0),
                         x, s)
        return y, vjp(g)

    fused_jx = jax.make_jaxpr(fused)(x, w, s, g)
    assert jaxpr_lint.lint_jaxpr(fused_jx, [rule]) == []


def test_dtype_promotion_rule_bf16_upcast():
    x, w, _, _ = _operands()
    K, N = w.shape
    rule = jaxpr_lint.DtypePromotionRule([(K, N)])
    up_jx = jax.make_jaxpr(
        lambda w: w.astype(jnp.float32) * 2.0)(w)
    found = jaxpr_lint.lint_jaxpr(up_jx, [rule])
    assert any("bf16->f32" in f.detail for f in found)
    # a downcast (f32 -> bf16) at the same shape is fine
    down_jx = jax.make_jaxpr(
        lambda s: s.astype(jnp.bfloat16))(jnp.zeros((K, N), jnp.float32))
    assert jaxpr_lint.lint_jaxpr(down_jx, [rule]) == []


def test_dtype_promotion_rule_f64():
    with jax.enable_x64(True):
        jx = jax.make_jaxpr(
            lambda x: x.astype(jnp.float64) + 1.0)(jnp.ones((4,)))
    found = jaxpr_lint.lint_jaxpr(
        jx, [jaxpr_lint.DtypePromotionRule()])
    assert any("f64" in f.detail for f in found)


def test_donation_alias_rule():
    inner = jax.jit(lambda x: x * 2.0, donate_argnums=0)

    def bad(x):
        return inner(x) + x          # x read AFTER its buffer is donated

    def good(x):
        return inner(x) + 1.0

    rule = jaxpr_lint.DonationAliasRule()
    x = jnp.ones((8, 8))
    bad_f = jaxpr_lint.lint_jaxpr(jax.make_jaxpr(bad)(x), [rule])
    assert any(f.rule == "donation-alias" for f in bad_f)
    assert jaxpr_lint.lint_jaxpr(jax.make_jaxpr(good)(x), [rule]) == []


def test_walker_descends_into_scan():
    """Leaf defs inside lax.scan bodies are visited (the walker must
    not stop at the call wrapper)."""
    def body(c, _):
        return c, (c.astype(jnp.float32) ** 2)

    w = jnp.zeros((128, 128), jnp.bfloat16)
    jx = jax.make_jaxpr(
        lambda w: jax.lax.scan(body, w, jnp.arange(3)))(w)
    found = jaxpr_lint.lint_jaxpr(
        jx, [jaxpr_lint.weight_f32_temporaries((128, 128))])
    assert found


# ---------------------------------------------------------------------------
# stream engine
# ---------------------------------------------------------------------------


def _sds(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


def test_stream_cover_clean_tree():
    tree = {"a": masking.MaskedLeaf.build(_sds(3, 4, 8), None, 5),
            "b": masking.MaskedLeaf.build(_sds(16, 8), None, 9),
            "c": None}
    ivs = stream_cover.collect_intervals(tree)
    assert len(ivs) == 4                     # 3 stacked blocks + 1
    assert stream_cover.check_intervals(ivs) == []


def test_stream_overlap_detected():
    leaf = masking.MaskedLeaf.build(_sds(3, 4, 8), None, 5)
    leaf.off = jnp.zeros_like(leaf.off)      # every block reads [0, 32)
    found = stream_cover.check_intervals(
        stream_cover.collect_intervals({"a": leaf}))
    assert any(f.rule == "stream-overlap" for f in found)


def test_stream_gap_detected():
    leaf = masking.MaskedLeaf.build(_sds(2, 4, 8), None, 5)
    leaf.off = leaf.off * jnp.uint32(2)      # hole between the blocks
    found = stream_cover.check_intervals(
        stream_cover.collect_intervals({"a": leaf}))
    assert any(f.rule == "stream-gap" for f in found)


def test_stream_seed_collision_across_leaves():
    tree = {"a": masking.MaskedLeaf.build(_sds(4, 8), None, 5),
            "b": masking.MaskedLeaf.build(_sds(4, 8), None, 5)}
    found = stream_cover.check_intervals(
        stream_cover.collect_intervals(tree))
    assert any(f.rule == "stream-overlap" and "seed" in f.detail
               for f in found)


def test_state_stream_report_flags_collision_sweep():
    """The (shard, cohort) sweep itself catches collisions: same
    (step, dev, cohort, run_seed) coordinates for every leaf index
    can't happen through mask_stream_seed, so corrupt the report's
    inputs instead — two devs that alias to one id."""
    from repro.analysis import model_check
    _, state, _ = model_check.model_step_setup(
        model_check.MODEL_CHECK_CFG, C=2, S=16)
    rep = stream_cover.state_stream_report(state, devs=(0, 0),
                                           cohorts=range(2))
    assert any(f.rule == "stream-overlap" for f in rep["findings"])
    clean = stream_cover.state_stream_report(state, devs=(0, 1),
                                             cohorts=range(2))
    assert clean["findings"] == []
    assert clean["n_streams"] == clean["n_leaves"] * 4


def test_stream_gate_multi_shard_grouped_moe():
    """Acceptance: the coverage gate over the deepseek-style MoE smoke
    config — grouped (E, K, N) expert leaves — swept across 8 shard
    ids x 2 cohorts (mask_stream_seed is pure; no devices needed)."""
    rep = stream_cover.arch_stream_report(
        "deepseek-v2-lite-16b", smoke=True, C=2, devs=range(8))
    assert rep["findings"] == []
    assert rep["n_leaves"] > 0
    assert rep["n_intervals"] > rep["n_leaves"]   # stacked/grouped
    assert rep["n_streams"] == rep["n_leaves"] * 8 * 2


_FORCED_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
from repro.analysis import stream_cover
from repro.configs import get_config
from repro.core import masking
from repro.launch import mesh as meshlib
from repro.launch import steps as steplib
from repro.models import build_model

cfg = get_config("deepseek-v2-lite-16b", smoke=True)
api = build_model(cfg)
mesh = meshlib.make_debug_mesh(4, 2)
assert len(jax.devices()) == 8, jax.devices()
n_dev = 1
for a in mesh.axis_names:
    n_dev *= mesh.shape[a]
state = jax.eval_shape(
    lambda k: steplib.init_fed_state(k, api, masking.MaskSpec(), C=2),
    jax.random.PRNGKey(0))
rep = stream_cover.state_stream_report(
    state, devs=range(n_dev), cohorts=range(2), run_seed=17)
assert rep["findings"] == [], [str(f) for f in rep["findings"][:3]]
assert rep["n_streams"] == rep["n_leaves"] * n_dev * 2
print("STREAM_OK", rep["n_leaves"], rep["n_intervals"],
      rep["n_streams"])
"""


def test_stream_gate_on_forced_multi_device_mesh():
    """Acceptance: the gate passes on a REAL forced 8-device mesh
    (xla_force_host_platform_device_count, the dryrun mechanism) with
    grouped MoE leaves, shard ids enumerated from the mesh axes."""
    env = {"PYTHONPATH": str(REPO / "src"),
           "PATH": "/usr/bin:/bin:/usr/local/bin",
           "HOME": "/tmp"}
    out = subprocess.run([sys.executable, "-c", _FORCED_MESH_SCRIPT],
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "STREAM_OK" in out.stdout


# ---------------------------------------------------------------------------
# source engine (AST rules): fixtures fire, HEAD is clean
# ---------------------------------------------------------------------------


def test_bare_prngkey_rule_fires_on_fixture():
    found = source_lint.check_bare_prngkey([FIX / "bad_prngkey.py"],
                                           allowlist=frozenset())
    assert any(f.rule == "bare-prngkey" and "PRNGKey(29)" in f.detail
               for f in found)


def test_bare_prngkey_clean_at_head():
    assert source_lint.check_bare_prngkey(
        source_lint.launch_files()) == []


def test_kernel_oracle_rules_fire_on_fixture():
    found = source_lint.check_kernel_oracles(
        FIX / "bad_kernels.py", FIX / "bad_ref.py", FIX / "bad_ops.py")
    rules = {f.rule for f in found}
    assert "missing-oracle" in rules
    assert "missing-ref-bwd-hatch" in rules


def test_kernel_oracles_clean_at_head():
    assert source_lint.check_kernel_oracles(
        SRC / "kernels" / "masked_matmul.py",
        SRC / "kernels" / "ref.py",
        SRC / "kernels" / "ops.py") == []


def test_knob_doc_rule_fires_on_fixture_and_clean_at_head():
    readme = REPO / "README.md"
    found = source_lint.check_knob_docs([FIX / "bad_knob.py"], readme)
    assert any("REPRO_BOGUS_KNOB" in f.detail for f in found)
    # the documented table really exists and the real tree is clean
    assert "REPRO_FORCE_INTERPRET" in source_lint.readme_knobs(readme)
    files = (sorted(SRC.rglob("*.py"))
             + sorted((REPO / "benchmarks").glob("*.py")))
    assert source_lint.check_knob_docs(files, readme) == []


def test_materialize_allowlist_rule():
    found = source_lint.check_materialize_allowlist(
        [FIX / "bad_materialize.py"])
    assert len(found) == 2                   # both sneaky calls
    assert all(f.rule == "materialize-allowlist" for f in found)
    assert source_lint.check_materialize_allowlist(
        sorted(SRC.rglob("*.py"))) == []


def test_source_lint_clean_at_head():
    assert source_lint.run_all(REPO) == []


# ---------------------------------------------------------------------------
# kernels/ops.py backend-cache reset (satellite regression)
# ---------------------------------------------------------------------------


def test_reset_backend_cache_unsticks_env_flip(monkeypatch,
                                               kernel_backend_reset):
    """The bug the satellite fixes: flipping REPRO_FORCE_INTERPRET
    mid-process was silently ignored by the lru_cache; the public
    reset makes the flip take effect."""
    monkeypatch.setattr(ops, "repro_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_FORCE_INTERPRET", raising=False)
    ops.reset_backend_cache()
    assert ops._use_interpret() is False
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    assert ops._use_interpret() is False     # stale: flip ignored
    ops.reset_backend_cache()
    assert ops._use_interpret() is True      # reset applies the flip
