"""Lowered-step tests on a tiny debug mesh (1 device): the production
train/round/serve steps must run end-to-end on CPU with real values."""
import dataclasses
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import masking
from repro.models import build_model
from repro.launch import steps as steplib
from repro.launch import sharding as shd
from repro.launch import mesh as meshlib


SPEC = masking.MaskSpec()


def _mini(name="internlm2-1.8b"):
    cfg = get_config(name, smoke=True)
    api = build_model(cfg)
    return cfg, api


def test_train_step_runs_and_reduces_loss():
    cfg, api = _mini()
    key = jax.random.PRNGKey(0)
    state = steplib.init_fed_state(key, api, SPEC, C=2)
    scfg = steplib.StepConfig(lam=0.1, lr=1.0)
    step = jax.jit(steplib.make_train_step(api, scfg))
    # learnable data: deterministic repeating sequence (uniform-random
    # tokens are at the CE optimum already). Score-SGD on a tiny signed-
    # constant net learns slowly; assert a clear but modest improvement.
    seq = (jnp.arange(16) * 3) % 7
    batch = {"tokens": jnp.broadcast_to(seq, (2, 2, 16)).astype(
        jnp.int32)}
    losses = []
    for i in range(30):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert min(losses[-5:]) < losses[0] - 0.05, losses
    assert int(state["step"]) == 30


def test_round_step_no_mesh_packed_equals_unpacked_theta():
    cfg, api = _mini()
    key = jax.random.PRNGKey(1)
    state = steplib.init_fed_state(key, api, SPEC, C=2)
    # make scores asymmetric so theta is non-trivial
    state["scores"] = jax.tree_util.tree_map(
        lambda s: None if s is None else s
        + jax.random.normal(key, s.shape),
        state["scores"], is_leaf=lambda x: x is None)
    rp = steplib.make_round_step(api, steplib.StepConfig(
        packed_masks=True))
    ru = steplib.make_round_step(api, steplib.StepConfig(
        packed_masks=False))
    sp_, mp_ = jax.jit(rp)(state)
    su_, mu_ = jax.jit(ru)(state)
    # identical mask sampling -> identical theta (packed path is lossless)
    for (pa, a), (pb, b) in zip(
            masking.leaves_with_paths(sp_["scores"]),
            masking.leaves_with_paths(su_["scores"])):
        if a is None:
            continue
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-2)  # bf16 psum rounding
    assert 0.0 <= float(mp_["bpp"]) <= 1.0


def test_round_step_resets_cohort_scores_identically():
    cfg, api = _mini()
    key = jax.random.PRNGKey(2)
    state = steplib.init_fed_state(key, api, SPEC, C=3)
    state["scores"] = jax.tree_util.tree_map(
        lambda s: None if s is None else s + jax.random.normal(
            jax.random.PRNGKey(9), s.shape),
        state["scores"], is_leaf=lambda x: x is None)
    rs = jax.jit(steplib.make_round_step(api, steplib.StepConfig()))
    s2, _ = rs(state)
    for _, leaf in masking.leaves_with_paths(s2["scores"]):
        if leaf is None:
            continue
        a = np.asarray(leaf)
        assert np.allclose(a[0], a[1]) and np.allclose(a[0], a[2])


def test_round_step_deterministic_and_step_dependent():
    """The counter-based mask streams are a pure function of
    (step, shard, leaf, cohort): re-running the round on the same state
    gives bit-identical theta; a later step samples different masks."""
    cfg, api = _mini()
    key = jax.random.PRNGKey(6)
    state = steplib.init_fed_state(key, api, SPEC, C=2)
    state["scores"] = jax.tree_util.tree_map(
        lambda s: None if s is None else s
        + jax.random.normal(key, s.shape),
        state["scores"], is_leaf=lambda x: x is None)
    rs = jax.jit(steplib.make_round_step(api, steplib.StepConfig()))
    s1, m1 = rs(state)
    s2, m2 = rs(state)
    for (_, a), (_, b) in zip(masking.leaves_with_paths(s1["scores"]),
                              masking.leaves_with_paths(s2["scores"])):
        if a is None:
            continue
        assert np.array_equal(np.asarray(a), np.asarray(b))
    later = dict(state, step=state["step"] + 5)
    s3, m3 = rs(later)
    diff = any(
        a is not None and not np.array_equal(np.asarray(a),
                                             np.asarray(b))
        for (_, a), (_, b) in zip(
            masking.leaves_with_paths(s1["scores"]),
            masking.leaves_with_paths(s3["scores"])))
    assert diff


def test_sample_and_pack_rows_kernel_matches_reference():
    """aggregation.sample_and_pack_rows: the fused-kernel and pure-jnp
    dispatches produce identical packed words (the round_step transport
    invariant)."""
    from repro.core import aggregation
    key = jax.random.PRNGKey(8)
    flat = jax.random.normal(key, (3, 500), jnp.float32)
    seeds = jnp.asarray([1, 2, 3], jnp.uint32)
    wk = aggregation.sample_and_pack_rows(flat, seeds, use_kernel=True)
    wr = aggregation.sample_and_pack_rows(flat, seeds, use_kernel=False)
    assert wk.shape == (3, (500 + 31) // 32)
    assert bool(jnp.all(wk == wr))
    # rows draw from distinct streams
    assert not bool(jnp.all(wk[0] == wk[1]))


def _scores_equal(a, b):
    return all(
        x is None or np.array_equal(np.asarray(x), np.asarray(y))
        for (_, x), (_, y) in zip(masking.leaves_with_paths(a),
                                  masking.leaves_with_paths(b)))


def test_train_step_seed_plumbed_and_deterministic():
    """StepConfig.seed feeds every mask stream (no hard-coded PRNGKey):
    equal seeds reproduce the step bit-for-bit, different seeds sample
    different masks and so take a different step."""
    cfg, api = _mini()
    key = jax.random.PRNGKey(11)
    state = steplib.init_fed_state(key, api, SPEC, C=2)
    batch = {"tokens": jnp.broadcast_to((jnp.arange(16) * 3) % 7,
                                        (2, 2, 16)).astype(jnp.int32)}
    s_a, _ = jax.jit(steplib.make_train_step(
        api, steplib.StepConfig(seed=1)))(state, batch)
    s_a2, _ = jax.jit(steplib.make_train_step(
        api, steplib.StepConfig(seed=1)))(state, batch)
    s_b, _ = jax.jit(steplib.make_train_step(
        api, steplib.StepConfig(seed=2)))(state, batch)
    assert _scores_equal(s_a["scores"], s_a2["scores"])
    assert not _scores_equal(s_a["scores"], s_b["scores"])


def test_train_step_eff_path_matches_fused(monkeypatch):
    """REPRO_EFF_PATH=1 (materialized effective params) draws the SAME
    hash-stream masks as the fused kernels: identical loss, score
    updates equal to bf16 rounding."""
    cfg, api = _mini()
    key = jax.random.PRNGKey(12)
    state = steplib.init_fed_state(key, api, SPEC, C=2)
    scfg = steplib.StepConfig(lam=0.1, lr=0.5)
    batch = {"tokens": jnp.broadcast_to((jnp.arange(16) * 5) % 11,
                                        (2, 2, 16)).astype(jnp.int32)}
    s_f, m_f = jax.jit(steplib.make_train_step(api, scfg))(state, batch)
    monkeypatch.setenv("REPRO_EFF_PATH", "1")
    s_e, m_e = jax.jit(steplib.make_train_step(api, scfg))(state, batch)
    assert float(m_f["loss"]) == float(m_e["loss"])
    for (p, a), (_, b) in zip(masking.leaves_with_paths(s_f["scores"]),
                              masking.leaves_with_paths(s_e["scores"])):
        if a is None:
            continue
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-2, err_msg=p)


def test_round_step_threshold_mode():
    """mask_mode="threshold" (the fedmask plan): the uplink packs the
    deterministic mask, so with shared scores theta IS the thresholded
    mask — and re-running is bit-identical (no sampling)."""
    cfg, api = _mini()
    key = jax.random.PRNGKey(13)
    state = steplib.init_fed_state(key, api, SPEC, C=2)
    state["scores"] = jax.tree_util.tree_map(
        lambda s: None if s is None else s
        + jax.random.normal(key, s.shape),
        state["scores"], is_leaf=lambda x: x is None)
    rs = jax.jit(steplib.make_round_step(api, steplib.StepConfig(
        mask_mode="threshold", tau=0.5)))
    s1, m1 = rs(state)
    s2, _ = rs(state)
    assert _scores_equal(s1["scores"], s2["scores"])
    # theta = mean over cohorts of the deterministic thresholded masks
    # (no sampling); new scores are logit(theta), clipped at 1e-6
    for (p, leaf), (_, s0) in zip(
            masking.leaves_with_paths(s1["scores"]),
            masking.leaves_with_paths(state["scores"])):
        if leaf is None:
            continue
        theta = jax.nn.sigmoid(np.asarray(leaf, np.float32))
        want = np.mean(
            (jax.nn.sigmoid(np.asarray(s0, np.float32)) > 0.5)
            .astype(np.float32), axis=0)
        assert np.allclose(theta, want, atol=2e-5), p
    assert 0.0 <= float(m1["bpp"]) <= 1.0


def test_fedmask_launch_plan_runs():
    """--algo fedmask resolves to a launch plan whose train step
    differentiates through the fused threshold kernels."""
    from repro import api as fedapi
    from repro.launch import plans  # noqa: F401 (registers)
    cfg, api = _mini()
    plan = fedapi.get_launch_plan("fedmask")(
        api, steplib.StepConfig(lr=0.5), key=jax.random.PRNGKey(0),
        cohorts=2)
    toks = jnp.arange(512, dtype=jnp.int32) % 7
    batch = plan.make_batch(jax.random.PRNGKey(1), toks, 2, 16)
    state, m = plan.step_fn(plan.state, batch)
    assert np.isfinite(float(m["loss"]))
    state, rm = plan.round_fn(state)
    assert 0.0 <= float(rm["bpp"]) <= 1.0


@pytest.mark.parametrize("family", ["dense", "moe", "hybrid"])
def test_train_step_jaxpr_zero_weight_temporaries(family):
    """Acceptance invariant (tier-1 twin of the benchmark gate): the
    jaxpr of a jitted make_train_step for an MXU-aligned config of
    each family — dense transformer, deepseek-style MoE (stacked
    (E, K, N) expert leaves through the GROUPED kernel), and
    recurrentgemma-style hybrid ((W, C) conv leaves through the fused
    conv kernel) — defines ZERO weight-shaped f32 values outside
    pallas_call, forward AND backward, for every masked block shape,
    while the materialized REPRO_EFF_PATH reference defines strictly
    more at every leaf shape.  Twin and bench import the SAME
    traversal from repro.analysis (no duplicated walker)."""
    from repro.analysis import model_check
    cfg, S = model_check.MODEL_CHECK_CFGS[family]
    model = model_check.model_step_weight_defs(cfg, S=S)
    assert model["block_shapes"], "no masked blocks found"
    for sh, cts in model["block_shapes"].items():
        assert cts["fused"] == 0, (family, sh, cts)
    for sh, cts in model["leaf_shapes"].items():
        assert cts["eff"] > cts["fused"], (family, sh, cts)


def test_serve_step_runs():
    cfg, api = _mini("gemma3-4b")
    key = jax.random.PRNGKey(3)
    params = api.init_params(key)
    cache = api.init_cache(2, 32)
    serve = jax.jit(steplib.make_serve_step(api))
    logits, cache2 = serve(params, cache, jnp.zeros((2,), jnp.int32),
                           jnp.asarray(5, jnp.int32))
    assert logits.shape == (2, cfg.vocab)
    assert not bool(jnp.any(jnp.isnan(logits)))


def test_fedavg_step_runs():
    cfg, api = _mini()
    key = jax.random.PRNGKey(4)
    state = steplib.init_fedavg_state(key, api)
    scfg = steplib.StepConfig(lr=0.05)
    step = jax.jit(steplib.make_fedavg_step(api, scfg))
    batch = {"tokens": jax.random.randint(key, (2, 16), 0, cfg.vocab)}
    l0 = None
    for i in range(5):
        state, m = step(state, batch)
        if l0 is None:
            l0 = float(m["loss"])
    assert float(m["loss"]) < l0


def test_sharding_rules_divisibility():
    """Every assigned arch x both meshes: every param leaf gets a spec
    whose sharded dims divide evenly (the dry-run precondition)."""
    import os
    from repro.configs import ARCH_NAMES
    mesh = meshlib.make_debug_mesh(1, 1)
    for name in ARCH_NAMES:
        cfg = get_config(name, smoke=True)
        api = build_model(cfg)
        shapes = jax.eval_shape(api.init_params, jax.random.PRNGKey(0))
        sh = shd.tree_param_shardings(shapes, mesh)
        leaves = jax.tree_util.tree_leaves(
            sh, is_leaf=lambda x: x is None)
        assert leaves


def test_train_step_adam_scores():
    """Adam-on-scores (the FedPM reference optimizer) in the production
    step: runs, reduces loss, round resets both moments."""
    cfg, api = _mini()
    key = jax.random.PRNGKey(7)
    state = steplib.init_fed_state(key, api, SPEC, C=2,
                                   optimizer="adam")
    assert "opt_v" in state
    scfg = steplib.StepConfig(lam=0.5, lr=0.05, optimizer="adam")
    step = jax.jit(steplib.make_train_step(api, scfg))
    rnd = jax.jit(steplib.make_round_step(api, scfg))
    seq = (jnp.arange(16) * 5) % 11
    batch = {"tokens": jnp.broadcast_to(seq, (2, 2, 16)).astype(
        jnp.int32)}
    losses = []
    for i in range(12):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    state, rm = rnd(state)
    assert 0.0 <= float(rm["bpp"]) <= 1.0
    for v in jax.tree_util.tree_leaves(state["opt_v"]):
        assert float(jnp.max(jnp.abs(v))) == 0.0  # reset at round


# ---------------------------------------------------------------------------
# jax.shard_map over the pod axis (the round step's collective home)
# ---------------------------------------------------------------------------


def test_shard_map_pod_psum_executes():
    """The round step maps its body with jax.shard_map(check_vma=False);
    a psum over the pod axis through it executes on the forced mesh."""
    mesh = meshlib.make_debug_pod_mesh()
    P = jax.sharding.PartitionSpec
    fn = jax.shard_map(lambda x: jax.lax.psum(x, "pod"), mesh=mesh,
                       in_specs=(P(),), out_specs=P(), check_vma=False)
    x = jnp.arange(4.0)
    np.testing.assert_allclose(
        jax.jit(fn)(x), x * mesh.shape["pod"])


# ---------------------------------------------------------------------------
# the entry points: depth cut, returned metrics, compile cache
# ---------------------------------------------------------------------------


def test_get_config_layers_cuts_depth_only():
    from repro.configs import get_config
    full = get_config("internlm2-1.8b")
    cut = get_config("internlm2-1.8b", layers=4)
    assert cut.n_layers == 4 and full.n_layers == 24
    assert dataclasses.replace(cut, n_layers=24) == full
    assert get_config("internlm2-1.8b", layers=0) is full


def test_train_main_returns_metrics():
    """`train.main` returns its last metrics and the compiled step it
    ran (whose state it donated), so callers need not parse stdout."""
    from repro.launch import train
    out = train.main(["--smoke", "--algo", "fedpm_reg", "--steps", "2",
                      "--round-every", "1", "--cohorts", "2",
                      "--seq", "16"])
    assert np.isfinite(out["loss"])
    assert out["rounds"] == 2
    assert 0.0 < out["uplink_bpp"] <= 1.0
    assert out["bits_measured"] > 0
    assert out["compile_s"] > 0 and out["step_s"] >= 0
    assert out["compiled_step"].memory_analysis() is not None


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code;
    otherwise the cache sits at the checkout's fixed .jax_cache."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        if env_dir:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            jax.config.update("jax_compilation_cache_dir", None)
            assert compile_cache.enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir is None
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            got = compile_cache.enable_compile_cache()
            root = os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))
            assert got == os.path.join(root, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


_POD_PLAN = r"""
import os
import pathlib
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp, numpy as np
from repro import api as fedapi
from repro.configs import get_config
from repro.launch import plans, steps as steplib
from repro.launch import mesh as meshlib
from repro.models import build_model

api = build_model(get_config("internlm2-1.8b", smoke=True))
scfg = steplib.StepConfig(lr=0.3, downlink_bits=8, seed=17)
key = jax.random.PRNGKey(4)
plan = fedapi.get_launch_plan("fedpm_reg")
one = plan(api, scfg, key=key, cohorts=4, codec="arithmetic")
pod = plan(api, scfg, key=key, cohorts=4, codec="arithmetic",
           mesh=meshlib.make_debug_pod_mesh(4, 1, 1))
toks = jax.random.randint(key, (4096,), 0, api.cfg.vocab)
batch = one.make_batch(key, toks, 2, 16)
outs = []
for p in (one, pod):
    st, m = p.step_fn(p.state, batch)
    st, rm = p.round_fn(st)
    outs.append((st, float(m["loss"]), float(rm["bits_measured"])))
(a, la, ba), (b, lb, bb) = outs
assert pod.state is not None and pod.shardings is not None
assert abs(la - lb) < 1e-5 * abs(la), (la, lb)
assert ba == bb, (ba, bb)
for x, y in zip(jax.tree_util.tree_leaves(a["scores"]),
                jax.tree_util.tree_leaves(b["scores"])):
    np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)
print("POD_OK", la, ba)
"""


def test_pod_mesh_plan_matches_one_device_plan():
    """The mask plan on a (4, 1, 1) pod mesh (state placed by
    fed_state_shardings, train step and round under shard_map, one
    cohort per device) trains and rounds four cohorts as the one-device
    plan does: the same loss, the same measured uplink bits, the same
    new scores — each cohort samples its masks from the stream it
    samples without the mesh."""
    import subprocess
    import sys
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env = {"PYTHONPATH": src, "PATH": "/usr/bin:/bin:/usr/local/bin",
           "HOME": os.environ.get("HOME", "/tmp")}
    out = subprocess.run([sys.executable, "-c", _POD_PLAN],
                         capture_output=True, text=True, timeout=600,
                         env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "POD_OK" in out.stdout
