"""Per-architecture smoke tests: reduced config, one forward/train step
on CPU, asserting shapes + no NaNs; decode matches forward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, ARCH_NAMES
from repro.core import masking
from repro.models import build_model
from repro.optim import optimizers as optlib


def _batch_for(cfg, key, B=2, S=16):
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    if cfg.family == "vlm":
        batch["vis_embeds"] = 0.1 * jax.random.normal(
            key, (B, 4, cfg.d_model)).astype(jnp.bfloat16)
        batch["tokens"] = batch["tokens"][:, : S - 4]
    if cfg.family == "encdec":
        batch["frames"] = 0.1 * jax.random.normal(
            key, (B, cfg.enc_seq, cfg.d_model)).astype(jnp.bfloat16)
    return batch


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_smoke_forward_shapes_no_nan(name):
    cfg = get_config(name, smoke=True)
    api = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = api.init_params(key)
    batch = _batch_for(cfg, key)
    logits, aux = api.forward(params, batch)
    B = batch["tokens"].shape[0]
    assert logits.shape[0] == B and logits.shape[-1] == cfg.vocab
    assert not bool(jnp.any(jnp.isnan(logits)))
    loss = api.loss((logits, aux), batch)
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_smoke_one_train_step_reduces_grad(name):
    """One float-SGD step on the smoke config must produce finite grads
    and change the loss."""
    cfg = get_config(name, smoke=True)
    api = build_model(cfg)
    key = jax.random.PRNGKey(1)
    params = api.init_params(key)
    batch = _batch_for(cfg, key)

    def loss_fn(p):
        return api.loss(api.forward(p, batch), batch)

    l0, grads = jax.value_and_grad(loss_fn)(params)
    flat = [g for g in jax.tree_util.tree_leaves(grads)]
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in flat)
    params2 = jax.tree_util.tree_map(
        lambda p, g: (p - 0.3 * g.astype(p.dtype)).astype(p.dtype),
        params, grads)
    l1 = loss_fn(params2)
    assert np.isfinite(float(l1)) and float(l1) != float(l0)


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_smoke_masked_train_step(name):
    """The paper's technique applies to every arch: one STE score update."""
    cfg = get_config(name, smoke=True)
    api = build_model(cfg)
    key = jax.random.PRNGKey(2)
    params = api.init_params(key)
    spec = masking.MaskSpec()
    mp = masking.init_masked(key, params, spec)
    n_masked = masking.count_params(mp.scores)
    assert n_masked > 0, "every arch must have maskable tensors"
    batch = _batch_for(cfg, key)

    def loss_fn(scores):
        eff = masking.sample_effective(
            masking.MaskedParams(mp.weights, scores, mp.floats), key)
        return api.loss(api.forward(eff, batch), batch)

    l0, g = jax.value_and_grad(loss_fn)(mp.scores)
    gl = [x for x in jax.tree_util.tree_leaves(g) if x is not None]
    assert gl and all(bool(jnp.all(jnp.isfinite(x))) for x in gl)
    assert any(float(jnp.max(jnp.abs(x))) > 0 for x in gl)


# one config per model family (dense / moe / vlm / ssm / hybrid /
# encdec) for the fused-vs-reference path equivalence sweep
FAMILY_REPS = ("internlm2-1.8b", "deepseek-v2-lite-16b", "qwen2-vl-2b",
               "mamba2-370m", "recurrentgemma-9b", "whisper-medium")


@pytest.mark.parametrize("name", FAMILY_REPS)
def test_masked_execution_matches_reference_path(name):
    """The tentpole invariant: the fused masked-execution forward
    (MaskedLeaf -> ops.masked_dense) and the materialized reference
    path (masking.hash_effective -> plain forward) sample bit-identical
    masks under the shared seed convention, so logits are bit-identical
    and score grads agree."""
    cfg = get_config(name, smoke=True)
    api = build_model(cfg)
    key = jax.random.PRNGKey(5)
    params = api.init_params(key)
    mp = masking.init_masked(key, params, masking.MaskSpec())
    seed_fn = lambda i: masking.mask_stream_seed(3, 0, i, 1, run_seed=17)
    batch = _batch_for(cfg, key)

    fused = api.forward(masking.masked_forward_tree(mp, seed_fn), batch)
    eff = api.forward(masking.hash_effective(mp, seed_fn), batch)
    assert np.array_equal(np.asarray(fused[0], np.float32),
                          np.asarray(eff[0], np.float32)), \
        "fused and materialized logits diverge"

    def loss_fused(scores):
        t = masking.masked_forward_tree(
            masking.MaskedParams(mp.weights, scores, mp.floats), seed_fn)
        return api.loss(api.forward(t, batch), batch)

    def loss_eff(scores):
        e = masking.hash_effective(
            masking.MaskedParams(mp.weights, scores, mp.floats), seed_fn)
        return api.loss(api.forward(e, batch), batch)

    l1, g1 = jax.value_and_grad(loss_fused)(mp.scores)
    l2, g2 = jax.value_and_grad(loss_eff)(mp.scores)
    assert float(l1) == float(l2)
    for (path, a), (_, b) in zip(masking.leaves_with_paths(g1),
                                 masking.leaves_with_paths(g2)):
        if a is None:
            continue
        # grads differ only by bf16 rounding of the reference's x^T@g
        d = float(jnp.max(jnp.abs(a - b)))
        scale = float(jnp.max(jnp.abs(b))) + 1e-9
        assert d <= 0.05 * scale + 1e-5, (path, d, scale)


def test_masked_execution_matches_reference_path_cnn():
    """The cnn family (the paper's own Conv models): conv kernels ride
    the materializing fallback, denses the fused kernels — same stream,
    same outputs."""
    from repro.models import cnn
    cfg = cnn.ConvConfig("quick", (8, 8), (32,), n_classes=4, img_size=8)
    key = jax.random.PRNGKey(6)
    params = cnn.init_params(key, cfg)
    mp = masking.init_masked(key, params, masking.MaskSpec())
    seed_fn = lambda i: masking.mask_stream_seed(0, 0, i, 0, run_seed=9)
    images = jax.random.normal(key, (4, 8, 8, 3), jnp.float32)
    labels = jnp.asarray([0, 1, 2, 3], jnp.int32)

    y1 = cnn.forward(masking.masked_forward_tree(mp, seed_fn), cfg,
                     images)
    y2 = cnn.forward(masking.hash_effective(mp, seed_fn), cfg, images)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-5, atol=1e-5)

    def loss_of(build):
        def f(scores):
            t = build(masking.MaskedParams(mp.weights, scores,
                                           mp.floats), seed_fn)
            return cnn.ce_loss(cnn.forward(t, cfg, images),
                               {"labels": labels})
        return f

    l1, g1 = jax.value_and_grad(
        loss_of(masking.masked_forward_tree))(mp.scores)
    l2, g2 = jax.value_and_grad(
        loss_of(masking.hash_effective))(mp.scores)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    for (path, a), (_, b) in zip(masking.leaves_with_paths(g1),
                                 masking.leaves_with_paths(g2)):
        if a is None:
            continue
        # the reference path rounds x^T@g through bf16; the fused
        # kernel keeps it f32 — bf16-level agreement is the bound
        d = float(jnp.max(jnp.abs(a - b)))
        scale = float(jnp.max(jnp.abs(b))) + 1e-9
        assert d <= 0.05 * scale + 1e-5, (path, d, scale)


@pytest.mark.parametrize("name", ["internlm2-1.8b", "mamba2-370m"])
def test_masked_execution_threshold_mode(name):
    """FedMask threshold mode through the fused kernels equals the
    materialized threshold reference."""
    cfg = get_config(name, smoke=True)
    api = build_model(cfg)
    key = jax.random.PRNGKey(8)
    params = api.init_params(key)
    mp = masking.init_masked(key, params, masking.MaskSpec())
    seed_fn = lambda i: masking.mask_stream_seed(0, 0, i, 0)
    batch = _batch_for(cfg, key)
    fused = api.forward(masking.masked_forward_tree(
        mp, seed_fn, mode="threshold", tau=0.45), batch)
    eff = api.forward(masking.hash_effective(
        mp, seed_fn, mode="threshold", tau=0.45), batch)
    assert np.array_equal(np.asarray(fused[0], np.float32),
                          np.asarray(eff[0], np.float32))


def test_dynamics_params_stay_float():
    """A_log / D (ssm) and a_param (hybrid) must NOT be masked —
    Bernoulli-masking a decay rate destroys stability (docs/DESIGN.md
    §Arch-applicability)."""
    for name, frags in (("mamba2-370m", ("A_log", "/D")),
                        ("recurrentgemma-9b", ("a_param",))):
        cfg = get_config(name, smoke=True)
        api = build_model(cfg)
        params = api.init_params(jax.random.PRNGKey(0))
        mp = masking.init_masked(jax.random.PRNGKey(0), params,
                                 masking.MaskSpec())
        for path, leaf in masking.leaves_with_paths(mp.scores):
            for frag in frags:
                if frag.strip("/") in path.split("/")[-1]:
                    assert leaf is None, f"{name}: {path} got masked"


@pytest.mark.parametrize("name", [n for n in ARCH_NAMES
                                  if n != "qwen2-vl-2b"])
def test_decode_matches_forward(name):
    cfg = get_config(name, smoke=True)
    api = build_model(cfg)
    key = jax.random.PRNGKey(3)
    params = api.init_params(key)
    B, S = 2, 8
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    batch = {"tokens": tokens}
    if cfg.family == "encdec":
        batch["frames"] = 0.1 * jax.random.normal(
            key, (B, cfg.enc_seq, cfg.d_model)).astype(jnp.bfloat16)
    ref_logits = api.forward(params, batch)[0]
    cache = api.init_cache(B, S)
    if cfg.family == "encdec":
        from repro.models import encdec
        enc_out = encdec.encode(params, cfg, batch["frames"])

        def fill(lp):
            kk = (enc_out @ lp["cross"]["w_k"]).reshape(
                B, cfg.enc_seq, cfg.n_kv_heads, cfg.hd)
            vv = (enc_out @ lp["cross"]["w_v"]).reshape(
                B, cfg.enc_seq, cfg.n_kv_heads, cfg.hd)
            return kk, vv

        ck, cv = jax.vmap(fill)(params["dec_layers"])
        cache = dict(cache, ck=ck.astype(cache["ck"].dtype),
                     cv=cv.astype(cache["cv"].dtype))
    dec = jax.jit(api.decode_step)
    errs = []
    for t in range(S):
        logits, cache = dec(params, cache, tokens[:, t],
                            jnp.asarray(t, jnp.int32))
        errs.append(float(jnp.max(jnp.abs(logits - ref_logits[:, t]))))
    tol = 0.05 if cfg.family in ("hybrid",) else 0.02
    assert max(errs) < max(tol, 0.02), f"{name}: {errs}"


def test_exact_assigned_configs():
    """The full configs carry the exact assigned hyperparameters."""
    c = get_config("gemma3-4b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads,
            c.d_ff, c.vocab) == (34, 2560, 8, 4, 10240, 262144)
    assert c.global_every == 5 and c.sliding_window
    c = get_config("deepseek-v2-236b")
    assert (c.n_layers, c.d_model, c.n_heads, c.vocab) == \
        (60, 5120, 128, 102400)
    assert (c.n_experts, c.top_k, c.n_shared_experts,
            c.kv_lora_rank, c.moe_d_ff) == (160, 6, 2, 512, 1536)
    c = get_config("qwen2-7b")
    assert c.qkv_bias and (c.n_layers, c.d_model, c.n_heads,
                           c.n_kv_heads, c.d_ff, c.vocab) == \
        (28, 3584, 28, 4, 18944, 152064)
    c = get_config("mamba2-370m")
    assert (c.n_layers, c.d_model, c.ssm_state, c.vocab) == \
        (48, 1024, 128, 50280)
    c = get_config("recurrentgemma-9b")
    assert c.block_pattern == ("rec", "rec", "attn")
    assert (c.n_layers, c.d_model, c.d_ff, c.vocab) == \
        (38, 4096, 12288, 256000)
    c = get_config("whisper-medium")
    assert (c.enc_layers, c.n_layers, c.d_model, c.vocab) == \
        (24, 24, 1024, 51865)
    c = get_config("internlm2-1.8b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads) == \
        (24, 2048, 16, 8)
    c = get_config("deepseek-7b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff) == \
        (30, 4096, 32, 32, 11008)
    c = get_config("deepseek-v2-lite-16b")
    assert (c.n_experts, c.top_k, c.kv_lora_rank, c.q_lora_rank) == \
        (64, 6, 512, 0)
    c = get_config("qwen2-vl-2b")
    assert c.mrope_sections == (16, 24, 24) and c.d_model == 1536
