"""The expert layer that holds a share of the experts, its dispatch and
grouped kernels, and MLA's YaRN positions, against plain references
(`benchmarks/chip/reference/moe.py`, the kernels' jnp oracles, the
published YaRN formulas) at small widths on the CPU."""
import dataclasses
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops, ref
from repro.kernels import masked_matmul as _mm
from repro.models import layers as L

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmarks.chip.reference import moe as RM  # noqa: E402

D, F, E, K_TOP = 32, 16, 8, 3
ident = lambda a: a  # noqa: E731  (activations held in float32)


def _layer(key, n_held=E, n_shared=0):
    """An uncut layer's plain float32 weights, and its share of
    experts [0, n_held)."""
    p = L.moe_init(key, D, F, E, n_shared, dtype=jnp.float32)
    cut = {k: (v[:n_held] if k.startswith("w_") else v)
           for k, v in p.items()}
    return p, cut


def _ref_layer(p, n_held, x):
    """reference/moe.py's layer on the same weights (shared experts
    included when `p` has them)."""
    cfg = dict(hidden_size=D, num_attention_heads=1, qk_nope_head_dim=1,
               qk_rope_head_dim=1, v_head_dim=1, kv_lora_rank=1,
               router_experts=E, n_routed_experts=n_held,
               num_experts_per_tok=K_TOP)
    w = {("moe", n): p[n][:n_held] for n in ("w_gate", "w_up", "w_down")}
    if "shared" in p:
        w.update({("moe", "shared", n): v for n, v in p["shared"].items()})
    else:
        zero = jnp.zeros((D, 1), jnp.float32)
        w.update({("moe", "shared", "w_gate"): zero,
                  ("moe", "shared", "w_up"): zero,
                  ("moe", "shared", "w_down"): zero.T})
    f = {("moe", "router_w"): p["router_w"]}
    with jax.default_matmul_precision("highest"):
        return RM.moe(x, w, f, cfg, ident)


def _x(key, B=2, S=40):
    return jax.random.normal(key, (B, S, D), jnp.float32)


@pytest.mark.parametrize("n_held", [8, 4, 1])
def test_share_matches_reference_layer(n_held):
    """The program's dropless layer holding experts [0, n_held) equals
    the reference's share of the same layer, balance term included."""
    key = jax.random.PRNGKey(n_held)
    p, cut = _layer(key, n_held, n_shared=2)
    x = _x(jax.random.fold_in(key, 1))
    with jax.default_matmul_precision("highest"):
        y, aux = L.moe_apply(cut, x, E, K_TOP)
    y_ref, aux_ref = _ref_layer(p, n_held, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-5)


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_shares_sum_to_the_uncut_layer(shares):
    """Every chip's share of a layer (its experts relabelled to [0,
    held), the router's columns with them) adds up to the uncut
    reference layer, the shared experts counted once."""
    key = jax.random.PRNGKey(10 + shares)
    held = E // shares
    p, _ = _layer(key, E, n_shared=2)
    x = _x(jax.random.fold_in(key, 1))
    total = jnp.zeros_like(x)
    with jax.default_matmul_precision("highest"):
        for s in range(shares):
            sl = slice(s * held, (s + 1) * held)
            part = {"router_w": jnp.roll(p["router_w"], -s * held, axis=1),
                    **{k: p[k][sl] for k in ("w_gate", "w_up", "w_down")}}
            y, _ = L.moe_apply(part, x, E, K_TOP)
            total = total + y
        shared = L.mlp_apply(p["shared"], x)
    y_ref, _ = _ref_layer(p, E, x)
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(y_ref), rtol=1e-4, atol=1e-5)


def test_routing_onto_one_expert_drops_no_token():
    """Every token's first choice forced onto expert 0: its group holds
    all T rows (far past any capacity), and the layer still equals the
    reference."""
    key = jax.random.PRNGKey(5)
    p, cut = _layer(key, 4)
    x = _x(jax.random.fold_in(key, 1), B=3, S=64)
    x = x.at[..., 0].set(jnp.abs(x[..., 0]) + 4.0)
    p["router_w"] = p["router_w"].at[0, 0].set(50.0)
    cut["router_w"] = p["router_w"]
    probs = jax.nn.softmax(x.reshape(-1, D) @ p["router_w"], -1)
    assert bool(jnp.all(jnp.argmax(probs, -1) == 0))
    with jax.default_matmul_precision("highest"):
        y, _ = L.moe_apply(cut, x, E, K_TOP)
    y_ref, _ = _ref_layer(p, 4, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-5)


def test_dispatch_places_every_held_pair_once():
    """The sorted buffer: each held (token, slot) pair sits in exactly
    one row of its expert's group, within the static bound, and no
    other pair appears."""
    key = jax.random.PRNGKey(7)
    T, held = 200, 3
    gidx = jnp.argsort(jax.random.uniform(key, (T, E)), -1)[:, :K_TOP]
    is_held = gidx < held
    plan = ops.grouped_plan(T * min(K_TOP, held), D, F, held,
                            T * K_TOP / E)
    layout, pair = L.moe_dispatch(gidx, is_held, held, plan)
    pair = np.asarray(pair)
    P = T * K_TOP
    live = pair < P
    want = np.flatnonzero(np.asarray(is_held).reshape(-1))
    assert sorted(pair[live].tolist()) == want.tolist()
    rows = np.flatnonzero(live)
    grp = np.repeat(np.asarray(layout.tile_group), plan.bm)[rows]
    assert np.array_equal(grp, np.asarray(gidx).reshape(-1)[pair[live]])
    assert int(layout.n_live[0]) <= plan.tiles


@pytest.mark.parametrize("sizes", [[130, 0, 5, 260], [0, 0, 0, 1],
                                   [0, 384, 0, 0], [128, 128, 128, 128]])
def test_group_layout_bound_and_tiles(sizes):
    """Each group owns max(1, ceil(size / bm)) tiles in order; the
    layout fits sum(sizes) // bm + E tiles."""
    bm, Eg = 128, len(sizes)
    sz = jnp.asarray(sizes, jnp.int32)
    tiles = sum(sizes) // bm + Eg
    lay = ops.group_layout(sz, bm, tiles)
    per = [max(1, -(-s // bm)) for s in sizes]
    assert int(lay.n_live[0]) == sum(per) <= tiles
    tg = np.asarray(lay.tile_group)
    assert tg[:sum(per)].tolist() == sum(([e] * n for e, n in
                                          enumerate(per)), [])
    assert (tg[sum(per):] == Eg - 1).all()
    assert np.asarray(lay.starts).tolist() == [
        bm * sum(per[:e]) for e in range(Eg)]


def _uneven(sizes, K, N, bm=128, seed=0):
    Eg = len(sizes)
    tiles = sum(sizes) // bm + Eg + 1       # one dead tile at least
    lay = ops.group_layout(jnp.asarray(sizes, jnp.int32), bm, tiles)
    key = jax.random.PRNGKey(seed)
    R = tiles * bm
    rows = np.zeros((R,), bool)
    for e, s in enumerate(sizes):
        st = int(lay.starts[e])
        rows[st:st + s] = True
    x = jax.random.normal(key, (R, K)) * rows[:, None]
    g = jax.random.normal(jax.random.fold_in(key, 1), (R, N)) * rows[:, None]
    w = jax.random.normal(jax.random.fold_in(key, 2), (Eg, K, N))
    s = jax.random.normal(jax.random.fold_in(key, 3), (Eg, K, N))
    return lay, x, g, w, s, rows


@pytest.mark.parametrize("sizes", [[130, 0, 5, 260], [0, 300, 0, 17]])
def test_grouped_kernels_match_einsum_at_uneven_groups(sizes):
    """fwd, dx and ds over groups of uneven size (empty ones too) equal
    each group's own einsum on the masked weights; the tiles past the
    live ones leave their rows alone."""
    K, N, bm = 256, 384, 128
    lay, x, g, w, s, rows = _uneven(sizes, K, N, bm)
    Eg = len(sizes)
    seeds = jnp.full((Eg,), 11, jnp.uint32)
    offs = jnp.arange(Eg, dtype=jnp.uint32) * jnp.uint32(K * N)
    kw = dict(bm=bm, bn=128, bk=128, interpret=True)
    tg, nl = lay.tile_group, lay.n_live
    y = _mm.masked_matmul_grouped(x, w, s, seeds, offs, tg, nl, **kw)
    dx = _mm.masked_matmul_grouped_dx(g, w, s, seeds, offs, tg, nl, **kw)
    ds = _mm.masked_matmul_grouped_ds(x, g, w, s, tg, nl, **kw)
    wm = np.stack([np.asarray(ref.sample_mask(s[e], 11, e * K * N),
                              np.float32) * np.asarray(w[e])
                   for e in range(Eg)])
    sig = jax.nn.sigmoid(s)
    for e, n in enumerate(sizes):
        st = int(lay.starts[e])
        xe, ge = np.asarray(x[st:st + n]), np.asarray(g[st:st + n])
        np.testing.assert_allclose(np.asarray(y[st:st + n]), xe @ wm[e],
                                   rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(np.asarray(dx[st:st + n]),
                                   ge @ wm[e].T, rtol=1e-4, atol=1e-3)
        want = (xe.T @ ge) * np.asarray(w[e] * sig[e] * (1 - sig[e]))
        np.testing.assert_allclose(np.asarray(ds[e]), want, rtol=1e-4,
                                   atol=1e-3)
    dead = int(nl[0]) * bm
    assert dead < x.shape[0]


def test_grouped_grads_through_the_layer_match_plain_weights():
    """jax.grad through the grouped custom-vjp inside the layer equals
    the plain-weight (ragged_dot) path on the same threshold masks."""
    key = jax.random.PRNGKey(3)
    p, cut = _layer(key, 4)
    x = _x(jax.random.fold_in(key, 1))
    s = {k: jax.random.normal(jax.random.fold_in(key, i), cut[k].shape)
         for i, k in enumerate(("w_gate", "w_up", "w_down"))}

    def masked(sc):
        return {**cut, **{k: masking_leaf(cut[k], sc[k]) for k in sc}}

    def plain(sc):
        return {**cut, **{k: (jax.nn.sigmoid(sc[k]) > 0.5).astype(
            jnp.float32) * cut[k] for k in sc}}

    def loss(sc, build):
        with jax.default_matmul_precision("highest"):
            y, _ = L.moe_apply(build(sc), x, E, K_TOP)
        return jnp.sum(y ** 2)

    l1, g1 = jax.value_and_grad(loss)(s, masked)
    l2 = loss(s, plain)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-4)
    assert all(bool(jnp.all(jnp.isfinite(v))) and float(jnp.abs(v).sum())
               > 0 for v in g1.values())


def masking_leaf(w, s):
    from repro.core.masking import MaskedLeaf
    return MaskedLeaf.build(w, s, 0, mode="threshold", tau=0.5)


# ---------------------------------------------------------------------------
# YaRN (DeepseekV2YarnRotaryEmbedding)
# ---------------------------------------------------------------------------


def _published_inv_freq(dim, base, factor, orig, beta_fast, beta_slow):
    """The published construction, transcribed in numpy."""
    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) \
            / (2 * math.log(base))
    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32)
                                / dim)
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2,
                                                    dtype=np.float32)
                                          / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return freq_inter * (1 - mask) + freq_extra * mask


def test_yarn_frequencies_match_the_published_formula():
    y = L.Yarn(40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    got = np.asarray(L.yarn_freqs(64, 10000.0, y))
    want = _published_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # the highest frequencies kept, the lowest divided by the factor
    np.testing.assert_allclose(got[0], 1.0, rtol=1e-6)
    np.testing.assert_allclose(got[-1], want[-1], rtol=1e-6)
    assert got[-1] == pytest.approx(10000.0 ** (-62 / 64) / 40, rel=1e-5)


def test_yarn_scale_and_amplitude():
    y = L.Yarn(40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert L.yarn_mscale(40.0, 0.707) == pytest.approx(m)
    assert m * m == pytest.approx(1.589, abs=1e-3)
    assert L.yarn_attn_scale(192, y) == pytest.approx(192 ** -0.5 * m * m)
    assert L.yarn_attn_scale(192, None) == pytest.approx(192 ** -0.5)
    # mscale == mscale_all_dim: cos and sin keep amplitude 1
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 9, 2, 64))
    out = L.apply_rope(x, jnp.arange(9), 10000.0, y)
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(out, axis=-1)),
                               np.asarray(jnp.linalg.norm(x, axis=-1)),
                               rtol=1e-5)


def test_mla_with_yarn_matches_reference_attention():
    """The program's MLA (value head 128 != qk head 192 here scaled
    down: v 8, qk 12+4) equals reference/moe.py's blocked attention."""
    cfg_p = get_config("deepseek-v2-lite-16b", smoke=True)
    nh, r, nope, rope_d, vd = 4, 32, 12, 4, 8
    key = jax.random.PRNGKey(2)
    p = L.mla_init(key, D, nh, r, 0, nope, rope_d, vd, dtype=jnp.float32)
    x = _x(jax.random.fold_in(key, 1), B=1, S=24)
    y = L.Yarn(cfg_p.yarn_factor, cfg_p.yarn_original_max_pos,
               cfg_p.yarn_beta_fast, cfg_p.yarn_beta_slow,
               cfg_p.yarn_mscale, cfg_p.yarn_mscale_all_dim)
    with jax.default_matmul_precision("highest"):
        out, _ = L.mla_apply(p, x, jnp.arange(24), nh, r, nope, rope_d,
                             vd, rope_theta=10000.0, yarn=y)
        cfg = dict(hidden_size=D, num_attention_heads=nh,
                   qk_nope_head_dim=nope, qk_rope_head_dim=rope_d,
                   v_head_dim=vd, kv_lora_rank=r, router_experts=E,
                   n_routed_experts=E, rms_norm_eps=1e-6,
                   rope_theta=10000,
                   rope_scaling=dict(factor=40, beta_fast=32, beta_slow=1,
                                     mscale=0.707, mscale_all_dim=0.707,
                                     original_max_position_embeddings=4096))
        w = {("attn", k): v for k, v in p.items() if k.startswith("w_")}
        f = {("attn", "kv_norm_scale"): p["kv_norm_scale"]}
        want = RM.attention(x, w, f, cfg, ident)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# The grouped plan (the counter chip_smoke.py prints)
# ---------------------------------------------------------------------------


def test_grouped_plan_at_the_share_cell_by_hand():
    """seq 8192, top-6 of 64, 8 held: 768 rows an expert on average.
    bm 512 (<= the mean, fits VMEM), the 1408-wide side one block; the
    buffer's bound 8192*6 // 512 + 8 = 104 tiles; 2 live tiles an
    expert at the mean."""
    up = ops.grouped_plan(8192 * 6, 2048, 1408, 8, 8192 * 6 / 64)
    down = ops.grouped_plan(8192 * 6, 1408, 2048, 8, 8192 * 6 / 64)
    assert up == ops.GroupedPlan(512, 1408, 512, 104, 16)
    assert down == ops.GroupedPlan(512, 512, 1408, 104, 16)
    tiny = ops.grouped_plan(64 * 2, 32, 16, 4, 64 * 2 / 8)
    assert (tiny.bm, tiny.tiles, tiny.live) == (128, 1 + 4, 4)


def test_config_holds_the_share_and_yarn():
    c = get_config("deepseek-v2-lite-16b")
    assert (c.n_experts, c.n_held, c.yarn_factor, c.yarn_mscale_all_dim) \
        == (64, 64, 40.0, 0.707)
    cut = dataclasses.replace(c, n_held_experts=8)
    assert cut.n_held == 8
