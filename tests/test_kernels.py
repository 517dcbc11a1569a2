"""Pallas kernel allclose sweeps vs ref.py oracles (interpret mode).

Runs without `hypothesis`: the randomized property sweep lives in
test_kernels_property.py (skipped when hypothesis is absent); the
fixed-seed cases below cover the same pack/unpack round trip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref, ops
from repro.kernels.masked_matmul import (masked_matmul, masked_matmul_dx,
                                         masked_matmul_ds,
                                         masked_matmul_grouped,
                                         masked_matmul_grouped_dx,
                                         masked_matmul_grouped_ds,
                                         sample_and_pack_leaf, VMEM_BUDGET)
from repro.kernels.bitpack import pack_bits, unpack_bits


SHAPES = [
    (128, 512, 512),
    (256, 512, 1024),
    (128, 1024, 512),
    (384, 512, 512),    # M not multiple of block -> smaller bm
]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_masked_matmul_allclose(shape, dtype):
    M, K, N = shape
    key = jax.random.PRNGKey(M + K + N)
    kx, kw, ks = jax.random.split(key, 3)
    x = jax.random.normal(kx, (M, K), jnp.float32).astype(dtype)
    w = jax.random.normal(kw, (K, N), jnp.float32).astype(dtype)
    s = jax.random.normal(ks, (K, N), jnp.float32)
    y_kernel = masked_matmul(x, w, s, 42, bm=128, bn=512, bk=512,
                             interpret=True)
    y_ref = ref.masked_matmul(x, w, s, 42)
    np.testing.assert_allclose(
        np.asarray(y_kernel, np.float32), np.asarray(y_ref, np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
        atol=1e-2 if dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("seed", [0, 1, 12345])
def test_masked_matmul_seed_changes_mask(seed):
    M, K, N = 128, 512, 512
    key = jax.random.PRNGKey(0)
    x = jnp.ones((M, K), jnp.float32)
    w = jnp.ones((K, N), jnp.float32)
    s = jnp.zeros((K, N), jnp.float32)  # theta = 0.5 everywhere
    y1 = masked_matmul(x, w, s, seed, interpret=True)
    y2 = masked_matmul(x, w, s, seed + 1, interpret=True)
    assert not np.allclose(np.asarray(y1), np.asarray(y2))
    # theta=0.5: each output ~ sum of K/2 ones
    assert abs(float(jnp.mean(y1)) - K / 2) < K * 0.05


def test_masked_matmul_extreme_scores():
    M, K, N = 128, 512, 512
    x = jnp.ones((M, K), jnp.float32)
    w = jnp.ones((K, N), jnp.float32)
    s_on = jnp.full((K, N), 40.0)
    s_off = jnp.full((K, N), -40.0)
    y_on = masked_matmul(x, w, s_on, 7, interpret=True)
    y_off = masked_matmul(x, w, s_off, 7, interpret=True)
    assert np.allclose(np.asarray(y_on), K)
    assert np.allclose(np.asarray(y_off), 0.0)


@pytest.mark.parametrize("seed,words", [
    (0, 1), (7, 3), (123, 17), (9972, 64), (2 ** 20, 33),
])
def test_bitpack_roundtrip_fixed_seeds(seed, words):
    """Fixed-seed fallback for the hypothesis property sweep."""
    key = jax.random.PRNGKey(seed % 9973)
    n = 32 * words
    m = jax.random.bernoulli(key, 0.5, (n,)).astype(jnp.uint8)
    pk = pack_bits(m, interpret=True)
    assert bool(jnp.all(pk == ref.pack_bits(m)))
    un = unpack_bits(pk, n, interpret=True)
    assert bool(jnp.all(un == m))


@pytest.mark.parametrize("fill", [0, 1])
def test_bitpack_roundtrip_constant_masks(fill):
    n = 32 * 5
    m = jnp.full((n,), fill, jnp.uint8)
    pk = pack_bits(m, interpret=True)
    expect = jnp.uint32(0xFFFFFFFF if fill else 0)
    assert bool(jnp.all(pk == expect))
    assert bool(jnp.all(unpack_bits(pk, n, interpret=True) == m))


def test_bitpack_compression_ratio():
    m = jnp.ones((32 * 1024,), jnp.uint8)
    pk = pack_bits(m, interpret=True)
    assert pk.size * 32 == m.size
    assert pk.dtype == jnp.uint32


def test_ops_masked_dense_ste_gradients():
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (32, 64), jnp.float32)
    w = jax.random.normal(key, (64, 16), jnp.float32)
    s = jnp.zeros((64, 16), jnp.float32)

    def loss(s, x):
        return jnp.sum(ops.masked_dense(x, w, s, 5) ** 2)

    gs = jax.grad(loss, argnums=0)(s, x)
    gx = jax.grad(loss, argnums=1)(s, x)
    assert gs.shape == s.shape and gx.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(gs)))
    # STE: ds includes sigmoid'(s)=0.25 factor at s=0
    assert float(jnp.max(jnp.abs(gs))) > 0


def test_ops_masked_dense_matches_ref_forward():
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (8, 4, 64), jnp.float32)  # batched
    w = jax.random.normal(key, (64, 32), jnp.float32)
    s = jax.random.normal(key, (64, 32), jnp.float32)
    y = ops.masked_dense(x, w, s, 9)
    y_ref = ref.masked_matmul(x.reshape(-1, 64), w, s, 9).reshape(
        8, 4, 32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", [(128, 512, 512), (256, 512, 1024)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_masked_matmul_dx_allclose(shape, dtype):
    M, K, N = shape
    key = jax.random.PRNGKey(M + K + N + 1)
    kg, kw, ks = jax.random.split(key, 3)
    g = jax.random.normal(kg, (M, N), jnp.float32).astype(dtype)
    w = jax.random.normal(kw, (K, N), jnp.float32).astype(dtype)
    s = jax.random.normal(ks, (K, N), jnp.float32)
    dx = masked_matmul_dx(g, w, s, 42, interpret=True)
    dx_ref = ref.masked_matmul_dx(g, w, s, 42)
    np.testing.assert_allclose(
        np.asarray(dx, np.float32), np.asarray(dx_ref, np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
        atol=1e-2 if dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("shape", [(128, 512, 512), (256, 1024, 512)])
def test_masked_matmul_ds_allclose(shape):
    M, K, N = shape
    key = jax.random.PRNGKey(M + K + N + 2)
    kx, kg, kw, ks = jax.random.split(key, 4)
    x = jax.random.normal(kx, (M, K), jnp.float32)
    g = jax.random.normal(kg, (M, N), jnp.float32)
    w = jax.random.normal(kw, (K, N), jnp.float32).astype(jnp.bfloat16)
    s = jax.random.normal(ks, (K, N), jnp.float32)
    ds = masked_matmul_ds(x, g, w, s, interpret=True)
    ds_ref = ref.masked_matmul_ds(x, g, w, s)
    np.testing.assert_allclose(np.asarray(ds), np.asarray(ds_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (128, 256),
                                    (256, 256)])
def test_fwd_bwd_ref_masks_bit_identical_across_tilings(blocks):
    """Fixed-seed fallback for the hypothesis sweep: the forward-kernel
    mask, the dx-kernel regenerated mask, and ref.sample_mask must agree
    BIT-EXACTLY regardless of block shape.  With w = 1 and an identity
    input, the forward returns m and dx returns m^T, both exactly."""
    bk, bn = blocks
    K = N = 256
    s = jax.random.normal(jax.random.PRNGKey(11), (K, N), jnp.float32)
    w = jnp.ones((K, N), jnp.float32)
    eye = jnp.eye(K, dtype=jnp.float32)
    m_fwd = masked_matmul(eye, w, s, 99, bm=128, bn=bn, bk=bk,
                          interpret=True)
    m_dx = masked_matmul_dx(jnp.eye(N, dtype=jnp.float32), w, s, 99,
                            bm=128, bn=bn, bk=bk, interpret=True)
    m_ref = ref.sample_mask(s, 99).astype(jnp.float32)
    assert np.array_equal(np.asarray(m_fwd), np.asarray(m_ref))
    assert np.array_equal(np.asarray(m_dx).T, np.asarray(m_ref))


@pytest.mark.parametrize("mode", ["sample", "threshold"])
@pytest.mark.parametrize("M", [384, 512])
def test_planned_bm_masks_bit_identical_to_bm128(M, mode):
    """`ops.dense_plan` takes the whole token count as one block (one
    pass over w and s per call); the forward and dx masks drawn at that
    bm equal those at bm=128, and the oracle's, bit for bit (w = 1 and
    an identity input return the mask exactly)."""
    K = N = M
    plan = ops.dense_plan(M, K, N)
    assert (plan.bm, plan.passes) == (M, 1)
    s = jax.random.normal(jax.random.PRNGKey(M), (K, N), jnp.float32)
    w = jnp.ones((K, N), jnp.float32)
    eye = jnp.eye(M, dtype=jnp.float32)
    kw = dict(bn=plan.bn, bk=plan.bk, interpret=True, mode=mode, tau=0.6)
    m_ref = (ref.sample_mask(s, 99, 5) if mode == "sample"
             else ref.threshold_mask(s, 0.6)).astype(jnp.float32)
    for bm in (plan.bm, 128):
        m_fwd = masked_matmul(eye, w, s, 99, 5, bm=bm, **kw)
        m_dx = masked_matmul_dx(eye, w, s, 99, 5, bm=bm, **kw)
        assert np.array_equal(np.asarray(m_fwd), np.asarray(m_ref)), bm
        assert np.array_equal(np.asarray(m_dx).T, np.asarray(m_ref)), bm


@pytest.mark.parametrize("kernel", ["fwd", "dx"])
@pytest.mark.parametrize("M", [384, 512])
def test_planned_bm_matches_bm128(kernel, M):
    """Forward and dx at the planned bm against bm=128 on random bf16
    operands.  Both sum the same k (forward) or n (dx) blocks in the
    same order into an f32 accumulator; only the dot inside one block
    may sum its terms in another order when it has more rows, which
    moves an f32 sum by at most T * 2^-24 of the sum of the terms'
    magnitudes (T terms per block), and the bf16 result then rounds to
    within one ulp (2^-8 of the value) of the other."""
    K, N = 256, 512
    plan = ops.dense_plan(M, K, N)
    assert (plan.bm, plan.passes) == (M, 1)
    kx, kw_, ks = jax.random.split(jax.random.PRNGKey(M + 7), 3)
    w = jax.random.normal(kw_, (K, N), jnp.float32).astype(jnp.bfloat16)
    s = jax.random.normal(ks, (K, N), jnp.float32)
    wm = ref.sample_mask(s, 17, 3).astype(jnp.float32) \
        * w.astype(jnp.float32)
    kw = dict(bn=plan.bn, bk=plan.bk, interpret=True)
    if kernel == "fwd":
        a = jax.random.normal(kx, (M, K), jnp.float32).astype(jnp.bfloat16)
        run = lambda bm: masked_matmul(a, w, s, 17, 3, bm=bm, **kw)
        mag, T = jnp.abs(a.astype(jnp.float32)) @ jnp.abs(wm), plan.bk
    else:
        a = jax.random.normal(kx, (M, N), jnp.float32).astype(jnp.bfloat16)
        run = lambda bm: masked_matmul_dx(a, w, s, 17, 3, bm=bm, **kw)
        mag, T = jnp.abs(a.astype(jnp.float32)) @ jnp.abs(wm).T, plan.bn
    big = np.asarray(run(plan.bm), np.float32)
    small = np.asarray(run(128), np.float32)
    tol = 2.0**-8 * np.abs(small) + T * 2.0**-24 * np.asarray(mag)
    assert np.all(np.abs(big - small) <= tol)


@pytest.mark.parametrize("proj,K,N", [
    ("w_q", 2048, 2048), ("w_k", 2048, 1024), ("w_v", 2048, 1024),
    ("w_o", 2048, 2048), ("w_gate", 2048, 8192), ("w_up", 2048, 8192),
    ("w_down", 8192, 2048)])
def test_dense_plan_one_pass_at_train_step(proj, K, N):
    """internlm2-1.8b's projections at one cohort's batch 2 x seq 512:
    the whole token count is one block, so w and s stream once a call,
    inside the VMEM budget the kernels are compiled with."""
    plan = ops.dense_plan(1024, K, N)
    assert (plan.bm, plan.passes) == (1024, 1), (proj, plan)
    assert ops._dense_vmem_bytes(plan.bm, plan.bn, plan.bk) \
        <= VMEM_BUDGET


@pytest.mark.parametrize("K,N", [(2048, 2048), (288, 64), (1152, 128)])
def test_dense_plan_caps_long_calls(K, N):
    """An im2col-sized token count does not fit one block: the budget
    caps bm, and w and s stream more than once."""
    M = 65536
    plan = ops.dense_plan(M, K, N)
    assert plan.passes > 1 and plan.bm * plan.passes == M, plan
    assert plan.bm % 128 == 0
    assert ops._dense_vmem_bytes(plan.bm, plan.bn, plan.bk) \
        <= VMEM_BUDGET
    # the largest such block: twice it would not fit, or not divide M
    assert (M % (2 * plan.bm) or ops._dense_vmem_bytes(
        2 * plan.bm, plan.bn, plan.bk) > VMEM_BUDGET)


def test_padded_launch_mask_matches_ref_bit_exact():
    """ops.masked_dense zero-pads MXU-unaligned shapes but hashes the
    LOGICAL index (n_logical), so the sampled mask must still equal
    ref.sample_mask on the original shape bit-for-bit."""
    K, N = 100, 60
    s = jax.random.normal(jax.random.PRNGKey(5), (K, N), jnp.float32)
    w = jnp.ones((K, N), jnp.float32)
    m = ops.masked_dense(jnp.eye(K, dtype=jnp.float32), w, s, 31)
    m_ref = ref.sample_mask(s, 31).astype(jnp.float32)
    assert np.array_equal(np.asarray(m), np.asarray(m_ref))


@pytest.mark.parametrize("seed,C,n", [
    (0, 1, 32), (3, 2, 1000), (17, 3, 4096), (101, 2, 33),
])
def test_sample_and_pack_matches_ref(seed, C, n):
    """Fixed-seed fallback for the hypothesis sweep: the fused kernel's
    words of flat (C, n) rows equal the two-pass sample-then-pack oracle
    exactly."""
    key = jax.random.PRNGKey(seed)
    s = jax.random.normal(key, (C, n), jnp.float32)
    seeds = jnp.arange(C, dtype=jnp.uint32) * 7919 + seed
    words = ops.sample_and_pack(s, seeds)
    words_ref = ref.sample_and_pack(s, seeds)
    assert words.shape == (C, (n + 31) // 32)
    assert bool(jnp.all(words == words_ref))
    # lossless round trip back to the jnp-sampled mask
    m = jax.vmap(lambda wd: ref.unpack_bits(wd, n))(words)
    assert bool(jnp.all(m == ref.sample_rows(s, seeds)))


def test_sample_and_pack_extreme_scores():
    n = 96
    s_on = jnp.full((1, n), 40.0)
    s_off = jnp.full((1, n), -40.0)
    seeds = jnp.asarray([5], jnp.uint32)
    assert bool(jnp.all(ops.sample_and_pack(s_on, seeds)
                        == jnp.uint32(0xFFFFFFFF)))
    assert bool(jnp.all(ops.sample_and_pack(s_off, seeds) == 0))


@pytest.mark.parametrize("mode", ["sample", "threshold"])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("body,in_place", [
    ((2, 256, 128), True), ((1, 128, 576), True), ((3, 64, 1408), True),
    ((2, 2, 64, 256), True),    # an expert stack (L, E, K, N)
    ((2, 40, 33), False),       # rows of ragged words: padded rows
])
@pytest.mark.parametrize("C", [1, 2, 4])
def test_sample_and_pack_leaf_matches_ref(C, body, in_place, dtype, mode):
    """`ops.sample_and_pack` of a stacked (C, *body) leaf equals the
    two-pass oracle on the flattened rows bit for bit, in place or by
    the flat fallback as `ops.pack_plan` says, with +-40 scores in the
    leaf's first and last rows."""
    s = jax.random.normal(jax.random.PRNGKey(C + sum(body)),
                          (C,) + body, jnp.float32) * 3
    s = s.reshape(C, -1, body[-1]).at[:, 0].set(40.0).at[:, -1].set(-40.0)
    s = s.reshape((C,) + body).astype(dtype)
    seeds = jnp.arange(C, dtype=jnp.uint32) * 104729 + jnp.uint32(2**31 + 5)
    plan = ops.pack_plan(s.shape, s.dtype)
    assert plan.in_place is in_place
    assert plan.grid_steps >= C
    words = ops.sample_and_pack(s, seeds, mode=mode, tau=0.3)
    want = ref.sample_and_pack(s.reshape(C, -1), seeds, mode=mode, tau=0.3)
    assert words.shape == want.shape
    assert np.array_equal(np.asarray(words), np.asarray(want))


@pytest.mark.parametrize("shape,br", [
    ((2, 48, 64), 16),       # three whole blocks, 16 rows a slab
    ((2, 40, 64), 16),       # a partial last block of 8 rows
    ((1, 8, 4160), 8),       # two 4096-lane groups, the second of 64
])
@pytest.mark.parametrize("mode", ["sample", "threshold"])
def test_sample_and_pack_leaf_kernel_matches_leaf_oracle(shape, br, mode):
    """The in-place kernel at a given row block equals its oracle
    `ref.sample_and_pack_leaf` word for word, over several blocks, a
    ragged last block and a row wider than one packing group."""
    s = jax.random.normal(jax.random.PRNGKey(shape[1]), shape) * 3
    seeds = jnp.arange(shape[0], dtype=jnp.uint32) + jnp.uint32(2**31 - 9)
    words = sample_and_pack_leaf(s, seeds, br=br, interpret=True, mode=mode,
                                 tau=0.6)
    want = ref.sample_and_pack_leaf(s, seeds, mode=mode, tau=0.6)
    assert words.shape == want.shape == shape[:2] + (shape[2] // 32,)
    assert np.array_equal(np.asarray(words), np.asarray(want))


@pytest.mark.parametrize("mode", ["sample", "threshold"])
def test_sample_and_pack_leaf_kernel_zeroes_the_pad(mode):
    """Rows that pad a leaf of n elements: the bits past n are zero
    even where the pad's scores would set them, and the words before
    them equal the oracle's."""
    n = 150
    s = jax.random.normal(jax.random.PRNGKey(3), (2, 3, 64)) * 3
    s = s.reshape(2, -1).at[:, n:].set(40.0).reshape(2, 3, 64)
    seeds = jnp.asarray([7, 2**31 + 3], jnp.uint32)
    words = sample_and_pack_leaf(s, seeds, br=3, n=n, interpret=True,
                                 mode=mode, tau=0.6)
    want = ref.sample_and_pack_leaf(s, seeds, mode=mode, tau=0.6, n=n)
    assert np.array_equal(np.asarray(words), np.asarray(want))
    flat = np.asarray(words).reshape(2, -1)
    assert not (flat[:, n // 32] >> (n % 32)).any()
    assert not flat[:, -(-n // 32):].any()


@pytest.mark.parametrize("shape,dtype,in_place,rows,cols", [
    ((2, 2048, 8192), jnp.float32, True, 2048, 8192),     # (C, K, N)
    ((2, 4, 2048, 576), jnp.float32, True, 8192, 576),    # a layer stack
    ((2, 4, 8, 64), jnp.float32, True, 32, 64),
    ((2, 4, 8, 64), jnp.bfloat16, False, 32, 64),         # 8 of 16 rows
    ((2, 24, 4, 2048), jnp.float32, False, 96, 2048),     # conv (L, 4, C)
    ((2, 40, 33), jnp.float32, False, 1, 1536),           # ragged words
    ((3, 1000), jnp.float32, False, 1, 1024),             # flat rows
    ((2, 2048 * 8192), jnp.float32, False, 4096, 4096),
])
def test_pack_plan_in_place_only_where_rows_are_free(shape, dtype, in_place,
                                                     rows, cols):
    """`ops.pack_plan` reads a leaf in its own layout only where its
    rows need no relayout; any other leaf is viewed as rows of whole
    words, padded, and still packs to the flattened leaf's words."""
    plan = ops.pack_plan(shape, dtype)
    assert (plan.in_place, plan.rows, plan.cols) == (in_place, rows, cols)
    assert plan.rows * plan.cols >= np.prod(shape[1:]) and plan.cols % 32 == 0
    assert plan.br == plan.rows or plan.br % 16 == 0
    assert plan.grid_steps == shape[0] * -(-plan.rows // plan.br)


@pytest.mark.parametrize("shape", [(32, 64, 16), (40, 100, 60),
                                   (128, 512, 512)])
def test_masked_dense_grads_match_ref_oracle(shape):
    """Fixed-seed fallback for the hypothesis sweep: jax.grad through
    the fused custom-vjp must match the naive jnp STE backward (same
    mask, same math) — including MXU-unaligned shapes via padding."""
    M, K, N = shape
    key = jax.random.PRNGKey(M + N)
    kx, kw, ks = jax.random.split(key, 3)
    x = jax.random.normal(kx, (M, K), jnp.float32)
    w = jax.random.normal(kw, (K, N), jnp.float32)
    s = jax.random.normal(ks, (K, N), jnp.float32)

    def loss(x, s):
        return jnp.sum(ops.masked_dense(x, w, s, 13) ** 2)

    gx, gs = jax.grad(loss, argnums=(0, 1))(x, s)
    y_ref = ref.masked_matmul(x, w, s, 13)
    dx_ref, ds_ref = ref.masked_dense_bwd(x, w, s, 13, 2.0 * y_ref)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(dx_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(ds_ref),
                               rtol=1e-4, atol=1e-4)


def test_masked_dense_offset_matches_ref_bit_exact():
    """The `off` operand shifts the flat hash index: identity-probing
    the kernel recovers ref.sample_mask(s, seed, off) bit-for-bit, on
    aligned and padded launches."""
    K, N = 100, 60
    s = jax.random.normal(jax.random.PRNGKey(5), (K, N), jnp.float32)
    w = jnp.ones((K, N), jnp.float32)
    for off in (0, 12345, 3 * K * N):
        m = ops.masked_dense(jnp.eye(K, dtype=jnp.float32), w, s, 31,
                             off)
        m_ref = ref.sample_mask(s, 31, off).astype(jnp.float32)
        assert np.array_equal(np.asarray(m), np.asarray(m_ref)), off


def test_stacked_leaf_offsets_equal_uplink_stream():
    """THE shared-stream identity behind the model zoo's MaskedLeaf
    convention: per-block masks at off = l*K*N are exactly the bits
    `sample_and_pack` packs for the flat stacked leaf under one seed."""
    L, K, N = 3, 24, 56
    ss = jax.random.normal(jax.random.PRNGKey(3), (L, K, N), jnp.float32)
    words = ref.sample_and_pack(ss.reshape(1, -1),
                                jnp.asarray([31], jnp.uint32))
    flat = ref.unpack_bits(words[0], L * K * N).reshape(L, K, N)
    per = jnp.stack([ref.sample_mask(ss[l], 31, l * K * N)
                     for l in range(L)])
    assert np.array_equal(np.asarray(flat), np.asarray(per))
    # and the kernel agrees with the per-block oracle
    w = jnp.ones((K, N), jnp.float32)
    for l in range(L):
        m = ops.masked_dense(jnp.eye(K, dtype=jnp.float32), w, ss[l],
                             31, l * K * N)
        assert np.array_equal(np.asarray(m),
                              np.asarray(per[l], np.float32))


def test_masked_dense_offset_grads_match_ref():
    M, K, N = 40, 100, 60
    key = jax.random.PRNGKey(7)
    kx, kw, ks = jax.random.split(key, 3)
    x = jax.random.normal(kx, (M, K), jnp.float32)
    w = jax.random.normal(kw, (K, N), jnp.float32)
    s = jax.random.normal(ks, (K, N), jnp.float32)

    def loss(x, s):
        return jnp.sum(ops.masked_dense(x, w, s, 13, 777) ** 2)

    gx, gs = jax.grad(loss, argnums=(0, 1))(x, s)
    y_ref = ref.masked_matmul(x, w, s, 13, 777)
    dx_ref, ds_ref = ref.masked_dense_bwd(x, w, s, 13, 2.0 * y_ref, 777)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(dx_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(ds_ref),
                               rtol=1e-4, atol=1e-4)


def test_masked_dense_threshold_forward_and_grads():
    """FedMask mode: m = 1[sigmoid(s) > tau] through the fused kernels,
    STE backward identical in form to the Bernoulli mode's."""
    M, K, N = 40, 96, 72
    key = jax.random.PRNGKey(11)
    kx, kw, ks = jax.random.split(key, 3)
    x = jax.random.normal(kx, (M, K), jnp.float32)
    w = jax.random.normal(kw, (K, N), jnp.float32)
    s = jax.random.normal(ks, (K, N), jnp.float32)
    tau = 0.4
    eff = ref.threshold_mask(s, tau).astype(jnp.float32) * w
    y = ops.masked_dense_threshold(x, w, s, tau)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ eff),
                               rtol=1e-5, atol=1e-5)

    def loss(x, s):
        return jnp.sum(ops.masked_dense_threshold(x, w, s, tau) ** 2)

    gx, gs = jax.grad(loss, argnums=(0, 1))(x, s)
    g = 2.0 * np.asarray(y)
    sig = np.asarray(jax.nn.sigmoid(s))
    np.testing.assert_allclose(np.asarray(gx), g @ np.asarray(eff).T,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(gs),
        (np.asarray(x).T @ g) * np.asarray(w) * sig * (1 - sig),
        rtol=1e-4, atol=1e-4)


def test_sample_and_pack_threshold_mode():
    s2 = jax.random.normal(jax.random.PRNGKey(5), (2, 500), jnp.float32)
    seeds = jnp.asarray([1, 2], jnp.uint32)
    wt = ops.sample_and_pack(s2, seeds, mode="threshold", tau=0.3)
    wr = ref.sample_and_pack(s2, seeds, mode="threshold", tau=0.3)
    assert np.array_equal(np.asarray(wt), np.asarray(wr))
    m = jax.vmap(lambda wd: ref.unpack_bits(wd, 500))(wt)
    assert np.array_equal(np.asarray(m),
                          np.asarray(ref.threshold_rows(s2, 0.3)))


# ---------------------------------------------------------------------------
# Grouped kernels: stacked (E, K, N) expert leaves
# ---------------------------------------------------------------------------


def _grouped_operands(E, M, K, N, seed=7, dtype=jnp.float32):
    key = jax.random.PRNGKey(E + M + K + N)
    kx, kw, ks, kg = jax.random.split(key, 4)
    x = jax.random.normal(kx, (E, M, K), jnp.float32).astype(dtype)
    w = jax.random.normal(kw, (E, K, N), jnp.float32).astype(dtype)
    s = jax.random.normal(ks, (E, K, N), jnp.float32)
    g = jax.random.normal(kg, (E, M, N), jnp.float32).astype(dtype)
    seeds = jnp.full((E,), seed, jnp.uint32)
    offs = jnp.arange(E, dtype=jnp.uint32) * jnp.uint32(K * N)
    return x, w, s, g, seeds, offs


@pytest.mark.parametrize("shape", [(2, 128, 256, 128), (3, 128, 128, 256)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_masked_matmul_grouped_allclose(shape, dtype):
    E, M, K, N = shape
    x3, w, s, g3, seeds, offs = _grouped_operands(E, M, K, N, dtype=dtype)
    x, tg, nl = ref.uniform_groups(x3)
    g, _, _ = ref.uniform_groups(g3)
    y = masked_matmul_grouped(x, w, s, seeds, offs, tg, nl,
                              interpret=True)
    y_ref = ref.masked_matmul_grouped(x, w, s, seeds, offs, tg, nl)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
        atol=1e-2 if dtype == jnp.bfloat16 else 1e-4)
    dx = masked_matmul_grouped_dx(g, w, s, seeds, offs, tg, nl,
                                  interpret=True)
    dx_ref = ref.masked_matmul_grouped_dx(g, w, s, seeds, offs, tg, nl)
    np.testing.assert_allclose(
        np.asarray(dx, np.float32), np.asarray(dx_ref, np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
        atol=1e-2 if dtype == jnp.bfloat16 else 1e-4)
    ds = masked_matmul_grouped_ds(x, g, w, s, tg, nl, interpret=True)
    ds_ref = ref.masked_matmul_grouped_ds(x, g, w, s, tg, nl)
    np.testing.assert_allclose(np.asarray(ds), np.asarray(ds_ref),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (128, 256)])
def test_grouped_masks_bit_identical_across_tilings(blocks):
    """Grouped twin of the tiling-invariance property: the forward and
    dx kernels regenerate every group's mask bit-identically to
    ref.sample_mask at that group's offset, for any block shape."""
    bk, bn = blocks
    E, K, N = 3, 256, 256
    _, _, s, _, seeds, offs = _grouped_operands(E, K, K, N)
    w1 = jnp.ones((E, K, N), jnp.float32)
    eye, tg, nl = ref.uniform_groups(
        jnp.broadcast_to(jnp.eye(K, dtype=jnp.float32), (E, K, K)))
    m_fwd = ref.ungroup(masked_matmul_grouped(
        eye, w1, s, seeds, offs, tg, nl, bm=128, bn=bn, bk=bk,
        interpret=True), E, K)
    eyeN, tgN, nlN = ref.uniform_groups(
        jnp.broadcast_to(jnp.eye(N, dtype=jnp.float32), (E, N, N)))
    m_dx = ref.ungroup(masked_matmul_grouped_dx(
        eyeN, w1, s, seeds, offs, tgN, nlN, bm=128, bn=bn, bk=bk,
        interpret=True), E, N)
    for e in range(E):
        m_ref = ref.sample_mask(s[e], 7, e * K * N).astype(np.float32)
        assert np.array_equal(np.asarray(m_fwd[e]), m_ref), (e, blocks)
        assert np.array_equal(np.asarray(m_dx[e]).T, m_ref), (e, blocks)


def _uniform_layout(x3, bm=128):
    rows, tg, nl = ref.uniform_groups(x3, bm)
    E = x3.shape[0]
    t = tg.shape[0] // E
    return rows, ops.GroupLayout(
        tg, nl, jnp.arange(E, dtype=jnp.int32) * t * bm)


def test_grouped_offsets_equal_uplink_stream():
    """THE stacked-leaf identity for experts: under offs[e] = e*K*N and
    one seed, the E per-expert kernel masks are exactly the bits
    `sample_and_pack` packs for the flat (E*K*N,) leaf stream."""
    E, K, N = 4, 24, 56
    ss = jax.random.normal(jax.random.PRNGKey(3), (E, K, N), jnp.float32)
    words = ref.sample_and_pack(ss.reshape(1, -1),
                                jnp.asarray([31], jnp.uint32))
    flat = ref.unpack_bits(words[0], E * K * N).reshape(E, K, N)
    eye, lay = _uniform_layout(
        jnp.broadcast_to(jnp.eye(K, dtype=jnp.float32), (E, K, K)))
    m = ref.ungroup(ops.masked_dense_grouped(
        eye, jnp.ones((E, K, N), jnp.float32), ss, 31, None, lay), E, K)
    assert np.array_equal(np.asarray(m), np.asarray(flat, np.float32))


@pytest.mark.parametrize("shape", [(2, 16, 64, 32), (3, 20, 100, 60)])
def test_masked_dense_grouped_grads_match_ref(shape):
    """jax.grad through the grouped custom-vjp matches the naive jnp
    grouped STE backward — including MXU-unaligned shapes via
    padding."""
    E, M, K, N = shape
    x3, w, s, g, seeds, offs = _grouped_operands(E, M, K, N, seed=13)
    x, lay = _uniform_layout(x3)

    def loss(x, s):
        return jnp.sum(ops.masked_dense_grouped(x, w, s, 13, offs, lay)
                       ** 2)

    gx, gs = jax.grad(loss, argnums=(0, 1))(x, s)
    tg, nl = lay.tile_group, lay.n_live
    y_ref = ref.masked_matmul_grouped(x, w, s, seeds, offs, tg, nl)
    dx_ref, ds_ref = ref.masked_dense_grouped_bwd(x, w, s, seeds, offs,
                                                  2.0 * y_ref, tg, nl)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(dx_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(ds_ref),
                               rtol=1e-4, atol=1e-4)


def test_masked_dense_grouped_threshold_matches_eff():
    """Grouped FedMask mode: threshold masks through the grouped
    kernel equal the materialized threshold reference."""
    E, M, K, N = 2, 12, 40, 24
    x3, w, s, _, _, _ = _grouped_operands(E, M, K, N)
    x, lay = _uniform_layout(x3)
    tau = 0.4
    y = ref.ungroup(ops.masked_dense_grouped_threshold(x, w, s, lay, tau),
                    E, M)
    eff = jax.vmap(lambda se, we: ref.threshold_mask(se, tau).astype(
        jnp.float32) * we)(s, w)
    y_ref = jnp.einsum("emk,ekn->emn", x3, eff)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)

    def loss(s):
        return jnp.sum(ops.masked_dense_grouped_threshold(x, w, s, lay,
                                                          tau) ** 2)

    gs = jax.grad(loss)(s)
    assert gs.shape == s.shape and bool(jnp.all(jnp.isfinite(gs)))


# ---------------------------------------------------------------------------
# Fused depthwise causal conv: the (W, C) kernel leaf
# ---------------------------------------------------------------------------


def _conv_operands(B, S, C, Wt=4, dtype=jnp.float32):
    key = jax.random.PRNGKey(B + S + C)
    kx, kw, ks = jax.random.split(key, 3)
    x = jax.random.normal(kx, (B, S, C), jnp.float32).astype(dtype)
    w = jax.random.normal(kw, (Wt, C), jnp.float32).astype(dtype)
    s = jax.random.normal(ks, (Wt, C), jnp.float32)
    return x, w, s


@pytest.mark.parametrize("C", [128, 96, 70])
@pytest.mark.parametrize("dtype", DTYPES)
def test_masked_conv1d_matches_ref(C, dtype):
    """The fused conv kernel equals the jnp tap-loop oracle (aligned
    and channel-padded launches; the hash stays indexed by the logical
    channel count).  Tolerance-level only: XLA may fuse the oracle's
    mul-add chain into FMAs — the BIT-level invariant of the model
    paths is kernel-vs-kernel (next test)."""
    x, w, s = _conv_operands(2, 16, C, dtype=dtype)
    y = ops.masked_conv1d(x, w, s, 31, 5)
    y_ref = ref.masked_conv1d(x, w, s, 31, 5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_masked_conv1d_equals_plain_on_materialized_weight():
    """Fused masked conv == the mask-free plain-conv kernel fed the
    materialized m⊙w — the instruction-identity that makes the fused
    and reference model paths bit-equal."""
    for dtype in DTYPES:
        x, w, s = _conv_operands(2, 12, 96, dtype=dtype)
        m = ref.sample_mask(s, 9, 77)
        weff = m.astype(w.dtype) * w
        y_fused = ops.masked_conv1d(x, w, s, 9, 77)
        y_plain = ops.conv1d_plain(x, weff)
        assert np.array_equal(np.asarray(y_fused), np.asarray(y_plain))


def test_masked_conv1d_grads_match_ref():
    x, w, s = _conv_operands(3, 10, 70)

    def loss(x, s):
        return jnp.sum(ops.masked_conv1d(x, w, s, 31, 5) ** 2)

    gx, gs = jax.grad(loss, argnums=(0, 1))(x, s)
    y_ref = ref.masked_conv1d(x, w, s, 31, 5)
    dx_ref, ds_ref = ref.masked_conv1d_bwd(x, w, s, 31, 2.0 * y_ref, 5)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(dx_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(ds_ref),
                               rtol=1e-4, atol=1e-4)


def test_masked_conv1d_stream_matches_sample_and_pack():
    """The conv leaf's kernel mask is its uplink stream: identity-probe
    the (W, C) mask via extreme weights and compare against the packed
    flat stream."""
    Wt, C = 4, 56
    s = jax.random.normal(jax.random.PRNGKey(2), (Wt, C), jnp.float32)
    words = ref.sample_and_pack(s.reshape(1, -1),
                                jnp.asarray([19], jnp.uint32))
    flat = ref.unpack_bits(words[0], Wt * C).reshape(Wt, C)
    # an impulse at position t makes y[·, W-1, c] = (m ⊙ 1)[t, c]:
    # at output position W-1 the window covers x[0..W-1] tap-aligned
    x = jnp.zeros((Wt, Wt, C), jnp.float32)
    for t in range(Wt):
        x = x.at[t, t].set(1.0)
    y = ops.masked_conv1d(x, jnp.ones((Wt, C), jnp.float32), s, 19, 0)
    got = np.stack([np.asarray(y[t, Wt - 1]) for t in range(Wt)])
    assert np.array_equal(got, np.asarray(flat, np.float32))


def test_conv1d_plain_grads_match_views_einsum():
    """The plain-conv custom-vjp (float baselines) matches autodiff
    through the old stacked-views einsum formulation."""
    B, S, C, Wt = 2, 12, 40, 4
    x, w, _ = _conv_operands(B, S, C, Wt)

    def loss_k(x, w):
        return jnp.sum(ops.conv1d_plain(x, w) ** 2)

    def loss_ref(x, w):
        xp = jnp.pad(x, ((0, 0), (Wt - 1, 0), (0, 0)))
        views = jnp.stack([xp[:, i:i + S] for i in range(Wt)], axis=2)
        out = jnp.einsum("bswc,wc->bsc", views.astype(jnp.float32),
                         w.astype(jnp.float32))
        return jnp.sum(out ** 2)

    g1 = jax.grad(loss_k, argnums=(0, 1))(x, w)
    g2 = jax.grad(loss_ref, argnums=(0, 1))(x, w)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_masked_conv1d_threshold_mode():
    x, w, s = _conv_operands(2, 8, 64)
    tau = 0.35
    y = ops.masked_conv1d_threshold(x, w, s, tau)
    weff = ref.threshold_mask(s, tau).astype(jnp.float32) * w
    y_ref = ops.conv1d_plain(x, weff)
    assert np.array_equal(np.asarray(y), np.asarray(y_ref))


def test_use_interpret_cached_and_forceable(monkeypatch,
                                            kernel_backend_reset):
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    assert ops._use_interpret() is True
    # cached: changing the env after the first call has no effect...
    monkeypatch.delenv("REPRO_FORCE_INTERPRET")
    assert ops._use_interpret() is True
    assert ops._use_interpret.cache_info().hits >= 1
    # ...until the public reset makes the flip take effect (on the CPU
    # test backend the uncached answer is interpret=True, so flip via
    # the backend probe instead)
    monkeypatch.setattr(ops, "repro_backend", lambda: "tpu")
    assert ops._use_interpret() is True      # still the stale cache
    ops.reset_backend_cache()
    assert ops._use_interpret() is False     # fresh decision


@pytest.mark.parametrize("backend,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", False)])
def test_use_interpret_only_on_cpu(monkeypatch, kernel_backend_reset,
                                   backend, interpret):
    """Interpret mode is chosen for the CPU backend only: any other
    backend compiles the kernels, so a device they cannot compile for
    fails instead of being emulated."""
    monkeypatch.delenv("REPRO_FORCE_INTERPRET", raising=False)
    monkeypatch.setattr(ops, "repro_backend", lambda: backend)
    assert ops._use_interpret() is interpret


def test_hash_uniform_distribution():
    idx = jnp.arange(1 << 16, dtype=jnp.uint32)
    u = ref.hash_uniform(idx, 3)
    assert 0.49 < float(jnp.mean(u)) < 0.51
    assert float(jnp.min(u)) >= 0.0 and float(jnp.max(u)) < 1.0
    # uniformity: chi-square-ish bucket check
    hist, _ = np.histogram(np.asarray(u), bins=16, range=(0, 1))
    assert hist.min() > (1 << 16) / 16 * 0.9
