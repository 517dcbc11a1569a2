"""Randomized property sweeps for the Pallas kernels.

Requires `hypothesis` (the `test` extra); the whole module skips
cleanly when it is absent — tier-1 coverage of the same properties
lives in test_kernels.py as fixed-seed cases.
"""
import pytest

hypothesis = pytest.importorskip("hypothesis")

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.bitpack import pack_bits, unpack_bits
from repro.kernels.masked_matmul import (masked_matmul, masked_matmul_dx,
                                         sample_and_pack)


@given(st.integers(0, 2 ** 20), st.integers(1, 64))
@settings(max_examples=15, deadline=None)
def test_bitpack_roundtrip_property(seed, words):
    key = jax.random.PRNGKey(seed % 9973)
    n = 32 * words
    m = jax.random.bernoulli(key, 0.5, (n,)).astype(jnp.uint8)
    pk = pack_bits(m, interpret=True)
    assert bool(jnp.all(pk == ref.pack_bits(m)))
    un = unpack_bits(pk, n, interpret=True)
    assert bool(jnp.all(un == m))


@given(st.integers(0, 2 ** 20),
       st.sampled_from([128, 256]), st.sampled_from([128, 256]),
       st.sampled_from([128, 256]), st.sampled_from([128, 256]))
@settings(max_examples=10, deadline=None)
def test_masks_bit_identical_across_tilings_property(
        seed, bk_f, bn_f, bk_b, bn_b):
    """Forward-kernel mask, dx-kernel regenerated mask, and
    ref.sample_mask agree bit-exactly for ANY (seed, tiling) pair —
    the invariant the STE backward correctness rests on."""
    K = N = 256
    s = jax.random.normal(jax.random.PRNGKey(seed % 9973), (K, N),
                          jnp.float32)
    w = jnp.ones((K, N), jnp.float32)
    m_fwd = masked_matmul(jnp.eye(K, dtype=jnp.float32), w, s, seed,
                          bm=128, bn=bn_f, bk=bk_f, interpret=True)
    m_dx = masked_matmul_dx(jnp.eye(N, dtype=jnp.float32), w, s, seed,
                            bm=128, bn=bn_b, bk=bk_b, interpret=True)
    m_ref = ref.sample_mask(s, seed).astype(jnp.float32)
    assert np.array_equal(np.asarray(m_fwd), np.asarray(m_ref))
    assert np.array_equal(np.asarray(m_dx).T, np.asarray(m_ref))


@given(st.integers(0, 2 ** 16), st.integers(1, 3),
       st.integers(1, 3000))
@settings(max_examples=15, deadline=None)
def test_sample_and_pack_matches_two_pass_property(seed, C, n):
    """The fused sample+pack kernel equals sample-then-pack_bits
    exactly for any row count / length (incl. non-multiples of 32)."""
    key = jax.random.PRNGKey(seed % 9973)
    s = jax.random.normal(key, (C, n), jnp.float32)
    seeds = jnp.arange(C, dtype=jnp.uint32) * 104729 + seed
    words = sample_and_pack(s, seeds, interpret=True)
    assert bool(jnp.all(words == ref.sample_and_pack(s, seeds)))


@given(st.integers(0, 2 ** 16),
       st.sampled_from([(8, 32, 16), (40, 100, 60), (16, 130, 70)]))
@settings(max_examples=10, deadline=None)
def test_masked_dense_grad_matches_ref_property(seed, shape):
    """jax.grad through the fused custom-vjp matches the pure-jnp STE
    oracle to tolerance for arbitrary (incl. unaligned) shapes."""
    M, K, N = shape
    key = jax.random.PRNGKey(seed % 9973)
    kx, kw, ks = jax.random.split(key, 3)
    x = jax.random.normal(kx, (M, K), jnp.float32)
    w = jax.random.normal(kw, (K, N), jnp.float32)
    s = jax.random.normal(ks, (K, N), jnp.float32)

    def loss(x, s):
        return jnp.sum(ops.masked_dense(x, w, s, seed) ** 2)

    gx, gs = jax.grad(loss, argnums=(0, 1))(x, s)
    y_ref = ref.masked_matmul(x, w, s, seed)
    dx_ref, ds_ref = ref.masked_dense_bwd(x, w, s, seed, 2.0 * y_ref)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(dx_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(ds_ref),
                               rtol=1e-4, atol=1e-4)


@given(st.integers(0, 2 ** 16),
       st.sampled_from([128, 256]), st.sampled_from([128, 256]))
@settings(max_examples=8, deadline=None)
def test_grouped_masks_bit_identical_across_tilings_property(
        seed, bk, bn):
    """Grouped twin of the tiling-invariance property: every group's
    forward/dx kernel mask equals ref.sample_mask at that group's flat
    offset for ANY (seed, tiling) pair."""
    from repro.kernels.masked_matmul import (masked_matmul_grouped,
                                             masked_matmul_grouped_dx)
    E, K, N = 2, 256, 256
    s = jax.random.normal(jax.random.PRNGKey(seed % 9973), (E, K, N),
                          jnp.float32)
    seeds = jnp.full((E,), seed, jnp.uint32)
    offs = jnp.arange(E, dtype=jnp.uint32) * jnp.uint32(K * N)
    w1 = jnp.ones((E, K, N), jnp.float32)
    eye, tg, nl = ref.uniform_groups(
        jnp.broadcast_to(jnp.eye(K, dtype=jnp.float32), (E, K, K)))
    m_fwd = ref.ungroup(masked_matmul_grouped(
        eye, w1, s, seeds, offs, tg, nl, bm=128, bn=bn, bk=bk,
        interpret=True), E, K)
    m_dx = ref.ungroup(masked_matmul_grouped_dx(
        eye, w1, s, seeds, offs, tg, nl, bm=128, bn=bn, bk=bk,
        interpret=True), E, K)
    for e in range(E):
        m_ref = ref.sample_mask(s[e], seed, e * K * N).astype(
            np.float32)
        assert np.array_equal(np.asarray(m_fwd[e]), m_ref)
        assert np.array_equal(np.asarray(m_dx[e]).T, m_ref)


@given(st.integers(0, 2 ** 16), st.integers(1, 4),
       st.sampled_from([(8, 24), (24, 56), (16, 130)]))
@settings(max_examples=10, deadline=None)
def test_grouped_offsets_equal_uplink_stream_property(seed, E, kn):
    """Per-expert offset identity: the E grouped-kernel masks under
    offs[e] = e*K*N are exactly the stacked leaf's flat
    `sample_and_pack` stream, for any (seed, E, K, N)."""
    K, N = kn
    s = jax.random.normal(jax.random.PRNGKey(seed % 9973), (E, K, N),
                          jnp.float32)
    words = ref.sample_and_pack(s.reshape(1, -1),
                                jnp.asarray([seed], jnp.uint32))
    flat = ref.unpack_bits(words[0], E * K * N).reshape(E, K, N)
    eye, tg, nl = ref.uniform_groups(
        jnp.broadcast_to(jnp.eye(K, dtype=jnp.float32), (E, K, K)))
    lay = ops.GroupLayout(tg, nl, jnp.arange(E, dtype=jnp.int32)
                          * (tg.shape[0] // E) * 128)
    m = ref.ungroup(ops.masked_dense_grouped(
        eye, jnp.ones((E, K, N), jnp.float32), s, seed, None, lay), E, K)
    assert np.array_equal(np.asarray(m), np.asarray(flat, np.float32))


@given(st.integers(0, 2 ** 16), st.sampled_from([40, 70, 128]))
@settings(max_examples=10, deadline=None)
def test_masked_conv1d_equals_plain_property(seed, C):
    """The fused masked conv equals the plain-conv kernel fed the
    materialized m⊙w bit-exactly (the model-path identity), and its
    mask is the leaf's flat uplink stream."""
    Wt = 4
    key = jax.random.PRNGKey(seed % 9973)
    kx, kw, ks = jax.random.split(key, 3)
    x = jax.random.normal(kx, (2, 9, C), jnp.float32)
    w = jax.random.normal(kw, (Wt, C), jnp.float32)
    s = jax.random.normal(ks, (Wt, C), jnp.float32)
    m = ref.sample_mask(s, seed, 0)
    y_fused = ops.masked_conv1d(x, w, s, seed, 0)
    y_plain = ops.conv1d_plain(x, m.astype(w.dtype) * w)
    assert np.array_equal(np.asarray(y_fused), np.asarray(y_plain))
    words = ref.sample_and_pack(s.reshape(1, -1),
                                jnp.asarray([seed], jnp.uint32))
    flat = ref.unpack_bits(words[0], Wt * C).reshape(Wt, C)
    assert np.array_equal(np.asarray(m), np.asarray(flat))
