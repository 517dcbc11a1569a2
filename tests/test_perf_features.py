"""Correctness tests for the §Perf hillclimb features: they must be
exact (or bf16-tolerant) drop-ins for the baselines they replace."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model, layers as L
from repro.launch.dryrun import collective_bytes


def test_windowed_decode_matches_regular():
    cfg = get_config("gemma3-4b", smoke=True)
    api_ref = build_model(cfg)
    api_w = build_model(dataclasses.replace(cfg, window_kv_cache=True))
    key = jax.random.PRNGKey(5)
    params = api_ref.init_params(key)
    B, S = 2, 24  # > window(8): exercises ring wraparound
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    c1, c2 = api_ref.init_cache(B, S), api_w.init_cache(B, S)
    d1, d2 = jax.jit(api_ref.decode_step), jax.jit(api_w.decode_step)
    for t in range(S):
        l1, c1 = d1(params, c1, tokens[:, t], jnp.asarray(t, jnp.int32))
        l2, c2 = d2(params, c2, tokens[:, t], jnp.asarray(t, jnp.int32))
        assert float(jnp.max(jnp.abs(l2 - l1))) < 0.05, t


def test_windowed_cache_is_smaller():
    cfg = dataclasses.replace(get_config("gemma3-4b", smoke=True),
                              window_kv_cache=True)
    api_w = build_model(cfg)
    api_r = build_model(get_config("gemma3-4b", smoke=True))
    S = 512
    sz = lambda c: sum(x.size * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(c))
    full = sz(jax.eval_shape(lambda: api_r.init_cache(1, S)))
    ring = sz(jax.eval_shape(lambda: api_w.init_cache(1, S)))
    assert ring < full / 3  # 5:1 local:global with window << S


def test_remat_preserves_forward_and_grads():
    cfg = get_config("internlm2-1.8b", smoke=True)
    api = build_model(cfg)
    api_r = build_model(dataclasses.replace(cfg, remat=True))
    key = jax.random.PRNGKey(0)
    params = api.init_params(key)
    batch = {"tokens": jax.random.randint(key, (2, 16), 0, cfg.vocab)}

    def loss(p, a):
        return a.loss(a.forward(p, batch), batch)

    l1, g1 = jax.value_and_grad(lambda p: loss(p, api))(params)
    l2, g2 = jax.value_and_grad(lambda p: loss(p, api_r))(params)
    assert abs(float(l1) - float(l2)) < 1e-4
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=1e-3)


def test_chunked_attention_matches_dense():
    key = jax.random.PRNGKey(3)
    B, S, H, Kv, Hd = 2, 64, 4, 2, 16
    q = jax.random.normal(key, (B, S, H, Hd), jnp.float32)
    k = jax.random.normal(key, (B, S, Kv, Hd), jnp.float32)
    v = jax.random.normal(key, (B, S, Kv, Hd), jnp.float32)
    pos = jnp.arange(S)
    dense = L.attention_core(q, k, v, pos, pos, causal=True)
    chunked = L.attention_core(q, k, v, pos, pos, causal=True,
                               chunk_kv=16)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(chunked),
                               rtol=1e-4, atol=1e-4)
    # sliding window variant
    dw = L.attention_core(q, k, v, pos, pos, causal=True, window=8)
    cw = L.attention_core(q, k, v, pos, pos, causal=True, window=8,
                          chunk_kv=16)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(cw),
                               rtol=1e-4, atol=1e-4)


def test_collective_bytes_parser():
    hlo = """
  %ag = bf16[4,128]{1,0} all-gather(%x), replica_groups=[2,16]<=[32], dimensions={0}
  %ar-start = f32[256]{0} all-reduce-start(%y), replica_groups=[1,32]<=[32]
  %ar-done = f32[256]{0} all-reduce-done(%ar-start)
  %rs = u32[8]{0} reduce-scatter(%z), replica_groups=[4,8]<=[32]
"""
    cb = collective_bytes(hlo)
    assert cb["all-gather"] == 4 * 128 * 2 // 16
    assert cb["all-reduce"] == 256 * 4          # start counted once
    assert cb["reduce-scatter"] == 8 * 4 * 8
    assert cb["total"] == sum(v for k, v in cb.items() if k != "total")
