"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def hash_uniform(idx: jax.Array, seed) -> jax.Array:
    """Must match masked_matmul._hash_uniform exactly."""
    s = jnp.asarray(seed, jnp.uint32) + jnp.uint32(1)
    s = (s ^ (s >> 16)) * jnp.uint32(0x45D9F3B5)
    s = s ^ (s >> 11)
    x = idx.astype(jnp.uint32) + jnp.uint32(0x9E3779B9) * s
    x = (x ^ (x >> 16)) * jnp.uint32(0x85EBCA6B)
    x = (x ^ s ^ (x >> 13)) * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return (x >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def masked_matmul(x, w, s, seed, off=0):
    wm = sample_mask(s, seed, off).astype(jnp.float32) \
        * w.astype(jnp.float32)
    return (x.astype(jnp.float32) @ wm).astype(x.dtype)


def sample_mask(s, seed, off=0):
    """The mask the fused kernel implicitly uses (for uplink packing).
    `off` shifts the flat hash index (layer-stacked leaves)."""
    K, N = s.shape
    idx = (jnp.asarray(off, jnp.uint32)
           + jnp.arange(K, dtype=jnp.uint32)[:, None] * jnp.uint32(N)
           + jnp.arange(N, dtype=jnp.uint32)[None, :])
    u = hash_uniform(idx, seed)
    return (u < jax.nn.sigmoid(s.astype(jnp.float32))).astype(jnp.uint8)


def threshold_mask(s, tau=0.5):
    """The deterministic FedMask mask m = 1[sigmoid(s) > tau]."""
    return (jax.nn.sigmoid(s.astype(jnp.float32))
            > jnp.asarray(tau, jnp.float32)).astype(jnp.uint8)


def masked_matmul_dx(g, w, s, seed, off=0):
    """Oracle for kernels.masked_matmul_dx: dx = g @ (m ⊙ w)ᵀ with the
    mask regenerated from the same hash stream as the forward."""
    m = sample_mask(s, seed, off).astype(jnp.float32)
    wm = m * w.astype(jnp.float32)
    return (g.astype(jnp.float32) @ wm.T).astype(g.dtype)


def masked_matmul_ds(x, g, w, s):
    """Oracle for kernels.masked_matmul_ds: the STE score gradient
    ds = (xᵀ@g) ⊙ w ⊙ σ(s)(1−σ(s))."""
    xg = x.astype(jnp.float32).T @ g.astype(jnp.float32)
    sig = jax.nn.sigmoid(s.astype(jnp.float32))
    return (xg * w.astype(jnp.float32) * sig * (1.0 - sig)).astype(
        s.dtype)


def masked_dense_bwd(x, w, s, seed, g, off=0):
    """The naive (3-temporary) STE backward — ops._bwd's fallback math
    and the benchmark baseline: materializes the mask, the masked
    weights, and xᵀ@g at weight size."""
    K, N = w.shape
    x2 = x.reshape(-1, K)
    g2 = g.reshape(-1, N)
    m = sample_mask(s, seed, off).astype(jnp.float32)
    wf = w.astype(jnp.float32)
    wm = (m * wf).astype(x.dtype)
    dx = (g2 @ wm.T).reshape(x.shape).astype(x.dtype)
    xg = x2.astype(jnp.float32).T @ g2.astype(jnp.float32)
    sig = jax.nn.sigmoid(s.astype(jnp.float32))
    ds = (xg * wf * sig * (1.0 - sig)).astype(s.dtype)
    return dx, ds


def _grouped_mask(s, seeds, offs, mode="sample", tau=0.5):
    if mode == "threshold":
        return jax.vmap(lambda se: threshold_mask(se, tau))(s)
    return jax.vmap(sample_mask)(s, jnp.asarray(seeds, jnp.uint32),
                                 jnp.asarray(offs, jnp.uint32))


def uniform_groups(x3, bm: int = 128):
    """E equal groups (E, M, K) in the grouped kernels' row layout:
    each group's M rows padded with zeros to whole tiles of `bm`.
    Returns (rows (E*t*bm, K), tile_group (E*t,), n_live) with
    t = ceil(M / bm); `ungroup` takes rows back to (E, M, .)."""
    E, M, K = x3.shape
    t = -(-M // bm)
    rows = jnp.pad(x3, ((0, 0), (0, t * bm - M), (0, 0)))
    return (rows.reshape(E * t * bm, K),
            jnp.repeat(jnp.arange(E, dtype=jnp.int32), t),
            jnp.array([E * t], jnp.int32))


def ungroup(rows, E: int, M: int):
    return rows.reshape(E, -1, rows.shape[-1])[:, :M]


def _row_groups(tile_group, n_live, R):
    """(R, E) one-hot of each row's group, zero on dead tiles' rows."""
    tiles = tile_group.shape[0]
    grp = jnp.repeat(jnp.asarray(tile_group), R // tiles)
    live = jnp.repeat(jnp.arange(tiles) < jnp.asarray(n_live).reshape(()),
                      R // tiles)
    return grp, live


def _grouped_rows(a, wm, tile_group, n_live, eq):
    R = a.shape[0]
    grp, live = _row_groups(tile_group, n_live, R)
    ys = jnp.einsum(eq, a.astype(jnp.float32), wm)      # (E, R, .)
    y = jnp.take_along_axis(ys, grp[None, :, None], axis=0)[0]
    return jnp.where(live[:, None], y, 0.0).astype(a.dtype)


def masked_matmul_grouped(x, w, s, seeds, offs, tile_group, n_live,
                          mode="sample", tau=0.5):
    """Oracle for kernels.masked_matmul_grouped: y[r] = x[r] @
    (m[e]⊙w[e]) for the rows r of group e (the tile's group in
    `tile_group`), group e's mask drawn at flat offset offs[e] of
    seeds[e]'s stream; dead tiles' rows are zero here."""
    wm = _grouped_mask(s, seeds, offs, mode, tau).astype(jnp.float32) \
        * w.astype(jnp.float32)
    return _grouped_rows(x, wm, tile_group, n_live, "rk,ekn->ern")


def masked_matmul_grouped_dx(g, w, s, seeds, offs, tile_group, n_live,
                             mode="sample", tau=0.5):
    """Oracle for kernels.masked_matmul_grouped_dx:
    dx[r] = g[r] @ (m[e] ⊙ w[e])ᵀ, same per-group streams."""
    wm = _grouped_mask(s, seeds, offs, mode, tau).astype(jnp.float32) \
        * w.astype(jnp.float32)
    return _grouped_rows(g, wm, tile_group, n_live, "rn,ekn->erk")


def masked_matmul_grouped_ds(x, g, w, s, tile_group, n_live):
    """Oracle for kernels.masked_matmul_grouped_ds:
    ds[e] = (x_eᵀ@g_e) ⊙ w[e] ⊙ σ(s[e])(1−σ(s[e])) over group e's
    rows."""
    E = w.shape[0]
    grp, live = _row_groups(tile_group, n_live, x.shape[0])
    sel = jax.nn.one_hot(grp, E, dtype=jnp.float32) * live[:, None]
    xg = jnp.einsum("rk,rn,re->ekn", x.astype(jnp.float32),
                    g.astype(jnp.float32), sel)
    sig = jax.nn.sigmoid(s.astype(jnp.float32))
    return (xg * w.astype(jnp.float32) * sig * (1.0 - sig)).astype(
        s.dtype)


def masked_dense_grouped_bwd(x, w, s, seeds, offs, g, tile_group, n_live,
                             mode="sample", tau=0.5):
    """The naive grouped STE backward (REPRO_REF_BWD=1 and the
    benchmark baseline): materializes the stacked mask, m⊙w and xᵀ@g
    at full (E, K, N) size."""
    dx = masked_matmul_grouped_dx(g, w, s, seeds, offs, tile_group,
                                  n_live, mode, tau)
    ds = masked_matmul_grouped_ds(x, g, w, s, tile_group, n_live)
    return dx, ds


def masked_conv1d(x, w, s, seed, off=0, mode="sample", tau=0.5):
    """Oracle for kernels.masked_conv1d: depthwise causal conv with the
    hash-stream masked (W, C) kernel, accumulated tap-by-tap in the
    SAME order as the Pallas kernel (bit-identical f32 sums).
    x: (B, S, C) unpadded; returns f32 (B, S, C)."""
    Wt = w.shape[0]
    S = x.shape[1]
    m = (threshold_mask(s, tau) if mode == "threshold"
         else sample_mask(s, seed, off))
    wm = (m.astype(w.dtype) * w).astype(jnp.float32)
    xp = jnp.pad(x, ((0, 0), (Wt - 1, 0), (0, 0)))
    out = xp[:, 0:S].astype(jnp.float32) * wm[0]
    for t in range(1, Wt):
        out = out + xp[:, t:t + S].astype(jnp.float32) * wm[t]
    return out


def masked_conv1d_bwd(x, w, s, seed, g, off=0, mode="sample", tau=0.5):
    """Naive STE backward of the masked depthwise causal conv
    (REPRO_REF_BWD=1 escape hatch): dx is the flipped-tap correlation
    of g with m⊙w, ds = (xᵀ★g) ⊙ w ⊙ σ'(s) at kernel size."""
    Wt = w.shape[0]
    S = x.shape[1]
    m = (threshold_mask(s, tau) if mode == "threshold"
         else sample_mask(s, seed, off))
    wm = (m.astype(w.dtype) * w).astype(jnp.float32)
    gp = jnp.pad(g, ((0, 0), (0, Wt - 1), (0, 0))).astype(jnp.float32)
    dx = gp[:, 0:S] * wm[Wt - 1]
    for u in range(1, Wt):
        dx = dx + gp[:, u:u + S] * wm[Wt - 1 - u]
    xp = jnp.pad(x, ((0, 0), (Wt - 1, 0), (0, 0))).astype(jnp.float32)
    gf = g.astype(jnp.float32)
    xg = jnp.stack([jnp.sum(xp[:, t:t + S] * gf, axis=(0, 1))
                    for t in range(Wt)])
    sig = jax.nn.sigmoid(s.astype(jnp.float32))
    ds = (xg * w.astype(jnp.float32) * sig * (1.0 - sig)).astype(s.dtype)
    return dx.astype(x.dtype), ds


def sample_rows(s2, seeds):
    """(C, n) score rows + (C,) seeds -> (C, n) uint8 Bernoulli masks.

    Row c is sampled from the flat-index hash stream with seeds[c] —
    bit-identical to what kernels.sample_and_pack packs."""
    _, n = s2.shape
    idx = jnp.arange(n, dtype=jnp.uint32)

    def one(row, seed):
        u = hash_uniform(idx, seed)
        return (u < jax.nn.sigmoid(row.astype(jnp.float32))).astype(
            jnp.uint8)

    return jax.vmap(one)(s2, jnp.asarray(seeds, jnp.uint32))


def threshold_rows(s2, tau=0.5):
    """(C, n) score rows -> (C, n) uint8 deterministic FedMask masks."""
    return (jax.nn.sigmoid(s2.astype(jnp.float32))
            > jnp.asarray(tau, jnp.float32)).astype(jnp.uint8)


def sample_and_pack(s2, seeds, mode="sample", tau=0.5):
    """Oracle for kernels.sample_and_pack: the two-pass sample-then-pack
    it fuses.  (C, n) scores -> (C, ceil(n/32)) uint32 words."""
    m = (threshold_rows(s2, tau) if mode == "threshold"
         else sample_rows(s2, seeds))
    n = m.shape[1]
    pad = (-n) % 32
    if pad:
        m = jnp.pad(m, ((0, 0), (0, pad)))
    return jax.vmap(pack_bits)(m)


def sample_and_pack_leaf(s3, seeds, mode="sample", tau=0.5, n=None):
    """Oracle for kernels.sample_and_pack_leaf: (C, R, N) scores, N % 32
    == 0 -> (C, R, N // 32) words, the flattened rows' words by row;
    with `n`, those of the first n elements, zero past them."""
    C, R, N = s3.shape
    n = R * N if n is None else n
    words = sample_and_pack(s3.reshape(C, R * N)[:, :n], seeds, mode=mode,
                            tau=tau)
    words = jnp.pad(words, ((0, 0), (0, R * N // 32 - words.shape[1])))
    return words.reshape(C, R, N // 32)


def pack_bits(mask_flat):
    bits = mask_flat.astype(jnp.uint32).reshape(-1, 32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(bits << shifts, axis=1).astype(jnp.uint32)


def unpack_bits(words, n):
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[:, None] >> shifts) & jnp.uint32(1)
    return bits.reshape(-1)[:n].astype(jnp.uint8)
