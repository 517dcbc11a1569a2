"""Jit'd public wrappers around the Pallas kernels.

`masked_dense` is the drop-in for the mask-training forward on a Dense
layer, with the STE custom-vjp.  Forward AND backward run fused:

    y     = x @ (m*w)                        [masked_matmul]
    dL/dx = g @ (m*w)^T                      [masked_matmul_dx]
    dL/ds = (x^T @ g) * w * sigmoid'(s)      [masked_matmul_ds]

The mask is never materialized in HBM on either pass: the backward
regenerates it per tile from the same counter-based hash stream as the
forward (bit-identical — asserted in tests/test_kernels.py).  The `off`
argument shifts the flat hash index so a layer-stacked (L, K, N) leaf
executed as L per-layer launches (off = l*K*N) samples exactly the
stream `sample_and_pack` packs for the flattened leaf — this is how the
model zoo's `MaskedLeaf` execution path (repro.models.layers) and the
uplink share one stream (docs/DESIGN.md §3).

`masked_dense_threshold` is the deterministic FedMask twin: the mask is
m = 1[sigmoid(s) > tau] (no hash), same STE backward, same fusion.

`masked_dense_grouped` (+ `_threshold`) is the stacked-leaf twin for
(E, K, N) MoE expert weights: ONE grouped pallas_call per projection
covers the group-contiguous rows of all E experts (groups of varying
length, laid out by `group_layout`, blocked by `grouped_plan`) with
per-group seed/off stream coordinates (offs[e] = e*K*N under the
`MaskedLeaf.build` convention), so the stacked m⊙w never exists in
HBM either.  `masked_conv1d`
(+ `_threshold`) covers the depthwise causal (W, C) conv kernel leaves,
and `conv1d_plain` is its mask-free twin for pre-materialized weights —
the reference path runs it so fused and materialized convs are
instruction-identical (bit-equal), and neither builds the old
(B, S, W, C) stacked-views tensor.

MXU-unaligned shapes are zero-padded up to lane (128) alignment before
the kernel launch instead of silently falling back to the jnp reference:
the hash is indexed by the LOGICAL column count (`n_logical`), so the
padded launch samples exactly the same mask, and padded columns carry
w == 0 so they contribute nothing.

`sample_and_pack` fuses the per-round uplink sampling with the 32->1
bitpack (scores -> hash -> Bernoulli -> uint32 words in one pass).

Environment knobs (documented in README "Execution paths"):
  * REPRO_REF_BWD=1        — naive jnp STE backward (debug baseline)
  * REPRO_FORCE_INTERPRET=1 — pin Pallas interpret mode on any backend
  * REPRO_EFF_PATH=1       — read by repro.launch.steps: train through
    materialized effective params instead of the fused kernels

On the CPU backend the wrappers call the kernels in interpret mode —
selected once per process by `_use_interpret()`.  Every other backend
compiles them with Mosaic: there is no silent fallback, so a backend
the kernels cannot compile for fails loudly instead of being emulated.
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import masked_matmul as _mm
from repro.kernels import bitpack as _bp
from repro.kernels import ref


def repro_backend() -> str:
    return jax.default_backend()


@functools.lru_cache(maxsize=1)
def _use_interpret() -> bool:
    """Interpret the kernels on the CPU backend only.  Cached per
    process: `jax.default_backend()` walks the backend registry, which
    is pure overhead when re-queried inside every jit trace.
    `REPRO_FORCE_INTERPRET=1` pins interpret mode on any backend."""
    if os.environ.get("REPRO_FORCE_INTERPRET", "") == "1":
        return True
    return repro_backend() == "cpu"


def reset_backend_cache() -> None:
    """Drop the cached `_use_interpret()` decision so a mid-process
    flip of `REPRO_FORCE_INTERPRET` (or a swapped backend) takes
    effect — without this the flip is silently ignored for the rest of
    the process.  Call it from any test/bench fixture that toggles the
    knob (tests/conftest.py `kernel_backend_reset`,
    benchmarks/kernels_bench.py main)."""
    _use_interpret.cache_clear()


def pack_bits(mask_flat: jax.Array) -> jax.Array:
    if mask_flat.size % 32:
        pad = 32 - mask_flat.size % 32
        mask_flat = jnp.concatenate(
            [mask_flat, jnp.zeros((pad,), mask_flat.dtype)])
    return _bp.pack_bits(mask_flat, interpret=_use_interpret())


def unpack_bits(words: jax.Array, n: int) -> jax.Array:
    return _bp.unpack_bits(words, n, interpret=_use_interpret())


def sample_and_pack(scores: jax.Array, seeds: jax.Array,
                    mode: str = "sample", tau: float = 0.5) -> jax.Array:
    """Fused uplink sampler: (C, n) score rows + (C,) uint32 seeds ->
    (C, ceil(n/32)) uint32 words of m ~ Bern(sigmoid(scores)).

    One kernel pass replaces the sample-then-pack_bits two-pass; the
    full uint8 mask never exists in HBM.  `ref.sample_rows` /
    `ref.sample_and_pack` are the bit-exact jnp oracles.
    `mode="threshold"` packs m = 1[sigmoid(scores) > tau] (FedMask).
    """
    return _mm.sample_and_pack(scores, seeds, interpret=_use_interpret(),
                               mode=mode, tau=tau)


# ---------------------------------------------------------------------------
# Padding to MXU alignment (keeps the hash indexed by logical shape)
# ---------------------------------------------------------------------------


def _round_up(d: int, m: int) -> int:
    return -(-d // m) * m


def _block_for(dp: int) -> int:
    """Largest MXU-friendly block (multiple of 128, <= 512) dividing the
    padded dim."""
    for b in (512, 256, 128):
        if dp % b == 0:
            return b
    raise AssertionError(dp)  # dp is always a multiple of 128


def _pad2(a: jax.Array, r: int, c: int) -> jax.Array:
    pr, pc = r - a.shape[0], c - a.shape[1]
    if pr == 0 and pc == 0:
        return a
    return jnp.pad(a, ((0, pr), (0, pc)))


class DensePlan(NamedTuple):
    """Blocks of one forward or dx launch.  `passes` = Mp // bm is how
    many times each (k, n) tile of `w` and `s` streams HBM->VMEM, and
    its mask is drawn, per call."""
    bm: int
    bn: int
    bk: int
    passes: int


def _dense_vmem_bytes(bm: int, bn: int, bk: int) -> int:
    """VMEM working set of one forward or dx grid step, every operand
    counted at 4 B (callers pass bf16 or f32).  The forward reads an
    (bm, bk) activation block and writes (bm, bn); dx the other way
    round, so both hold bm*(bk+bn) activation elements per buffer:
    double-buffered in and out blocks (2x), the f32 accumulator and the
    f32 cast of the in block (1x); and bk*bn tile elements: double-
    buffered `w` and `s` (4x), the body's f32 sigmoid and m*w (2x)."""
    act, tile = bm * (bk + bn) * 4, bk * bn * 4
    return 3 * act + 6 * tile


def dense_plan(M: int, K: int, N: int) -> DensePlan:
    """Blocks for an (M, K) x (K, N) masked matmul, padded to 128.
    bn and bk are `_block_for` the padded N and K; bm is the largest
    multiple of 128 dividing Mp whose working set fits the kernels'
    VMEM limit (`masked_matmul.VMEM_BUDGET`).  So the whole token count
    rides one block (passes == 1) wherever it fits, and only very long
    calls (im2col convs) stream `w` and `s` more than once."""
    Mp, Kp, Np = (_round_up(M, 128), _round_up(K, 128),
                  _round_up(N, 128))
    bn, bk = _block_for(Np), _block_for(Kp)
    bm = next(b for b in range(Mp, 0, -128) if Mp % b == 0
              and _dense_vmem_bytes(b, bn, bk) <= _mm.VMEM_BUDGET)
    return DensePlan(bm, bn, bk, Mp // bm)


def _fused_fwd(x, w, s, seed, off, tau, mode):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    M = x2.shape[0]
    K, N = w.shape
    Mp, Kp, Np = (_round_up(M, 128), _round_up(K, 128),
                  _round_up(N, 128))
    plan = dense_plan(M, K, N)
    y = _mm.masked_matmul(
        _pad2(x2, Mp, Kp), _pad2(w, Kp, Np), _pad2(s, Kp, Np), seed,
        off, bm=plan.bm, bn=plan.bn, bk=plan.bk, n_logical=N,
        interpret=_use_interpret(), mode=mode, tau=tau)[:M, :N]
    return y.reshape(shape[:-1] + (N,))


def _fused_bwd(x, w, s, seed, off, tau, mode, g):
    K, N = w.shape
    if os.environ.get("REPRO_REF_BWD", "") == "1":
        if mode == "threshold":
            m = ref.threshold_mask(s, tau).astype(jnp.float32)
            wf = w.astype(jnp.float32)
            g2 = g.reshape(-1, N)
            dx = (g2 @ (m * wf).T).reshape(x.shape).astype(x.dtype)
            ds = ref.masked_matmul_ds(x.reshape(-1, K), g2, w, s)
            return dx, ds
        return ref.masked_dense_bwd(x, w, s, seed, g, off)
    x2 = x.reshape(-1, K)
    g2 = g.reshape(-1, N)
    M = x2.shape[0]
    Mp, Kp, Np = (_round_up(M, 128), _round_up(K, 128),
                  _round_up(N, 128))
    plan = dense_plan(M, K, N)
    interp = _use_interpret()
    xp, gp = _pad2(x2, Mp, Kp), _pad2(g2, Mp, Np)
    wp, sp = _pad2(w, Kp, Np), _pad2(s, Kp, Np)
    dx = _mm.masked_matmul_dx(gp, wp, sp, seed, off, bm=plan.bm,
                              bn=plan.bn, bk=plan.bk, n_logical=N,
                              interpret=interp, mode=mode,
                              tau=tau)[:M, :K]
    # ds keeps bm=128: its grid has m innermost, so it streams each
    # tile of w and s once per call already
    ds = _mm.masked_matmul_ds(xp, gp, wp, sp, bm=128, bn=plan.bn,
                              bk=plan.bk, interpret=interp)[:K, :N]
    return (dx.reshape(x.shape).astype(x.dtype), ds.astype(s.dtype))


@jax.custom_vjp
def _masked_dense(x, w, s, seed, off):
    return _fused_fwd(x, w, s, seed, off, 0.5, "sample")


def _md_fwd(x, w, s, seed, off):
    return _masked_dense(x, w, s, seed, off), (x, w, s, seed, off)


def _md_bwd(res, g):
    x, w, s, seed, off = res
    dx, ds = _fused_bwd(x, w, s, seed, off, 0.5, "sample", g)
    return dx, None, ds, None, None


_masked_dense.defvjp(_md_fwd, _md_bwd)


@jax.custom_vjp
def _masked_dense_thr(x, w, s, tau):
    return _fused_fwd(x, w, s, 0, 0, tau, "threshold")


def _mdt_fwd(x, w, s, tau):
    return _masked_dense_thr(x, w, s, tau), (x, w, s, tau)


def _mdt_bwd(res, g):
    x, w, s, tau = res
    dx, ds = _fused_bwd(x, w, s, 0, 0, tau, "threshold", g)
    return dx, None, ds, None


_masked_dense_thr.defvjp(_mdt_fwd, _mdt_bwd)


def masked_dense(x, w, s, seed, off=0):
    """y = x @ (bern(sigmoid(s); seed) * w), STE backward. x: (..., K).

    `off` shifts the flat hash index: per-layer launches over a stacked
    (L, K, N) leaf pass off = l*K*N so the L masks together are exactly
    the leaf's flat `sample_and_pack` stream under the same seed.
    """
    return _masked_dense(x, w, s, jnp.asarray(seed, jnp.uint32),
                         jnp.asarray(off, jnp.uint32))


def masked_dense_threshold(x, w, s, tau=0.5):
    """y = x @ (1[sigmoid(s) > tau] * w), STE backward (FedMask mode).

    Deterministic twin of `masked_dense`: no hash stream, same fused
    kernels and the same ds epilogue (STE passes d m/d theta := 1
    through the threshold exactly as through the Bernoulli sample).
    """
    return _masked_dense_thr(x, w, s, jnp.asarray(tau, jnp.float32))


# ---------------------------------------------------------------------------
# Grouped masked dense: stacked (E, K, N) weights over groups of rows
# ---------------------------------------------------------------------------


class GroupLayout(NamedTuple):
    """Where each group's rows sit in a group-contiguous row buffer of
    `tiles` row tiles.  Group e owns max(1, ceil(size_e / bm)) whole
    tiles from row `starts[e]`, in group order; the tiles past `n_live`
    belong to no group (their `tile_group` is the last group's, so a
    kernel clamping to the last live tile sees no change)."""
    tile_group: jax.Array   # (tiles,) int32
    n_live: jax.Array       # (1,) int32
    starts: jax.Array       # (E,) int32


def group_layout(sizes: jax.Array, bm: int, tiles: int) -> GroupLayout:
    """The layout of groups of `sizes` (E,) rows in row tiles of `bm`.
    A buffer of `tiles` tiles holds it whenever tiles >=
    sum(sizes) // bm + E (the bound `grouped_plan` gives): each group
    rounds its rows up by less than one tile, an empty one to one."""
    E = sizes.shape[0]
    nt = jnp.maximum(1, -(-sizes.astype(jnp.int32) // bm))
    ends = jnp.cumsum(nt)
    t = jnp.arange(tiles, dtype=jnp.int32)
    tg = jnp.minimum(jnp.sum(ends[None, :] <= t[:, None], axis=1), E - 1)
    return GroupLayout(tg.astype(jnp.int32), ends[-1:].astype(jnp.int32),
                       ((ends - nt) * bm).astype(jnp.int32))


class GroupedPlan(NamedTuple):
    """Blocks of one grouped launch over a buffer of `tiles` row tiles
    of `bm` rows; `live` is how many of them hold rows when every group
    holds the mean load: each live tile streams its group's (K, N)
    tiles of `w` and `s` once, so live / groups is the grouped twin of
    `DensePlan.passes`."""
    bm: int
    bn: int
    bk: int
    tiles: int
    live: int


def _grouped_block(dp: int) -> int:
    """512 where it divides the padded dim, else the whole padded dim up
    to 1536 (an expert width of 1408 = 11 x 128 then takes one block,
    not eleven), else `_block_for`."""
    if dp % 512 and dp <= 1536:
        return dp
    return _block_for(dp)


def grouped_plan(rows: int, K: int, N: int, groups: int,
                 mean: float) -> GroupedPlan:
    """Blocks for up to `rows` routed rows in `groups` groups of `mean`
    rows on average, through (K, N) expert weights.  bm is the largest
    of 512, 256, 128 that is not above the mean load (128 at least) and
    whose working set fits `masked_matmul.VMEM_BUDGET`: a group pads by
    less than a tile, and a tile of `w` and `s` streams once per live
    tile.  The static tile bound is rows // bm + groups."""
    bk, bn = _grouped_block(_round_up(K, 128)), \
        _grouped_block(_round_up(N, 128))
    fits = [b for b in (512, 256) if b <= mean
            and _dense_vmem_bytes(b, bn, bk) <= _mm.VMEM_BUDGET]
    bm = fits[0] if fits else 128
    live = groups * max(1, -(-int(mean) // bm))
    return GroupedPlan(bm, bn, bk, rows // bm + groups, live)


def _pad3(a: jax.Array, k: int, n: int) -> jax.Array:
    """Zero-pad the trailing two axes of a stacked (E, K, N) leaf."""
    pk, pn = k - a.shape[1], n - a.shape[2]
    if pk == 0 and pn == 0:
        return a
    return jnp.pad(a, ((0, 0), (0, pk), (0, pn)))


def _grp_blocks(w):
    K, N = w.shape[-2:]
    Kp, Np = _round_up(K, 128), _round_up(N, 128)
    return K, N, Kp, Np, _grouped_block(Kp), _grouped_block(Np)


def _grp_fused_fwd(x, w, s, seeds, offs, tg, nl, tau, mode):
    R = x.shape[0]
    K, N, Kp, Np, bk, bn = _grp_blocks(w)
    y = _mm.masked_matmul_grouped(
        _pad2(x, R, Kp), _pad3(w, Kp, Np), _pad3(s, Kp, Np), seeds, offs,
        tg, nl, bm=R // tg.shape[0], bn=bn, bk=bk, n_logical=N,
        interpret=_use_interpret(), mode=mode, tau=tau)
    return y[:, :N]


def _grp_fused_bwd(x, w, s, seeds, offs, tg, nl, tau, mode, g):
    if os.environ.get("REPRO_REF_BWD", "") == "1":
        dx, ds = ref.masked_dense_grouped_bwd(x, w, s, seeds, offs, g, tg,
                                              nl, mode, tau)
        return dx.astype(x.dtype), ds
    R = x.shape[0]
    K, N, Kp, Np, bk, bn = _grp_blocks(w)
    bm = R // tg.shape[0]
    interp = _use_interpret()
    xp, gp = _pad2(x, R, Kp), _pad2(g, R, Np)
    wp, sp = _pad3(w, Kp, Np), _pad3(s, Kp, Np)
    dx = _mm.masked_matmul_grouped_dx(
        gp, wp, sp, seeds, offs, tg, nl, bm=bm, bn=bn, bk=bk,
        n_logical=N, interpret=interp, mode=mode, tau=tau)[:, :K]
    ds = _mm.masked_matmul_grouped_ds(
        xp, gp, wp, sp, tg, nl, bm=bm, bn=bn, bk=bk,
        interpret=interp)[:, :K, :N]
    return dx.astype(x.dtype), ds.astype(s.dtype)


@jax.custom_vjp
def _masked_dense_grouped(x, w, s, seeds, offs, tg, nl):
    return _grp_fused_fwd(x, w, s, seeds, offs, tg, nl, 0.5, "sample")


def _mdg_fwd(x, w, s, seeds, offs, tg, nl):
    return (_masked_dense_grouped(x, w, s, seeds, offs, tg, nl),
            (x, w, s, seeds, offs, tg, nl))


def _mdg_bwd(res, g):
    x, w, s, seeds, offs, tg, nl = res
    dx, ds = _grp_fused_bwd(x, w, s, seeds, offs, tg, nl, 0.5, "sample",
                            g)
    return dx, None, ds, None, None, None, None


_masked_dense_grouped.defvjp(_mdg_fwd, _mdg_bwd)


@jax.custom_vjp
def _masked_dense_grouped_thr(x, w, s, tau, tg, nl):
    zeros = jnp.zeros((w.shape[0],), jnp.uint32)
    return _grp_fused_fwd(x, w, s, zeros, zeros, tg, nl, tau,
                          "threshold")


def _mdgt_fwd(x, w, s, tau, tg, nl):
    return (_masked_dense_grouped_thr(x, w, s, tau, tg, nl),
            (x, w, s, tau, tg, nl))


def _mdgt_bwd(res, g):
    x, w, s, tau, tg, nl = res
    zeros = jnp.zeros((w.shape[0],), jnp.uint32)
    dx, ds = _grp_fused_bwd(x, w, s, zeros, zeros, tg, nl, tau,
                            "threshold", g)
    return dx, None, ds, None, None, None


_masked_dense_grouped_thr.defvjp(_mdgt_fwd, _mdgt_bwd)


def masked_dense_grouped(x, w, s, seeds, offs, layout: GroupLayout):
    """y[r] = x[r] @ (bern(sigmoid(s[e]); seeds[e], offs[e]) * w[e]) for
    the rows r of group e, STE backward.  x: (tiles * bm, K) rows laid
    out by `layout` (`group_layout`); w, s: (E, K, N).  Rows of dead
    tiles come back unwritten, and their cotangents are never read.

    One `pallas_call` covers all E groups with per-group `seeds`/`offs`
    stream coordinates (offs None: e*K*N, the `MaskedLeaf.build`
    convention), so the E masks together are exactly the stacked
    leaf's flat `sample_and_pack` stream.  MXU-unaligned K/N are
    zero-padded with the hash indexed by the logical column count, as
    in `masked_dense`.
    """
    E = w.shape[0]
    seeds = jnp.broadcast_to(jnp.asarray(seeds, jnp.uint32), (E,))
    if offs is None:
        K, N = w.shape[-2:]
        offs = jnp.arange(E, dtype=jnp.uint32) * jnp.uint32(K * N)
    offs = jnp.broadcast_to(jnp.asarray(offs, jnp.uint32), (E,))
    return _masked_dense_grouped(x, w, s, seeds, offs, layout.tile_group,
                                 layout.n_live)


def masked_dense_grouped_threshold(x, w, s, layout: GroupLayout,
                                   tau=0.5):
    """`masked_dense_grouped` with m = 1[sigmoid(s[e]) > tau] (FedMask
    mode; no hash stream)."""
    return _masked_dense_grouped_thr(x, w, s,
                                     jnp.asarray(tau, jnp.float32),
                                     layout.tile_group, layout.n_live)


# ---------------------------------------------------------------------------
# Masked depthwise causal conv: the (W, C) kernel leaf, fully fused
# ---------------------------------------------------------------------------


def _conv_pads(w):
    Wt, C = w.shape
    Cp = _round_up(C, 128)
    return Wt, C, Cp, min(_block_for(Cp), 128)


def _conv_fused_fwd(x, w, s, seed, off, tau, mode):
    B, S, C = x.shape
    Wt, _, Cp, bc = _conv_pads(w)
    xp = jnp.pad(x, ((0, 0), (Wt - 1, 0), (0, Cp - C)))
    wp, sp = _pad2(w, Wt, Cp), _pad2(s, Wt, Cp)
    y = _mm.masked_conv1d(xp, wp, sp, seed, off, bc=bc, n_logical=C,
                          interpret=_use_interpret(), mode=mode,
                          tau=tau)
    return y[:, :, :C]


def _conv_fused_bwd(x, w, s, seed, off, tau, mode, g):
    if os.environ.get("REPRO_REF_BWD", "") == "1":
        return ref.masked_conv1d_bwd(x, w, s, seed, g, off, mode, tau)
    B, S, C = x.shape
    Wt, _, Cp, bc = _conv_pads(w)
    interp = _use_interpret()
    wp, sp = _pad2(w, Wt, Cp), _pad2(s, Wt, Cp)
    # dL/dx: correlation of g with the flipped masked taps — the same
    # kernel with trailing (instead of leading) zero padding
    gp = jnp.pad(g, ((0, 0), (0, Wt - 1), (0, Cp - C)))
    dx = _mm.masked_conv1d(gp, wp, sp, seed, off, bc=bc, n_logical=C,
                           interpret=interp, mode=mode, tau=tau,
                           flip=True)[:, :, :C]
    xp = jnp.pad(x, ((0, 0), (Wt - 1, 0), (0, Cp - C)))
    gp2 = jnp.pad(g, ((0, 0), (0, 0), (0, Cp - C)))
    ds = _mm.masked_conv1d_ds(xp, gp2, wp, sp, bc=bc,
                              interpret=interp)[:, :C]
    return dx.astype(x.dtype), ds.astype(s.dtype)


@jax.custom_vjp
def _masked_conv1d(x, w, s, seed, off):
    return _conv_fused_fwd(x, w, s, seed, off, 0.5, "sample")


def _mc_fwd(x, w, s, seed, off):
    return _masked_conv1d(x, w, s, seed, off), (x, w, s, seed, off)


def _mc_bwd(res, g):
    x, w, s, seed, off = res
    dx, ds = _conv_fused_bwd(x, w, s, seed, off, 0.5, "sample", g)
    return dx, None, ds, None, None


_masked_conv1d.defvjp(_mc_fwd, _mc_bwd)


@jax.custom_vjp
def _masked_conv1d_thr(x, w, s, tau):
    return _conv_fused_fwd(x, w, s, 0, 0, tau, "threshold")


def _mct_fwd(x, w, s, tau):
    return _masked_conv1d_thr(x, w, s, tau), (x, w, s, tau)


def _mct_bwd(res, g):
    x, w, s, tau = res
    dx, ds = _conv_fused_bwd(x, w, s, 0, 0, tau, "threshold", g)
    return dx, None, ds, None


_masked_conv1d_thr.defvjp(_mct_fwd, _mct_bwd)


def masked_conv1d(x, w, s, seed, off=0):
    """Depthwise causal conv through the masked (W, C) kernel leaf:
    y[b,s,c] = Σ_t x[b, s+t-(W-1), c] · (m ⊙ w)[t,c], STE backward.
    x: (B, S, C); returns f32 (B, S, C) (bias/cast stay with the
    caller).  The mask is drawn at flat index off + t*C + c — the
    leaf's uplink `sample_and_pack` stream — and is regenerated
    per-tile on both passes; m⊙w never exists in HBM."""
    return _masked_conv1d(x, w, s, jnp.asarray(seed, jnp.uint32),
                          jnp.asarray(off, jnp.uint32))


def masked_conv1d_threshold(x, w, s, tau=0.5):
    """Deterministic FedMask twin of `masked_conv1d`:
    m = 1[sigmoid(s) > tau], same fused kernels and STE backward."""
    return _masked_conv1d_thr(x, w, s, jnp.asarray(tau, jnp.float32))


@jax.custom_vjp
def conv1d_plain(x, w):
    """Depthwise causal conv with a PLAIN (pre-materialized) (W, C)
    kernel, through the same Pallas tap loop as `masked_conv1d` — so
    the reference path (effective params) and the fused masked path
    are instruction-identical and their f32 sums bit-equal.  Replaces
    the old (B, S, W, C) stacked-shifted-views einsum (a W× activation
    blowup).  x: (B, S, C); returns f32 (B, S, C).

    Float baselines also land here, which on non-TPU backends means
    interpret-mode emulation — a deliberate trade: depthwise convs are
    a sliver of model FLOPs (W ≈ 4 taps vs d² matmuls), non-TPU runs
    are smoke-scale, and the payoff is that the fused-vs-materialized
    path equivalence stays bit-exact on every backend."""
    B, S, C = x.shape
    Wt, _, Cp, bc = _conv_pads(w)
    xp = jnp.pad(x, ((0, 0), (Wt - 1, 0), (0, Cp - C)))
    wp = _pad2(w, Wt, Cp)
    # wp doubles as the (unread) score operand: plain mode never
    # touches s_ref, so no extra weight-sized tensor is shipped
    return _mm.masked_conv1d(xp, wp, wp, 0, 0, bc=bc, n_logical=C,
                             interpret=_use_interpret(),
                             mode="plain")[:, :, :C]


def _cp_fwd(x, w):
    return conv1d_plain(x, w), (x, w)


def _cp_bwd(res, g):
    x, w = res
    B, S, C = x.shape
    Wt, _, Cp, bc = _conv_pads(w)
    interp = _use_interpret()
    wp = _pad2(w, Wt, Cp)
    gp = jnp.pad(g, ((0, 0), (0, Wt - 1), (0, Cp - C)))
    dx = _mm.masked_conv1d(gp, wp, wp, 0, 0, bc=bc, n_logical=C,
                           interpret=interp, mode="plain",
                           flip=True)[:, :, :C]
    xp = jnp.pad(x, ((0, 0), (Wt - 1, 0), (0, Cp - C)))
    gp2 = jnp.pad(g, ((0, 0), (0, 0), (0, Cp - C)))
    dw = _mm.masked_conv1d_ds(xp, gp2, wp, wp, bc=bc, interpret=interp,
                              epilogue="dw")[:, :C]
    return dx.astype(x.dtype), dw.astype(w.dtype)


conv1d_plain.defvjp(_cp_fwd, _cp_bwd)
