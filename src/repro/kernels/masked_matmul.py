"""Fused masked matmul Pallas kernels — the mask-training hot spot.

Forward:   y  = x @ (m ⊙ w),   m = 1[u < sigmoid(s)],  u = hash(seed, idx)

in ONE pass: each (k, n) tile of `w` and `s` streams HBM->VMEM, and
its Bernoulli mask is formed in VMEM/VREGs from a counter-based hash
(no RNG state, no mask tensor in HBM), once per token block of `bm`
rows; the gated tile feeds the MXU.  `ops.dense_plan` makes that once
per call: it takes `bm` as the whole padded token count wherever the
working set fits its VMEM budget.

Backward (STE, see ops.py): two more kernels with the same property —

  masked_matmul_dx:  dx = g @ (m ⊙ w)ᵀ     mask regenerated per tile
                                            from the SAME hash stream,
                                            bit-identical to the forward
  masked_matmul_ds:  ds = (xᵀ@g) ⊙ w ⊙ σ(s)(1−σ(s))
                                            the (K,N)-sized xᵀ@g product
                                            and the sigmoid never leave
                                            VMEM

and a fused uplink sampler —

  sample_and_pack:   scores -> hash -> Bernoulli -> packed uint32 words
                     in one pass (replaces sample-then-pack_bits, which
                     materialized the full uint8 mask in HBM); it
                     reads a leaf as (C, R, N) rows of whole words,
                     in the leaf's own layout where it has such rows
                     (`sample_and_pack_leaf`).

The GROUPED family extends the same discipline to stacked (E, K, N)
leaves (MoE expert weights): `masked_matmul_grouped` (+ dx/ds) runs one
pallas_call over the group-contiguous rows of all E groups (groups of
varying length, each padded to whole row tiles; a tile->group map and
the live tile count are scalar-prefetch operands, and tiles past the
live ones do no work), and each group carries its own `seed`/`off`
scalar operands, so group e's mask is drawn at flat offset e*K*N of
the leaf's uplink stream.  The CONV
family (`masked_conv1d`, `masked_conv1d_ds`) covers the depthwise
causal (W, C) kernel leaves (mamba2 / recurrentgemma frontends), where
the W-tap reduction is elementwise per channel and unrolled in-kernel;
`mode="plain"` is the mask-free twin the reference path runs on
pre-materialized weights, keeping both paths instruction-identical
(bit-equal f32 sums under FMA fusion).

Naive XLA: materialize sigmoid(s) (f32), u (f32), m*w (bf16) — three
extra weight-sized HBM tensors per step, and the backward repeats all
three plus xᵀ@g. These kernels eliminate every weight-sized temporary;
benchmarks/kernels_bench.py asserts the structural win by counting
weight-shaped f32 definitions in the lowered HLO.

The hash is xorshift-multiply (splitmix-like) over the *global* element
index, so the sampled mask is identical regardless of tiling — ref.py
reproduces it with pure jnp for the allclose oracle.  `n_logical` lets a
caller zero-pad operands to MXU alignment while keeping the hash indexed
by the LOGICAL column count, so padded and unpadded launches sample
bit-identical masks (padding columns carry w == 0 and contribute
nothing).  The `off` operand shifts the flat hash index: a layer-stacked
(L, K, N) leaf sampled through per-layer kernel launches with
off = l*K*N draws exactly the bits `sample_and_pack` packs for the full
flattened leaf — the model-forward masks and the uplink stream are one
stream (docs/DESIGN.md §3).

`mode="threshold"` swaps the Bernoulli draw for the deterministic
FedMask predicate m = 1[sigmoid(s) > tau] (tau rides as a runtime
scalar operand, so no retrace per tau); the hash/seed/off operands are
ignored in that mode.

Blocks: bn and bk are 128, 256 or 512 (MXU-aligned).  The forward and
dx grids put the token axis outermost, so a tile of `w` and `s` is
fetched once per token block: `ops.dense_plan` picks bm as the largest
multiple of 128 dividing the padded token count whose working set fits
`VMEM_BUDGET` (32 MiB; every operand counted at 4 B: the double-
buffered activation, output, `w` and `s` blocks, the f32 accumulator
and the body's f32 temporaries, 12*bm*(bk+bn) + 24*bk*bn bytes, 18 MiB
at bm = 1024, bk = bn = 512), and compiles the kernels with that
budget as their scoped VMEM limit.  The ds kernel's grid puts the
token axis innermost, so it reads each tile once at bm = 128.  The
grouped kernels take their blocks from `ops.grouped_plan` (bk or bn may
be a whole padded dim up to 1536) under the same limit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _hash_uniform(idx: jax.Array, seed) -> jax.Array:
    """Counter-based uniform in [0,1): splitmix32-style avalanche of the
    global element index. uint32 ops only (TPU-friendly).

    The seed is avalanched separately and injected a second time in the
    middle of the pipeline, so two seeds never yield index-shifted
    copies of one stream (a purely additive seed would: stream offsets
    only ~8M apart would overlap for >8M-element leaves)."""
    s = jnp.asarray(seed, jnp.uint32) + jnp.uint32(1)
    s = (s ^ (s >> 16)) * jnp.uint32(0x45D9F3B5)
    s = s ^ (s >> 11)
    x = idx.astype(jnp.uint32) + jnp.uint32(0x9E3779B9) * s
    x = (x ^ (x >> 16)) * jnp.uint32(0x85EBCA6B)
    x = (x ^ s ^ (x >> 13)) * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    # 24-bit mantissa -> [0, 1); through int32 because Mosaic has no
    # uint32 -> f32 cast (exact: the value is < 2^24)
    return ((x >> 8).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(1.0 / (1 << 24)))


def _tile_mask_vals(s_tile, seed, off, tau, *, row0, col0,
                    n_total: int, mode: str):
    """Bernoulli (hash-stream) or threshold mask for one 2-D score tile
    (the value-level core shared by the dense, grouped, and conv
    kernels; `seed`/`off`/`tau` are scalars already read from refs)."""
    theta = jax.nn.sigmoid(s_tile.astype(jnp.float32))
    if mode == "threshold":
        return theta > tau
    bk, bn = s_tile.shape
    rows = row0 + jax.lax.broadcasted_iota(jnp.uint32, (bk, bn), 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.uint32, (bk, bn), 1)
    idx = off + rows * jnp.uint32(n_total) + cols
    return _hash_uniform(idx, seed) < theta


def _tile_mask(s_ref, seed_ref, off_ref, tau_ref, *, row0, col0,
               bk: int, bn: int, n_total: int, mode: str):
    """Bernoulli (hash-stream) or threshold mask for one (bk, bn) tile."""
    del bk, bn  # implied by the ref block shape
    return _tile_mask_vals(s_ref[...], seed_ref[0, 0], off_ref[0, 0],
                           tau_ref[0, 0], row0=row0, col0=col0,
                           n_total=n_total, mode=mode)


def _kernel(x_ref, w_ref, s_ref, seed_ref, off_ref, tau_ref, o_ref,
            acc_ref, *, bk: int, bn: int, n_total: int, nk: int,
            mode: str):
    k_i = pl.program_id(2)

    @pl.when(k_i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # global element indices of this (bk, bn) tile of w/s
    n_i = pl.program_id(1)
    m = _tile_mask(s_ref, seed_ref, off_ref, tau_ref,
                   row0=k_i * jnp.uint32(bk), col0=n_i * jnp.uint32(bn),
                   bk=bk, bn=bn, n_total=n_total, mode=mode)
    wm = jnp.where(m, w_ref[...].astype(jnp.float32), 0.0)
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), wm,
                            preferred_element_type=jnp.float32)

    @pl.when(k_i == nk - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _scalar_operands(seed, off, tau):
    return (jnp.asarray(seed, jnp.uint32).reshape(1, 1),
            jnp.asarray(off, jnp.uint32).reshape(1, 1),
            jnp.asarray(tau, jnp.float32).reshape(1, 1))


# VMEM that `ops.dense_plan` sizes the forward and dx blocks against,
# and the scoped limit both kernels are compiled with (Mosaic's default
# is 16 MiB; a v5e core has 128 MiB).
VMEM_BUDGET = 32 * 2**20
_DENSE_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_BUDGET)

# Scalar operands live whole in SMEM as (1, n) arrays.  The leading unit
# axis is what keeps them legal under `vmap` (the cohort axis of the
# train step): batching prepends a squeezed block dim, and Mosaic only
# accepts that when the trailing two block dims equal the array's.
_SCALAR_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)
_SCALAR_SPECS = [_SCALAR_SPEC] * 3


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk",
                                             "n_logical", "interpret",
                                             "mode"))
def masked_matmul(x: jax.Array, w: jax.Array, s: jax.Array,
                  seed: jax.Array, off: jax.Array = 0, *, bm: int = 128,
                  bn: int = 512, bk: int = 512,
                  n_logical: int | None = None, interpret: bool = False,
                  mode: str = "sample", tau: jax.Array = 0.5
                  ) -> jax.Array:
    """x: (M, K) bf16/f32; w, s: (K, N); seed/off: scalar uint32.
    Returns (M, N) in x.dtype.  `n_logical` overrides the column count
    used for the hash index (for zero-padded launches); `off` shifts the
    flat hash index (layer-stacked leaves).  `mode="threshold"` uses the
    deterministic m = 1[sigmoid(s) > tau] mask instead of the hash."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2 and s.shape == (K, N)
    n_total = N if n_logical is None else n_logical
    bm_, bn_, bk_ = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm_ == 0 and N % bn_ == 0 and K % bk_ == 0, \
        (M, N, K, bm_, bn_, bk_)
    nm, nn, nk = M // bm_, N // bn_, K // bk_

    grid = (nm, nn, nk)
    kernel = functools.partial(_kernel, bk=bk_, bn=bn_, n_total=n_total,
                               nk=nk, mode=mode)
    return pl.pallas_call(
        kernel,
        name="masked_matmul",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk_, bn_), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk_, bn_), lambda i, j, k: (k, j)),
        ] + _SCALAR_SPECS,
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        compiler_params=_DENSE_PARAMS,
        interpret=interpret,
    )(x, w, s, *_scalar_operands(seed, off, tau))


# ---------------------------------------------------------------------------
# Fused STE backward: dx = g @ (m*w)^T, mask regenerated per (k, n) tile
# ---------------------------------------------------------------------------


def _dx_kernel(g_ref, w_ref, s_ref, seed_ref, off_ref, tau_ref, o_ref,
               acc_ref, *, bk: int, bn: int, n_total: int, nn: int,
               mode: str):
    n_i = pl.program_id(2)

    @pl.when(n_i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # global element indices of this (bk, bn) tile of w/s — the same
    # row-major flat index the forward kernel hashes, so the regenerated
    # mask is bit-identical to the forward sample
    k_i = pl.program_id(1)
    m = _tile_mask(s_ref, seed_ref, off_ref, tau_ref,
                   row0=k_i * jnp.uint32(bk), col0=n_i * jnp.uint32(bn),
                   bk=bk, bn=bn, n_total=n_total, mode=mode)
    wm = jnp.where(m, w_ref[...].astype(jnp.float32), 0.0)   # (bk, bn)
    # contract over the n axis: (bm, bn) x (bk, bn) -> (bm, bk)
    acc_ref[...] += jax.lax.dot_general(
        g_ref[...].astype(jnp.float32), wm,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(n_i == nn - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk",
                                             "n_logical", "interpret",
                                             "mode"))
def masked_matmul_dx(g: jax.Array, w: jax.Array, s: jax.Array,
                     seed: jax.Array, off: jax.Array = 0, *,
                     bm: int = 128, bn: int = 512, bk: int = 512,
                     n_logical: int | None = None,
                     interpret: bool = False, mode: str = "sample",
                     tau: jax.Array = 0.5) -> jax.Array:
    """g: (M, N) upstream cotangent; w, s: (K, N).  Returns
    dx = g @ (m ⊙ w)ᵀ : (M, K) in g.dtype.

    The transposed access pattern gets its own grid/BlockSpec layout
    (accumulation runs over the n axis, innermost), not a reuse of the
    forward grid.  `off`/`mode`/`tau` as in `masked_matmul` — the
    regenerated mask is bit-identical to the forward's.
    """
    M, N = g.shape
    K, N2 = w.shape
    assert N == N2 and s.shape == (K, N)
    n_total = N if n_logical is None else n_logical
    bm_, bn_, bk_ = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm_ == 0 and N % bn_ == 0 and K % bk_ == 0, \
        (M, N, K, bm_, bn_, bk_)
    nm, nk, nn = M // bm_, K // bk_, N // bn_

    grid = (nm, nk, nn)
    kernel = functools.partial(_dx_kernel, bk=bk_, bn=bn_,
                               n_total=n_total, nn=nn, mode=mode)
    return pl.pallas_call(
        kernel,
        name="masked_matmul_dx",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bn_), lambda i, k, n: (i, n)),
            pl.BlockSpec((bk_, bn_), lambda i, k, n: (k, n)),
            pl.BlockSpec((bk_, bn_), lambda i, k, n: (k, n)),
        ] + _SCALAR_SPECS,
        out_specs=pl.BlockSpec((bm_, bk_), lambda i, k, n: (i, k)),
        out_shape=jax.ShapeDtypeStruct((M, K), g.dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bk_), jnp.float32)],
        compiler_params=_DENSE_PARAMS,
        interpret=interpret,
    )(g, w, s, *_scalar_operands(seed, off, tau))


# ---------------------------------------------------------------------------
# Fused STE backward: ds = (x^T @ g) * w * sigmoid'(s), single pass
# ---------------------------------------------------------------------------


def _ds_kernel(x_ref, g_ref, w_ref, s_ref, o_ref, acc_ref, *, nm: int):
    m_i = pl.program_id(2)

    @pl.when(m_i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # contract over the batch axis: (bm, bk) x (bm, bn) -> (bk, bn)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), g_ref[...].astype(jnp.float32),
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(m_i == nm - 1)
    def _():
        # elementwise epilogue in VMEM: neither x^T@g nor the sigmoid
        # ever exist at weight size in HBM
        sig = jax.nn.sigmoid(s_ref[...].astype(jnp.float32))
        o_ref[...] = (acc_ref[...] * w_ref[...].astype(jnp.float32)
                      * sig * (1.0 - sig)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk",
                                             "interpret"))
def masked_matmul_ds(x: jax.Array, g: jax.Array, w: jax.Array,
                     s: jax.Array, *, bm: int = 128, bn: int = 512,
                     bk: int = 512, interpret: bool = False) -> jax.Array:
    """x: (M, K); g: (M, N); w, s: (K, N).  Returns the STE score
    gradient ds = (xᵀ@g) ⊙ w ⊙ σ(s)(1−σ(s)) : (K, N) in s.dtype."""
    M, K = x.shape
    M2, N = g.shape
    assert M == M2 and w.shape == (K, N) and s.shape == (K, N)
    bm_, bn_, bk_ = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm_ == 0 and N % bn_ == 0 and K % bk_ == 0, \
        (M, N, K, bm_, bn_, bk_)
    nk, nn, nm = K // bk_, N // bn_, M // bm_

    grid = (nk, nn, nm)
    kernel = functools.partial(_ds_kernel, nm=nm)
    return pl.pallas_call(
        kernel,
        name="masked_matmul_ds",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda k, n, m: (m, k)),
            pl.BlockSpec((bm_, bn_), lambda k, n, m: (m, n)),
            pl.BlockSpec((bk_, bn_), lambda k, n, m: (k, n)),
            pl.BlockSpec((bk_, bn_), lambda k, n, m: (k, n)),
        ],
        out_specs=pl.BlockSpec((bk_, bn_), lambda k, n, m: (k, n)),
        out_shape=jax.ShapeDtypeStruct((K, N), s.dtype),
        scratch_shapes=[pltpu.VMEM((bk_, bn_), jnp.float32)],
        interpret=interpret,
    )(x, g, w, s)


# ---------------------------------------------------------------------------
# Fused uplink sampler: scores -> Bernoulli bits -> packed uint32 words
# ---------------------------------------------------------------------------


def _or_lanes(m, axis: int):
    """Pack {0,1} bits along `axis` (bit j from lane j) into uint32 words.
    The bits are disjoint, so their int32 sum equals their OR; Mosaic
    reduces int32 but not uint32, and the bitcast restores bit 31."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, m.shape, axis)
    words = jnp.sum(m.astype(jnp.int32) << lanes, axis=axis)
    return jax.lax.bitcast_convert_type(words, jnp.uint32)


# In place: a (C, R, N) leaf with N % 32 == 0 is read in its own lane-
# dense layout.  The Bernoulli bits of a row are formed on full vregs,
# and the MXU compacts each run of 32 lanes into a word: a {0,1} bf16
# chunk times a constant power-of-two matrix gives the word's two 16-bit
# halves, exact in f32 (each half-sum is below 2^16).  A group of 4096
# lanes makes the 128 words of one output vreg row; in its matrix, word
# w's low half lands in column w and its high half in column (w+64) %
# 128, so the first 64 words take one pass of the MXU, and the last 64
# a second pass whose halves a select and a 64-lane roll interleave.

_PACK_GROUP = 4096       # lanes whose words fill 128 output lanes
_PACK_CHUNK = 512        # lanes of one MXU pass (16 words)


def _pack_matrix() -> jax.Array:
    """(4096, 128) bf16: lane l of a group carries bit j = l % 32 of
    word w = l // 32, scaled 2^(j % 16), in column w (j < 16) or
    (w + 64) % 128 (j >= 16)."""
    lane = jnp.arange(_PACK_GROUP)
    w, j = lane // 32, lane % 32
    col = jnp.where(j < 16, w, (w + 64) % 128)
    val = jnp.left_shift(1, j % 16).astype(jnp.float32)
    return (jax.nn.one_hot(col, 128, dtype=jnp.float32)
            * val[:, None]).astype(jnp.bfloat16)


def _sap_leaf_kernel(s_ref, seed_ref, p_ref, o_ref, *, br: int, rs: int,
                     n_cols: int, n_valid: int | None, mode: str,
                     tau: float):
    c, i = pl.program_id(0), pl.program_id(1)
    seed = seed_ref[0, c]
    half = jax.lax.broadcasted_iota(jnp.int32, (rs, 128), 1) < 64

    def slab(r0):
        rows = (i * br + r0).astype(jnp.uint32) + jax.lax.broadcasted_iota(
            jnp.uint32, (rs, 1), 0)
        base = rows * jnp.uint32(n_cols)       # flat index of column 0
        for g0 in range(0, n_cols, _PACK_GROUP):
            width = min(_PACK_GROUP, n_cols - g0)
            acc = [None, None]                 # words 0..63, 64..127
            for c0 in range(0, width, _PACK_CHUNK):
                cw = min(_PACK_CHUNK, width - c0)
                theta = jax.nn.sigmoid(
                    s_ref[pl.ds(r0, rs), pl.ds(g0 + c0, cw)].astype(
                        jnp.float32))
                idx = base + (jnp.uint32(g0 + c0) + jax.lax.broadcasted_iota(
                    jnp.uint32, (1, cw), 1))
                if mode == "threshold":
                    m = theta > jnp.float32(tau)
                else:
                    m = _hash_uniform(idx, seed) < theta
                if n_valid is not None:
                    # the pad past a ragged leaf's n elements packs zeros,
                    # as pack_bits of the words-padded mask does
                    m = m & (idx < jnp.uint32(n_valid))
                part = jnp.dot(m.astype(jnp.bfloat16),
                               p_ref[pl.ds(c0, cw), :],
                               preferred_element_type=jnp.float32)
                h = c0 // (_PACK_GROUP // 2)
                acc[h] = part if acc[h] is None else acc[h] + part
            a, b = acc
            if b is None:
                lo, hi = a, pltpu.roll(a, 64, 1)
            else:
                lo = jnp.where(half, a, b)
                hi = pltpu.roll(jnp.where(half, b, a), 64, 1)
            words = jax.lax.bitcast_convert_type(
                lo.astype(jnp.int32) | (hi.astype(jnp.int32) << 16),
                jnp.uint32)
            nw = width // 32
            o_ref[pl.ds(r0, rs), pl.ds(g0 // 32, nw)] = words[:, :nw]

    if br == rs:
        # one slab: a static row offset, which Mosaic proves aligned
        # for any rs (a traced one it cannot, for bf16 with rs odd)
        slab(0)
    else:
        def body(t, carry):
            slab(pl.multiple_of(t * rs, rs))
            return carry

        jax.lax.fori_loop(0, br // rs, body, 0)


@functools.partial(jax.jit, static_argnames=("br", "n", "interpret",
                                             "mode", "tau"))
def sample_and_pack_leaf(s: jax.Array, seeds: jax.Array, *, br: int,
                         n: int | None = None, interpret: bool = False,
                         mode: str = "sample",
                         tau: float = 0.5) -> jax.Array:
    """s: (C, R, N) score rows of a leaf, N % 32 == 0; seeds: (C,).
    Returns (C, R, N // 32) uint32: word w of row r packs flat elements
    (r*N + 32w) .. +31 of cohort c's row, little-endian, so its reshape
    to (C, R*N // 32) is `sample_and_pack` of the flattened leaf.  With
    `n` < R*N the rows are a leaf of n elements padded to whole rows,
    and the bits past n are zero.  The grid reads (br, N) blocks, br a
    multiple of 16 or the whole R; the kernel walks a block 64, 32 or
    16 rows at a time, the most that divide it (all of it where none
    does)."""
    C, R, N = s.shape
    assert N % 32 == 0 and seeds.shape == (C,), (s.shape, seeds.shape)
    rs = next((r for r in (64, 32, 16) if br % r == 0), br)
    assert br == R or rs % 16 == 0, (br, R)
    n_valid = n if n is not None and n < R * N else None
    kernel = functools.partial(_sap_leaf_kernel, br=br, rs=rs, n_cols=N,
                               n_valid=n_valid, mode=mode, tau=tau)
    return pl.pallas_call(
        kernel,
        name="sample_and_pack",
        grid=(C, pl.cdiv(R, br)),
        in_specs=[
            pl.BlockSpec((None, br, N), lambda c, i: (c, i, 0)),
            _SCALAR_SPEC,
            pl.BlockSpec((_PACK_GROUP, 128), lambda c, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, br, N // 32), lambda c, i: (c, i, 0)),
        out_shape=jax.ShapeDtypeStruct((C, R, N // 32), jnp.uint32),
        compiler_params=_DENSE_PARAMS,
        interpret=interpret,
    )(s, jnp.asarray(seeds, jnp.uint32).reshape(1, C), _pack_matrix())


# ---------------------------------------------------------------------------
# Grouped masked matmul: y[r] = x[r] @ (m[e] ⊙ w[e]) over groups of rows
# ---------------------------------------------------------------------------
#
# The rows of all groups sit in one buffer, group-contiguous, each group
# padded to whole row tiles of `bm` (MoE: the (token, slot) pairs routed
# to each held expert, sorted by expert).  Two scalar-prefetch operands
# describe the layout: `tile_group[t]`, the group of row tile t, and
# `n_live`, how many tiles hold a group's rows; the buffer's other
# tiles are dead.  A dead tile issues no MXU work and fetches nothing:
# every index map clamps it to the last live tile's final block, so the
# pipeline sees an unchanged block index, and its output rows are never
# written.  Group e's mask is drawn at flat index offs[e] + row*n_total
# + col of seeds[e]'s stream (offs[e] = e*K*N under the
# `MaskedLeaf.build` convention): one pallas_call for all groups, no
# (E, K, N) m⊙w tensor in HBM.


def _grp_operands(seeds, offs, tau):
    return (jnp.asarray(seeds, jnp.uint32).reshape(1, -1),
            jnp.asarray(offs, jnp.uint32).reshape(1, -1),
            jnp.asarray(tau, jnp.float32).reshape(1, 1))


def _layout_operands(tile_group, n_live):
    return (jnp.asarray(tile_group, jnp.int32).reshape(-1),
            jnp.asarray(n_live, jnp.int32).reshape(1))


def _live_clamp(t, nl, *inner):
    """(live, clamped tile, clamped inner indices): a dead tile maps to
    the last live tile and each inner index to its last block."""
    live = t < nl[0]
    tc = jnp.minimum(t, nl[0] - 1)
    return live, tc, tuple(jnp.where(live, i, n - 1) for i, n in inner)


def _g_kernel(tg_ref, nl_ref, x_ref, w_ref, s_ref, seed_ref, off_ref,
              tau_ref, o_ref, acc_ref, *, bk: int, bn: int, n_total: int,
              nk: int, mode: str):
    t, n_i, k_i = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(t < nl_ref[0])
    def _():
        @pl.when(k_i == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        e = tg_ref[t]
        m = _tile_mask_vals(s_ref[...], seed_ref[0, e], off_ref[0, e],
                            tau_ref[0, 0],
                            row0=k_i * jnp.uint32(bk),
                            col0=n_i * jnp.uint32(bn),
                            n_total=n_total, mode=mode)
        wm = jnp.where(m, w_ref[...].astype(jnp.float32), 0.0)
        acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), wm,
                                preferred_element_type=jnp.float32)

        @pl.when(k_i == nk - 1)
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _grouped_call(kernel, name, grid, in_specs, out_spec, out_shape,
                  scratch, interpret, operands):
    return pl.pallas_call(
        kernel,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
            out_specs=out_spec, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=_DENSE_PARAMS,
        interpret=interpret,
    )(*operands)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk",
                                             "n_logical", "interpret",
                                             "mode"))
def masked_matmul_grouped(x: jax.Array, w: jax.Array, s: jax.Array,
                          seeds: jax.Array, offs: jax.Array,
                          tile_group: jax.Array, n_live: jax.Array, *,
                          bm: int = 128, bn: int = 512, bk: int = 512,
                          n_logical: int | None = None,
                          interpret: bool = False, mode: str = "sample",
                          tau: jax.Array = 0.5) -> jax.Array:
    """x: (R, K) group-contiguous rows, R = tiles * bm; w, s: (E, K, N);
    seeds, offs: (E,) uint32 per-group stream coordinates; tile_group:
    (tiles,) int32; n_live: scalar int32.  Returns (R, N) in x.dtype:
    y[r] = x[r] @ (m[e] ⊙ w[e]) for the rows of live tiles (e the
    tile's group); dead tiles' rows are left unwritten.
    `mode="threshold"` as in `masked_matmul`."""
    R, K = x.shape
    E, K2, N = w.shape
    assert K == K2 and s.shape == w.shape, (x.shape, w.shape, s.shape)
    tiles = tile_group.shape[0]
    assert R == tiles * bm, (R, tiles, bm)
    n_total = N if n_logical is None else n_logical
    bn_, bk_ = min(bn, N), min(bk, K)
    assert N % bn_ == 0 and K % bk_ == 0, (N, K, bn_, bk_)
    nn, nk = N // bn_, K // bk_

    def x_map(t, j, k, tg, nl):
        _, tc, (kc,) = _live_clamp(t, nl, (k, nk))
        return tc, kc

    def ws_map(t, j, k, tg, nl):
        _, tc, (jc, kc) = _live_clamp(t, nl, (j, nn), (k, nk))
        return tg[tc], kc, jc

    def o_map(t, j, k, tg, nl):
        _, tc, (jc,) = _live_clamp(t, nl, (j, nn))
        return tc, jc

    kernel = functools.partial(_g_kernel, bk=bk_, bn=bn_,
                               n_total=n_total, nk=nk, mode=mode)
    return _grouped_call(
        kernel, "masked_matmul_grouped", (tiles, nn, nk),
        [pl.BlockSpec((bm, bk_), x_map),
         pl.BlockSpec((None, bk_, bn_), ws_map),
         pl.BlockSpec((None, bk_, bn_), ws_map)] + _SCALAR_SPECS,
        pl.BlockSpec((bm, bn_), o_map),
        jax.ShapeDtypeStruct((R, N), x.dtype),
        [pltpu.VMEM((bm, bn_), jnp.float32)], interpret,
        _layout_operands(tile_group, n_live)
        + (x, w, s) + _grp_operands(seeds, offs, tau))


def _g_dx_kernel(tg_ref, nl_ref, g_ref, w_ref, s_ref, seed_ref, off_ref,
                 tau_ref, o_ref, acc_ref, *, bk: int, bn: int,
                 n_total: int, nn: int, mode: str):
    t, k_i, n_i = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(t < nl_ref[0])
    def _():
        @pl.when(n_i == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        e = tg_ref[t]
        m = _tile_mask_vals(s_ref[...], seed_ref[0, e], off_ref[0, e],
                            tau_ref[0, 0],
                            row0=k_i * jnp.uint32(bk),
                            col0=n_i * jnp.uint32(bn),
                            n_total=n_total, mode=mode)
        wm = jnp.where(m, w_ref[...].astype(jnp.float32), 0.0)  # (bk, bn)
        acc_ref[...] += jax.lax.dot_general(
            g_ref[...].astype(jnp.float32), wm,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(n_i == nn - 1)
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk",
                                             "n_logical", "interpret",
                                             "mode"))
def masked_matmul_grouped_dx(g: jax.Array, w: jax.Array, s: jax.Array,
                             seeds: jax.Array, offs: jax.Array,
                             tile_group: jax.Array, n_live: jax.Array, *,
                             bm: int = 128, bn: int = 512,
                             bk: int = 512, n_logical: int | None = None,
                             interpret: bool = False,
                             mode: str = "sample",
                             tau: jax.Array = 0.5) -> jax.Array:
    """g: (R, N) upstream cotangent rows; w, s: (E, K, N).  Returns
    dx[r] = g[r] @ (m[e] ⊙ w[e])ᵀ : (R, K) in g.dtype for the rows of
    live tiles, masks bit-identical to the grouped forward's."""
    R, N = g.shape
    E, K, N2 = w.shape
    assert N == N2 and s.shape == w.shape
    tiles = tile_group.shape[0]
    assert R == tiles * bm, (R, tiles, bm)
    n_total = N if n_logical is None else n_logical
    bn_, bk_ = min(bn, N), min(bk, K)
    assert N % bn_ == 0 and K % bk_ == 0, (N, K, bn_, bk_)
    nk, nn = K // bk_, N // bn_

    def g_map(t, k, n, tg, nl):
        _, tc, (nc,) = _live_clamp(t, nl, (n, nn))
        return tc, nc

    def ws_map(t, k, n, tg, nl):
        _, tc, (kc, nc) = _live_clamp(t, nl, (k, nk), (n, nn))
        return tg[tc], kc, nc

    def o_map(t, k, n, tg, nl):
        _, tc, (kc,) = _live_clamp(t, nl, (k, nk))
        return tc, kc

    kernel = functools.partial(_g_dx_kernel, bk=bk_, bn=bn_,
                               n_total=n_total, nn=nn, mode=mode)
    return _grouped_call(
        kernel, "masked_matmul_grouped_dx", (tiles, nk, nn),
        [pl.BlockSpec((bm, bn_), g_map),
         pl.BlockSpec((None, bk_, bn_), ws_map),
         pl.BlockSpec((None, bk_, bn_), ws_map)] + _SCALAR_SPECS,
        pl.BlockSpec((bm, bk_), o_map),
        jax.ShapeDtypeStruct((R, K), g.dtype),
        [pltpu.VMEM((bm, bk_), jnp.float32)], interpret,
        _layout_operands(tile_group, n_live)
        + (g, w, s) + _grp_operands(seeds, offs, tau))


def _g_ds_kernel(tg_ref, nl_ref, x_ref, g_ref, w_ref, s_ref, o_ref,
                 acc_ref, *, tiles: int):
    t = pl.program_id(2)
    nl = nl_ref[0]
    e = tg_ref[t]
    first = (t == 0) | (tg_ref[jnp.maximum(t - 1, 0)] != e)
    last = (t == nl - 1) | (tg_ref[jnp.minimum(t + 1, tiles - 1)] != e)

    @pl.when(t < nl)
    def _():
        @pl.when(first)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # contract over the rows: (bm, bk) x (bm, bn) -> (bk, bn)
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...].astype(jnp.float32), g_ref[...].astype(jnp.float32),
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(last)
        def _():
            sig = jax.nn.sigmoid(s_ref[...].astype(jnp.float32))
            o_ref[...] = (acc_ref[...] * w_ref[...].astype(jnp.float32)
                          * sig * (1.0 - sig)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk",
                                             "interpret"))
def masked_matmul_grouped_ds(x: jax.Array, g: jax.Array, w: jax.Array,
                             s: jax.Array, tile_group: jax.Array,
                             n_live: jax.Array, *, bm: int = 128,
                             bn: int = 512, bk: int = 512,
                             interpret: bool = False) -> jax.Array:
    """x: (R, K), g: (R, N) group-contiguous rows; w, s: (E, K, N).
    Returns the STE score gradient ds[e] = (x_eᵀ@g_e) ⊙ w[e] ⊙
    σ(s[e])(1−σ(s[e])) : (E, K, N) in s.dtype, x_e and g_e the rows of
    group e's tiles.  The row tiles run innermost, so each (k, n) block
    of a group accumulates over its tiles and is written once, after
    its last; every group owns at least one tile (`ops.group_layout`),
    so every block is written."""
    R, K = x.shape
    N = g.shape[-1]
    E = w.shape[0]
    assert g.shape == (R, N) and w.shape == (E, K, N) \
        and s.shape == (E, K, N)
    tiles = tile_group.shape[0]
    assert R == tiles * bm, (R, tiles, bm)
    bn_, bk_ = min(bn, N), min(bk, K)
    assert N % bn_ == 0 and K % bk_ == 0, (N, K, bn_, bk_)
    nk, nn = K // bk_, N // bn_

    def x_map(k, n, t, tg, nl):
        return jnp.minimum(t, nl[0] - 1), k

    def g_map(k, n, t, tg, nl):
        return jnp.minimum(t, nl[0] - 1), n

    def ws_map(k, n, t, tg, nl):
        return tg[jnp.minimum(t, nl[0] - 1)], k, n

    kernel = functools.partial(_g_ds_kernel, tiles=tiles)
    return _grouped_call(
        kernel, "masked_matmul_grouped_ds", (nk, nn, tiles),
        [pl.BlockSpec((bm, bk_), x_map),
         pl.BlockSpec((bm, bn_), g_map),
         pl.BlockSpec((None, bk_, bn_), ws_map),
         pl.BlockSpec((None, bk_, bn_), ws_map)],
        pl.BlockSpec((None, bk_, bn_), ws_map),
        jax.ShapeDtypeStruct((E, K, N), s.dtype),
        [pltpu.VMEM((bk_, bn_), jnp.float32)], interpret,
        _layout_operands(tile_group, n_live) + (x, g, w, s))


# ---------------------------------------------------------------------------
# Masked depthwise causal conv: the (W, C) kernel leaf, fully fused
# ---------------------------------------------------------------------------
#
# A depthwise conv is elementwise per channel, so it cannot ride the
# matmul kernels; this kernel family extends the same hash-stream
# discipline to it.  The W-tap reduction is unrolled in-kernel over a
# (S, bc) activation tile (W is 4ish), the (W, bc) mask tile is drawn
# from flat index off + w_row*n_total + col — the leaf's uplink stream —
# and neither the mask nor m⊙w ever exists in HBM.  The `flip` variant
# reverses the tap order, which turns the forward correlation into the
# dL/dx transposed correlation with the SAME regenerated mask.


def _conv_kernel(x_ref, w_ref, s_ref, seed_ref, off_ref, tau_ref, o_ref,
                 *, Wt: int, S: int, n_total: int, mode: str,
                 flip: bool):
    if mode == "plain":
        # mask-free twin for pre-materialized weights (the reference
        # path): the SAME tap loop, so fused and reference convs are
        # instruction-identical (bit-equal f32 sums under FMA fusion)
        wm = w_ref[...].astype(jnp.float32)                 # (Wt, bc)
    else:
        j = pl.program_id(1)
        bc = w_ref.shape[-1]
        m = _tile_mask_vals(s_ref[...], seed_ref[0, 0], off_ref[0, 0],
                            tau_ref[0, 0], row0=jnp.uint32(0),
                            col0=j * jnp.uint32(bc),
                            n_total=n_total, mode=mode)
        wm = jnp.where(m, w_ref[...].astype(jnp.float32), 0.0)
    acc = None
    for t in range(Wt):
        row = Wt - 1 - t if flip else t
        term = x_ref[0, t:t + S, :].astype(jnp.float32) \
            * wm[row][None, :]
        acc = term if acc is None else acc + term
    o_ref[...] = acc.astype(o_ref.dtype)[None]


@functools.partial(jax.jit, static_argnames=("bc", "n_logical",
                                             "interpret", "mode",
                                             "flip"))
def masked_conv1d(x_pad: jax.Array, w: jax.Array, s: jax.Array,
                  seed: jax.Array, off: jax.Array = 0, *, bc: int = 128,
                  n_logical: int | None = None, interpret: bool = False,
                  mode: str = "sample", tau: jax.Array = 0.5,
                  flip: bool = False) -> jax.Array:
    """x_pad: (B, S + W - 1, C) causally padded input; w, s: (W, C)
    depthwise kernel/scores.  Returns f32 (B, S, C):
    y[b,s,c] = Σ_t x_pad[b,s+t,c] · (m ⊙ w)[t,c], the mask drawn at
    flat index off + t*n_total + c (the leaf's uplink stream).
    `flip=True` reverses the tap order (wm[W-1-t] at shift t) — the
    dL/dx correlation of the causal conv, same mask."""
    B, Sp, C = x_pad.shape
    Wt, C2 = w.shape
    assert C == C2 and s.shape == (Wt, C)
    S = Sp - Wt + 1
    n_total = C if n_logical is None else n_logical
    bc_ = min(bc, C)
    assert C % bc_ == 0, (C, bc_)
    kernel = functools.partial(_conv_kernel, Wt=Wt, S=S,
                               n_total=n_total, mode=mode, flip=flip)
    return pl.pallas_call(
        kernel,
        name="masked_conv1d",
        grid=(B, C // bc_),
        in_specs=[
            pl.BlockSpec((1, Sp, bc_), lambda b, j: (b, 0, j)),
            pl.BlockSpec((Wt, bc_), lambda b, j: (0, j)),
            pl.BlockSpec((Wt, bc_), lambda b, j: (0, j)),
        ] + _SCALAR_SPECS,
        out_specs=pl.BlockSpec((1, S, bc_), lambda b, j: (b, 0, j)),
        out_shape=jax.ShapeDtypeStruct((B, S, C), jnp.float32),
        interpret=interpret,
    )(x_pad, w, s, *_scalar_operands(seed, off, tau))


def _conv_ds_kernel(x_ref, g_ref, w_ref, s_ref, o_ref, acc_ref, *,
                    Wt: int, S: int, nb: int, epilogue: str):
    b_i = pl.program_id(1)

    @pl.when(b_i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    gv = g_ref[0].astype(jnp.float32)                  # (S, bc)
    acc_ref[...] += jnp.concatenate(
        [jnp.sum(x_ref[0, t:t + S, :].astype(jnp.float32) * gv,
                 axis=0, keepdims=True) for t in range(Wt)], axis=0)

    @pl.when(b_i == nb - 1)
    def _():
        if epilogue == "dw":
            # raw xᵀ★g: the weight gradient of the PLAIN conv (float
            # baselines training the materialized kernel directly)
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)
        else:
            sig = jax.nn.sigmoid(s_ref[...].astype(jnp.float32))
            o_ref[...] = (acc_ref[...] * w_ref[...].astype(jnp.float32)
                          * sig * (1.0 - sig)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bc", "interpret",
                                             "epilogue"))
def masked_conv1d_ds(x_pad: jax.Array, g: jax.Array, w: jax.Array,
                     s: jax.Array, *, bc: int = 128,
                     interpret: bool = False,
                     epilogue: str = "ste") -> jax.Array:
    """x_pad: (B, S + W - 1, C); g: (B, S, C) cotangent; w, s: (W, C).
    Returns the STE score gradient
    ds[t,c] = (Σ_{b,s} x_pad[b,s+t,c] g[b,s,c]) ⊙ w ⊙ σ(s)(1−σ(s)) :
    (W, C) in s.dtype — the xᵀg correlation and the sigmoid epilogue
    never leave VMEM.  `epilogue="dw"` skips the STE epilogue and
    returns the raw correlation (the plain conv's weight gradient)."""
    B, Sp, C = x_pad.shape
    Wt, C2 = w.shape
    S = Sp - Wt + 1
    assert C == C2 and s.shape == (Wt, C) and g.shape == (B, S, C)
    bc_ = min(bc, C)
    assert C % bc_ == 0, (C, bc_)
    kernel = functools.partial(_conv_ds_kernel, Wt=Wt, S=S, nb=B,
                               epilogue=epilogue)
    return pl.pallas_call(
        kernel,
        name="masked_conv1d_ds",
        grid=(C // bc_, B),
        in_specs=[
            pl.BlockSpec((1, Sp, bc_), lambda j, b: (b, 0, j)),
            pl.BlockSpec((1, S, bc_), lambda j, b: (b, 0, j)),
            pl.BlockSpec((Wt, bc_), lambda j, b: (0, j)),
            pl.BlockSpec((Wt, bc_), lambda j, b: (0, j)),
        ],
        out_specs=pl.BlockSpec((Wt, bc_), lambda j, b: (0, j)),
        out_shape=jax.ShapeDtypeStruct((Wt, C), s.dtype),
        scratch_shapes=[pltpu.VMEM((Wt, bc_), jnp.float32)],
        interpret=interpret,
    )(x_pad, g, w, s)
