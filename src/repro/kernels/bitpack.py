"""Bit-pack / unpack Pallas kernels for the 1-Bpp mask uplink.

pack:   (W, 32) {0,1} -> (W,) uint32   (little-endian bit order)
unpack: (W,) uint32   -> (W, 32) uint8

TPU adaptation: GPU implementations use warp ballots; on TPU we pack by
a vectorized shift-OR across the 32-lane minor axis. Blocks are (1024,
32): the sublane axis carries words (multiple of 8) while the 32-bit
lanes hold the bits — Mosaic relayouts this to native tiling. A 1-D
uint32 word block must be a multiple of 1024 (XLA's tiling of a 1-D u32
array) or the whole array. The packed uplink then rides
jax.lax.all_gather at 1/16 the bytes of a bf16 psum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.masked_matmul import _or_lanes


def _pack_kernel(m_ref, o_ref):
    o_ref[...] = _or_lanes(m_ref[...], axis=1)             # (bw, 32)


def _unpack_kernel(w_ref, o_ref):
    words = w_ref[...].astype(jnp.uint32)                  # (bw,)
    shifts = jax.lax.broadcasted_iota(
        jnp.uint32, (words.shape[0], 32), 1)
    o_ref[...] = ((words[:, None] >> shifts)
                  & jnp.uint32(1)).astype(jnp.uint8)


def _word_block(W: int, bw: int) -> tuple[int, int]:
    """(block, padded word count): the whole array when it fits one
    block, else `bw`-word blocks over W rounded up to a multiple."""
    if W <= bw:
        return W, W
    return bw, -(-W // bw) * bw


@functools.partial(jax.jit, static_argnames=("bw", "interpret"))
def pack_bits(mask_flat: jax.Array, *, bw: int = 1024,
              interpret: bool = False) -> jax.Array:
    """mask_flat: (n,) with n % 32 == 0, values in {0,1}. -> (n//32,)
    uint32."""
    assert mask_flat.ndim == 1 and mask_flat.size % 32 == 0
    W = mask_flat.size // 32
    bw_, Wp = _word_block(W, bw)
    m2 = mask_flat.reshape(W, 32)
    if Wp > W:
        m2 = jnp.pad(m2, ((0, Wp - W), (0, 0)))
    return pl.pallas_call(
        _pack_kernel,
        name="pack_bits",
        grid=(Wp // bw_,),
        in_specs=[pl.BlockSpec((bw_, 32), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bw_,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Wp,), jnp.uint32),
        interpret=interpret,
    )(m2)[:W]


@functools.partial(jax.jit, static_argnames=("n", "bw", "interpret"))
def unpack_bits(words: jax.Array, n: int, *, bw: int = 1024,
                interpret: bool = False) -> jax.Array:
    """words: (W,) uint32 -> (n,) uint8 (n <= 32*W)."""
    W = words.size
    bw_, Wp = _word_block(W, bw)
    if Wp > W:
        words = jnp.pad(words, (0, Wp - W))
    bits = pl.pallas_call(
        _unpack_kernel,
        name="unpack_bits",
        grid=(Wp // bw_,),
        in_specs=[pl.BlockSpec((bw_,), lambda i: (i,))],
        out_specs=pl.BlockSpec((bw_, 32), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Wp, 32), jnp.uint8),
        interpret=interpret,
    )(words)
    return bits.reshape(-1)[:n]
