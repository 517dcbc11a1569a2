"""Server-side aggregation (eq. 8) and the bit-packed mask collectives.

Two transport paths for the uplink inside a TPU mesh:

  * ``psum_bf16``  — m cast to bf16, ``jax.lax.psum`` over client axes.
    Simple, but moves 16 bits/parameter on the wire.
  * ``packed_allgather`` — m bit-packed 32->1 into uint32 (Pallas kernel
    on TPU, jnp fallback elsewhere), ``all_gather`` of the packed words,
    then unpack+weighted-mean locally. Moves ~1 bit/parameter/client on
    each link — the paper's 1 Bpp uplink, TPU-native.

Bayesian (Beta-prior) aggregation from FedPM is included as an option.
"""
from __future__ import annotations

import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any

# ---------------------------------------------------------------------------
# Bit packing — the public serialization trio (pure-jnp reference; the
# Pallas variant lives in repro.kernels.bitpack):
#
#     flat, pad = pad_to_words(mask.reshape(-1))   # zero-pad to 32k bits
#     words     = pack_bits(flat)                  # uint32 words, 32->1
#     mask_back = unpack_bits(words, mask.size)    # lossless inverse
#
# `repro.api.payloads` builds every `BitpackedMasks` uplink through
# these, and `federated.final_artifact` serializes the deployable
# artifact with them.
# ---------------------------------------------------------------------------


def pack_bits(mask_flat: jax.Array) -> jax.Array:
    """Pack a flat {0,1} uint8/float vector into uint32 words (little-end).

    Length must be a multiple of 32 (pad with `pad_to_words` first).
    """
    assert mask_flat.ndim == 1 and mask_flat.size % 32 == 0
    bits = mask_flat.astype(jnp.uint32).reshape(-1, 32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(bits << shifts, axis=1).astype(jnp.uint32)


def unpack_bits(words: jax.Array, n: int) -> jax.Array:
    """Inverse of pack_bits -> uint8 vector of length n (padding bits
    beyond n are dropped)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[:, None] >> shifts) & jnp.uint32(1)
    return bits.reshape(-1)[:n].astype(jnp.uint8)


def pad_to_words(x: jax.Array, word_bits: int = 32):
    """Flatten and zero-pad `x` to a multiple of `word_bits` entries.

    Returns (flat_padded, pad_count).  Zero padding is what makes
    `unpack_bits(pack_bits(...), n)` an exact round trip and keeps
    entropy accounting honest (pad bits are never counted as params).
    """
    pad = (-x.size) % word_bits
    x = x.reshape(-1)
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), dtype=x.dtype)])
    return x, pad


# Backwards-compatible alias (pre-1.0 private name).
_pad32 = pad_to_words


# ---------------------------------------------------------------------------
# Host-side (simulation) aggregation: list of client masks -> theta
# ---------------------------------------------------------------------------


def aggregate_masks(masks: Sequence[Pytree],
                    weights: Sequence[float] | None = None) -> Pytree:
    """eq. (8): theta(t+1) = sum_i |D_i| m̂_i / sum_k |D_k|.

    `masks` is a list of client mask pytrees (uint8 leaves / None).
    """
    if weights is None:
        weights = [1.0] * len(masks)
    wsum = float(sum(weights))
    ws = [w / wsum for w in weights]

    def one(*ms):
        if ms[0] is None:
            return None
        acc = jnp.zeros(ms[0].shape, jnp.float32)
        for w, m in zip(ws, ms):
            acc = acc + w * m.astype(jnp.float32)
        return acc

    return jax.tree_util.tree_map(one, *masks,
                                  is_leaf=lambda x: x is None)


def aggregate_bayesian(masks: Sequence[Pytree], alpha0: float = 1.0,
                       beta0: float = 1.0) -> Pytree:
    """FedPM's Bayesian aggregation: Beta(alpha0+ones, beta0+zeros) mean.

    Slightly better-calibrated theta for small cohorts (beyond-paper
    option; the paper itself uses the weighted arithmetic mean).
    """
    k = len(masks)

    def one(*ms):
        if ms[0] is None:
            return None
        ones = jnp.zeros(ms[0].shape, jnp.float32)
        for m in ms:
            ones = ones + m.astype(jnp.float32)
        return (alpha0 + ones) / (alpha0 + beta0 + k)

    return jax.tree_util.tree_map(one, *masks,
                                  is_leaf=lambda x: x is None)


def aggregate_floats(float_trees: Sequence[Pytree],
                     weights: Sequence[float] | None = None) -> Pytree:
    """FedAvg for the non-masked float leaves (norms, biases...)."""
    if weights is None:
        weights = [1.0] * len(float_trees)
    wsum = float(sum(weights))
    ws = [w / wsum for w in weights]

    def one(*fs):
        if fs[0] is None:
            return None
        acc = jnp.zeros(fs[0].shape, jnp.float32)
        for w, f in zip(ws, fs):
            acc = acc + w * f.astype(jnp.float32)
        return acc.astype(fs[0].dtype)

    return jax.tree_util.tree_map(one, *float_trees,
                                  is_leaf=lambda x: x is None)


def sample_and_pack_rows(scores: jax.Array, seeds: jax.Array,
                         use_kernel: bool = False, mode: str = "sample",
                         tau: float = 0.5) -> jax.Array:
    """Fused per-cohort uplink sampling + 32->1 bitpack.

    (C, *body) scores + (C,) uint32 seeds -> (C, ceil(n/32)) uint32
    words of m ~ Bern(sigmoid(scores)) over each cohort's flattened
    leaf of n elements, where row c draws from the counter-based hash
    stream seeded by seeds[c] (the same stream the fused masked-matmul
    kernels regenerate).  With ``use_kernel`` the one-pass Pallas
    kernel runs (scores -> hash -> Bernoulli -> words; no uint8 mask in
    HBM), reading a stacked leaf in its own layout where
    `kernels.ops.pack_plan` says so; otherwise the pure-jnp two-pass
    reference — the two are bit-identical.  ``mode="threshold"`` packs
    the deterministic FedMask mask 1[sigmoid(s) > tau] (seeds ignored).
    """
    if use_kernel:
        from repro.kernels import ops as _kops
        return _kops.sample_and_pack(scores, seeds, mode=mode, tau=tau)
    from repro.kernels import ref as _kref
    return _kref.sample_and_pack(scores.reshape(scores.shape[0], -1),
                                 seeds, mode=mode, tau=tau)


# ---------------------------------------------------------------------------
# Buffered-async aggregation support (repro.runtime.async_engine):
# staleness-discounted survivor weights + the wire-integrity checksum
# ---------------------------------------------------------------------------


def staleness_weight(staleness, alpha: float = 1.0):
    """FedBuff-style polynomial staleness discount ``(1 + s)^-alpha``.

    ``staleness`` counts COMMITS between the theta a client trained
    against and the round its mask is folded into; s = 0 (in-time)
    gives weight 1.0 exactly, so the zero-fault async engine reduces to
    the synchronous weighted mean bit-for-bit.  Works on Python floats
    and np/jnp arrays alike.
    """
    if hasattr(staleness, "dtype"):
        one = np.float32(1.0) if isinstance(staleness, np.ndarray) \
            else jnp.float32(1.0)
        return (one + staleness) ** (-alpha)
    return float((1.0 + staleness) ** (-alpha))


def staleness_weights(sizes, staleness, alpha: float = 1.0):
    """Normalized fold weights for a commit buffer: |D_i| discounted by
    per-entry staleness, renormalized over the buffer — the SAME
    formula `repro.api.protocol.run_round` applies to its participation
    vector (`w = sizes * pf; wn = w / max(sum(w), 1e-9)`), so a buffer
    of all-fresh arrivals aggregates identically to a synchronous
    round."""
    sizes = jnp.asarray(sizes, jnp.float32)
    disc = jnp.asarray(staleness_weight(
        jnp.asarray(staleness, jnp.float32), alpha), jnp.float32)
    # s == 0 must contribute exactly `sizes` (discount is exactly 1.0)
    w = jnp.where(disc == 1.0, sizes, sizes * disc)
    return w / jnp.maximum(jnp.sum(w), 1e-9)


def fold_popcount(acc_ones, words) -> int:
    """Running popcount fold: add one arrival's packed-word one-counts
    to a host accumulator (the async engine's live buffer statistic).
    The device does the popcount; the running sum lives in an unbounded
    Python int so the fold is exact at any scale — bits are integers,
    no float accumulation order issues."""
    ones = jnp.sum(jax.lax.population_count(
        jnp.asarray(words, jnp.uint32)).astype(jnp.int32))
    return int(acc_ones) + int(ones)


def fold_bit_counts(acc, words):
    """Per-bit-position count fold: add one (or a batch of) client's
    packed uint32 words into an integer per-parameter count accumulator
    — the edge aggregator's O(params) pooled state.

    ``acc`` is int32[P] over the padded word domain (P = 32 * n_words);
    ``words`` is uint32[W] (one client) or uint32[B, W] (a chunk of B
    clients, summed in one pass).  Counts are exact integers, so the
    fold is associative and lossless: ANY grouping of clients into
    edges produces the identical accumulator — the property the
    aggregator tree's bit-identity gate rests on.
    """
    w = jnp.asarray(words, jnp.uint32)
    if w.ndim == 1:
        w = w[None, :]
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = ((w[:, :, None] >> shifts) & jnp.uint32(1)).astype(jnp.int32)
    return jnp.asarray(acc, jnp.int32) + bits.reshape(w.shape[0], -1
                                                      ).sum(axis=0)


_COUNT_DTYPES = {8: "<u1", 16: "<u2", 32: "<u4"}


def pack_counts(counts, acc_bits: int = 16) -> np.ndarray:
    """Fixed-width serialization of a count accumulator into uint32
    words (host-side, little-endian): each per-bit count rides in an
    ``acc_bits``-wide field, so the record size depends ONLY on the
    parameter count and the field width — never on how many clients
    were folded.  ``acc_bits`` must be 8, 16 or 32; a count that does
    not fit the field is a hard error (silent truncation would forge
    the fold)."""
    if acc_bits not in _COUNT_DTYPES:
        raise ValueError(f"acc_bits must be one of 8/16/32, "
                         f"got {acc_bits}")
    c = np.asarray(counts).reshape(-1)
    if c.size and (int(c.max()) >> acc_bits or int(c.min()) < 0):
        raise OverflowError(
            f"count {int(c.max())} does not fit {acc_bits}-bit "
            f"accumulator field")
    per = 32 // acc_bits
    pad = (-c.size) % per
    c = c.astype(np.uint64)
    if pad:
        c = np.concatenate([c, np.zeros((pad,), np.uint64)])
    return np.ascontiguousarray(
        c.astype(_COUNT_DTYPES[acc_bits])).view("<u4").astype(np.uint32)


def unpack_counts(words, n: int, acc_bits: int = 16) -> np.ndarray:
    """Inverse of `pack_counts`: uint32 word stream -> int64[n] counts
    (padding fields beyond n are dropped)."""
    if acc_bits not in _COUNT_DTYPES:
        raise ValueError(f"acc_bits must be one of 8/16/32, "
                         f"got {acc_bits}")
    w = np.ascontiguousarray(np.asarray(words, np.uint32).astype("<u4"))
    return w.view(_COUNT_DTYPES[acc_bits])[:n].astype(np.int64)


def packed_count_bits(n_positions: int, acc_bits: int = 16) -> int:
    """Exact serialized size in bits of one `pack_counts` stream over
    ``n_positions`` count fields (word-aligned)."""
    per = 32 // acc_bits
    return 32 * ((n_positions + per - 1) // per)


def words_checksum(arrays) -> int:
    """CRC32 checksum over serialized uint32 word streams — the
    per-message integrity header `repro.api.codecs.WireMessage` carries
    (host-side: the wire is host bytes).  `arrays` is a sequence of
    uint32 numpy arrays; the checksum covers their concatenated
    little-endian bytes, so any single bit flip in transit changes it.
    """
    import zlib
    h = 0
    for a in arrays:
        b = np.ascontiguousarray(
            np.asarray(a, np.uint32).astype("<u4")).tobytes()
        h = zlib.crc32(b, h)
    return int(h & 0xFFFFFFFF)


# ---------------------------------------------------------------------------
# In-mesh collectives (used under shard_map over client axes)
# ---------------------------------------------------------------------------


def mask_mean_psum(mask: Pytree, axis_names) -> Pytree:
    """bf16 psum path: theta = mean over client axes. 16 bits/param."""
    names = (axis_names if isinstance(axis_names, (tuple, list))
             else (axis_names,))

    def one(m):
        if m is None:
            return None
        s = jax.lax.psum(m.astype(jnp.bfloat16), names)
        k = 1
        for a in names:
            k *= jax.lax.axis_size(a)
        return s.astype(jnp.float32) / k

    return jax.tree_util.tree_map(one, mask, is_leaf=lambda x: x is None)


def mask_mean_packed(mask: Pytree, axis_names, use_kernel: bool = False
                     ) -> Pytree:
    """Bit-packed path: pack 32 mask bits -> uint32, all_gather packed
    words over client axes, unpack + mean locally. ~1 bit/param/client on
    the wire (vs 16 for bf16 psum).
    """
    names = (axis_names if isinstance(axis_names, (tuple, list))
             else (axis_names,))

    if use_kernel:
        from repro.kernels import ops as _kops
        _pack = _kops.pack_bits
    else:
        _pack = pack_bits

    def one(m):
        if m is None:
            return None
        shape = m.shape
        flat, _ = pad_to_words(m.reshape(-1))
        words = _pack(flat)
        gathered = words
        for a in names:
            gathered = jax.lax.all_gather(gathered, a)
        gathered = gathered.reshape(-1, words.size)
        k = gathered.shape[0]
        # popcount-style unpack-mean: accumulate per-bit sums
        shifts = jnp.arange(32, dtype=jnp.uint32)
        bits = ((gathered[:, :, None] >> shifts) & jnp.uint32(1))
        mean = jnp.mean(bits.astype(jnp.float32), axis=0)
        return mean.reshape(-1)[:m.size].reshape(shape)

    return jax.tree_util.tree_map(one, mask, is_leaf=lambda x: x is None)


def uplink_bits(mask: Pytree, packed: bool = True) -> int:
    """Static accounting: bits a client sends for this mask pytree."""
    n = sum(m.size for m in jax.tree_util.tree_leaves(mask)
            if m is not None)
    if packed:
        return ((n + 31) // 32) * 32
    return n * 16  # bf16 transport


# ---------------------------------------------------------------------------
# Downlink compression (beyond-paper): stochastic k-bit theta broadcast
# ---------------------------------------------------------------------------


def quantize_theta(theta: Pytree, key, bits: int = 8) -> Pytree:
    """Unbiased stochastic quantization of the server's probability mask
    for the downlink broadcast (the paper counts UL masks only; with
    8-bit DL the full round costs ~(1 UL + 8/rounds DL) bits/param).

    Returns uint8/uint16 leaves in [0, 2^bits - 1].
    """
    levels = (1 << bits) - 1
    dtype = jnp.uint8 if bits <= 8 else jnp.uint16
    leaves = [t for t in jax.tree_util.tree_leaves(
        theta, is_leaf=lambda x: x is None)]
    n = sum(1 for t in leaves if t is not None)
    keys = jax.random.split(key, max(n, 1))
    it = iter(range(n))

    def one(t):
        if t is None:
            return None
        k = keys[next(it)]
        x = jnp.clip(t.astype(jnp.float32), 0.0, 1.0) * levels
        lo = jnp.floor(x)
        up = jax.random.uniform(k, t.shape) < (x - lo)  # stochastic
        return (lo + up).astype(dtype)

    return jax.tree_util.tree_map(one, theta,
                                  is_leaf=lambda x: x is None)


def dequantize_theta(q: Pytree, bits: int = 8) -> Pytree:
    levels = (1 << bits) - 1
    return jax.tree_util.tree_map(
        lambda t: None if t is None else
        t.astype(jnp.float32) / levels,
        q, is_leaf=lambda x: x is None)
