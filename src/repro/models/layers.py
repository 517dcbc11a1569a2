"""Shared neural-net layers for the model zoo (pure JAX, pytree params).

Conventions:
  * activations x: (B, S, D); params are nested dicts of jnp arrays.
  * maskable tensors get names WITHOUT the MaskSpec float patterns
    ("w_*"); norms/biases/routers carry "scale"/"bias"/"router" so the
    paper's technique skips them (docs/DESIGN.md §Arch-applicability).
  * every layer has init(key, cfg...) -> params and apply(params, x, ...).
  * every maskable projection is consumed through a per-leaf dispatch:
    `masked_dense_apply` (2-D dense weights), `masked_grouped_apply`
    (stacked (E, K, N) MoE expert weights), `masked_conv1d_apply`
    (depthwise (W, C) conv kernels) or `masked_conv2d_apply` (CNN
    (kh, kw, ci, co) kernels).  A leaf may be a plain array (float
    training, or effective params materialized by
    `masking.sample_effective` / `masking.hash_effective`) OR a
    `masking.MaskedLeaf` (w, s, seed) bundle, in which case the fused
    Pallas kernels run — no mask or masked-weight tensor ever exists
    in HBM for ANY maskable leaf shape (docs/DESIGN.md §3).
    `effective_weight` (the materializing fallback) survives only on
    the per-token decode path (`conv1d_step`), where
    `masking.freeze_for_decode` materializes once per session anyway.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import masking
from repro.core.masking import MaskedLeaf
from repro.kernels import ops

Pytree = Any

DEFAULT_DTYPE = jnp.bfloat16


# ---------------------------------------------------------------------------
# Masked execution dispatch: plain array | MaskedLeaf (w, s, seed)
# ---------------------------------------------------------------------------


def masked_dense_apply(x: jax.Array, p) -> jax.Array:
    """y = x @ w_eff for a plain weight array or a `MaskedLeaf`.

    Plain array: the ordinary matmul (float baselines, materialized
    effective params).  MaskedLeaf: the fused masked-dense kernel —
    the Bernoulli (or FedMask-threshold) mask is regenerated per tile
    from the leaf's hash-stream coordinates on BOTH passes, with scores
    a first-class grad argument through the STE custom-vjp.
    """
    if isinstance(p, MaskedLeaf):
        if p.mode == "threshold":
            return ops.masked_dense_threshold(x, p.w, p.s, p.tau)
        return ops.masked_dense(x, p.w, p.s, p.seed, p.off)
    return x @ p


def masked_grouped_apply(x: jax.Array, p, layout: ops.GroupLayout
                         ) -> jax.Array:
    """y[r] = x[r] @ w_eff[e] for the group-contiguous rows r of group e
    (`layout`, `ops.group_layout`) and a stacked (E, K, N) weight (MoE
    experts).  Rows of tiles past the live ones are not computed.

    Plain array: `jax.lax.ragged_dot` over the groups' tiles (float
    baselines, materialized effective params).  MaskedLeaf: ONE grouped
    Pallas launch for all E groups — per-group `seed`/`off` stream
    coordinates make each expert's mask exactly its slice of the leaf's
    flat uplink stream, and the stacked m⊙w never exists in HBM on
    either pass."""
    if isinstance(p, MaskedLeaf):
        if p.mode == "threshold":
            return ops.masked_dense_grouped_threshold(x, p.w, p.s, layout,
                                                      p.tau)
        return ops.masked_dense_grouped(x, p.w, p.s, p.seed, p.off,
                                        layout)
    E = p.shape[0]
    bm = x.shape[0] // layout.tile_group.shape[0]
    live = jnp.arange(layout.tile_group.shape[0]) < layout.n_live[0]
    rows = bm * jnp.sum(jax.nn.one_hot(layout.tile_group, E,
                                       dtype=jnp.int32) * live[:, None],
                        axis=0)
    return jax.lax.ragged_dot(x, p.astype(x.dtype), rows)


def masked_conv1d_apply(x: jax.Array, p) -> jax.Array:
    """Depthwise causal conv y[b,s,c] = Σ_t x[b,s+t-(W-1),c]·w_eff[t,c]
    for a (W, C) kernel leaf, f32 output (bias/cast stay with the
    caller).  Both branches run the SAME Pallas tap loop
    (`ops.masked_conv1d` / `ops.conv1d_plain`), so fused and
    materialized-reference convs are bit-identical — and neither
    builds the old (B, S, W, C) stacked-views tensor."""
    if isinstance(p, MaskedLeaf):
        if p.mode == "threshold":
            return ops.masked_conv1d_threshold(x, p.w, p.s, p.tau)
        return ops.masked_conv1d(x, p.w, p.s, p.seed, p.off)
    return ops.conv1d_plain(x, p)


def masked_conv2d_apply(x: jax.Array, p) -> jax.Array:
    """2-D SAME conv for a (kh, kw, ci, co) kernel leaf (the paper's
    Conv4/6/10 CNNs).  x: (B, H, W, ci) -> (B, H, W, co).

    Plain array: `lax.conv_general_dilated`.  MaskedLeaf: im2col ONCE
    to (B·H·W, kh·kw·ci) and run ONE fused `ops.masked_dense` launch —
    the (kh·kw·ci, co) row-major reshape of the leaf is contiguous
    with its flat hash stream (idx = row·co + col == the leaf's flat
    index), so the single launch at the leaf's base offset samples the
    identical mask as the uplink `sample_and_pack` stream, m⊙w never
    exists in HBM, and the activations are padded/read once rather
    than once per tap."""
    if not isinstance(p, MaskedLeaf):
        return jax.lax.conv_general_dilated(
            x, p.astype(x.dtype), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    kh, kw, ci, co = p.w.shape
    B, H, Wd, _ = x.shape
    ph, pw = (kh - 1) // 2, (kw - 1) // 2
    xp = jnp.pad(x, ((0, 0), (ph, kh - 1 - ph), (pw, kw - 1 - pw),
                     (0, 0)))
    cols = jnp.concatenate(
        [xp[:, dy:dy + H, dx:dx + Wd, :]
         for dy in range(kh) for dx in range(kw)],
        axis=-1).reshape(-1, kh * kw * ci)
    blk = MaskedLeaf(p.w.reshape(kh * kw * ci, co),
                     p.s.reshape(kh * kw * ci, co),
                     p.seed[0, 0], p.off[0, 0], p.mode, p.tau)
    return masked_dense_apply(cols, blk).reshape(B, H, Wd, co)


def effective_weight(p) -> jax.Array:
    """Effective weight tensor m * w from the SAME hash stream as the
    fused kernels (one weight-sized temporary).

    Since the grouped/conv kernels landed this survives ONLY on the
    per-token decode path (`conv1d_step`) — decode sessions should
    materialize once up front via `masking.freeze_for_decode`, making
    this a no-op pass-through (docs/DESIGN.md §3)."""
    if isinstance(p, MaskedLeaf):
        return masking.materialize_leaf(p)
    return p

# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, shape, dtype=DEFAULT_DTYPE, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[0]
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype=DEFAULT_DTYPE):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm_init(d):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rms_norm(params, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * params["scale"]
    return out.astype(x.dtype)


def layer_norm_init(d):
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def layer_norm(params, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * params["scale"] \
        + params["bias"]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE (+ M-RoPE for qwen2-vl)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim, theta=10000.0, dtype=jnp.float32):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=dtype)
                            / head_dim))


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN rotary scaling as DeepSeek-V2 publishes it
    (`DeepseekV2YarnRotaryEmbedding`; `rope_scaling` of its config)."""
    factor: float
    original_max_pos: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """0.1 * mscale * ln(scale) + 1 (1 for scale <= 1)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _yarn_dim(rotations: float, dim: int, base: float, max_pos: int):
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))
            / (2 * math.log(base)))


def yarn_freqs(head_dim: int, theta: float, y: Yarn) -> jax.Array:
    """Rotary frequencies mixed by a linear ramp between the
    interpolated base^(-2i/d)/factor (low frequencies) and the original
    base^(-2i/d) (high ones), over the correction range that beta_fast
    and beta_slow give at the original context length."""
    freq = rope_freqs(head_dim, theta)
    low = max(math.floor(_yarn_dim(y.beta_fast, head_dim, theta,
                                   y.original_max_pos)), 0)
    high = min(math.ceil(_yarn_dim(y.beta_slow, head_dim, theta,
                                   y.original_max_pos)), head_dim - 1)
    high = high + 0.001 if low == high else high
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp          # 1: the original frequency
    return freq / y.factor * (1.0 - keep) + freq * keep


def yarn_attn_scale(qk_dim: int, y: Yarn | None) -> float:
    """Softmax scale qk_dim^-0.5, times mscale(factor,
    mscale_all_dim)^2 under YaRN."""
    s = qk_dim ** -0.5
    if y is not None and y.mscale_all_dim:
        s *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return s


def apply_rope(x, positions, theta=10000.0, yarn: Yarn | None = None):
    """x: (..., S, H, Hd), positions: broadcastable to (..., S).  With
    `yarn`, YaRN frequencies and cos/sin scaled by mscale(factor,
    mscale) / mscale(factor, mscale_all_dim)."""
    hd = x.shape[-1]
    if yarn is None:
        freqs, amp = rope_freqs(hd, theta), 1.0
    else:
        freqs = yarn_freqs(hd, theta, yarn)
        amp = (yarn_mscale(yarn.factor, yarn.mscale)
               / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Hd/2)
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]
    if amp != 1.0:
        sin, cos = sin * amp, cos * amp
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, sections=(16, 24, 24), theta=10000.0):
    """Qwen2-VL M-RoPE: positions3 (3, ..., S) for (t, h, w); the rotary
    dim is partitioned into `sections` (halved freq indices), each section
    rotated by its own position stream."""
    hd = x.shape[-1]
    half = hd // 2
    assert sum(sections) == half, (sections, hd)
    freqs = rope_freqs(hd, theta)  # (half,)
    # build per-frequency position selector
    sec_id = jnp.repeat(jnp.arange(3), jnp.array(sections),
                        total_repeat_length=half)  # (half,)
    # positions3: (3, B, S) -> (B, S, half) gathering by sec_id
    pos = jnp.take(positions3, sec_id, axis=0)          # (half, B, S)
    pos = jnp.moveaxis(pos, 0, -1).astype(jnp.float32)  # (B, S, half)
    ang = pos * freqs                                   # (B, S, half)
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, optional sliding window, chunked online-softmax)
# ---------------------------------------------------------------------------


def gqa_init(key, d_model, n_heads, n_kv, head_dim, qkv_bias=False,
             dtype=DEFAULT_DTYPE):
    ks = jax.random.split(key, 4)
    p = {
        "w_q": dense_init(ks[0], (d_model, n_heads * head_dim), dtype),
        "w_k": dense_init(ks[1], (d_model, n_kv * head_dim), dtype),
        "w_v": dense_init(ks[2], (d_model, n_kv * head_dim), dtype),
        "w_o": dense_init(ks[3], (n_heads * head_dim, d_model), dtype,
                          fan_in=n_heads * head_dim),
    }
    if qkv_bias:
        p["bias_q"] = jnp.zeros((n_heads * head_dim,), jnp.float32)
        p["bias_k"] = jnp.zeros((n_kv * head_dim,), jnp.float32)
        p["bias_v"] = jnp.zeros((n_kv * head_dim,), jnp.float32)
    return p


def _attn_scores_mask(q_pos, k_pos, window: int | None, causal=True):
    """(Sq, Sk) additive mask. window=None -> full (causal)."""
    diff = q_pos[:, None] - k_pos[None, :]
    ok = (diff >= 0) if causal else jnp.ones_like(diff, bool)
    if window is not None:
        ok = ok & (diff < window)
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


@jax.named_scope("attention_core")
def attention_core(q, k, v, q_pos, k_pos, window=None, causal=True,
                   chunk_kv: int | None = None, soft_cap: float | None = None,
                   scale: float | None = None):
    """q: (B, Sq, H, Hd); k: (B, Sk, Kv, Hd); v: (B, Sk, Kv, Dv).
    GQA by head repetition; Dv may differ from Hd (MLA).  `scale`
    defaults to Hd^-0.5.

    chunk_kv: if set, run online-softmax over KV chunks (flash-style
    memory behaviour: never materializes the (Sq, Sk) matrix; each
    chunk's scores are recomputed on the backward pass, so training
    keeps only the running (max, sum, output) per chunk). This is the
    memory path for 32k prefill / 500k contexts and 8k training.
    """
    B, Sq, H, Hd = q.shape
    Kv = k.shape[2]
    Dv = v.shape[-1]
    rep = H // Kv
    scale = 1.0 / math.sqrt(Hd) if scale is None else scale
    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, Kv, rep, Hd)

    if chunk_kv is None:
        s = jnp.einsum("bqgrh,bkgh->bgrqk", qf, k.astype(jnp.float32))
        if soft_cap is not None:
            s = jnp.tanh(s / soft_cap) * soft_cap
        s = s + _attn_scores_mask(q_pos, k_pos, window, causal)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bgrqk,bkgh->bqgrh", p, v.astype(jnp.float32))
        return o.reshape(B, Sq, H, Dv).astype(q.dtype)

    # online softmax over kv chunks
    Sk = k.shape[1]
    n_chunks = (Sk + chunk_kv - 1) // chunk_kv
    pad = n_chunks * chunk_kv - Sk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kpos = jnp.pad(k_pos, (0, pad), constant_values=-(10 ** 9))
    kc = kp.reshape(B, n_chunks, chunk_kv, Kv, Hd)
    vc = vp.reshape(B, n_chunks, chunk_kv, Kv, Dv)
    pc = kpos.reshape(n_chunks, chunk_kv)

    def body(carry, xs):
        m, l, acc = carry
        kci, vci, pci = xs
        s = jnp.einsum("bqgrh,bkgh->bgrqk", qf, kci.astype(jnp.float32))
        if soft_cap is not None:
            s = jnp.tanh(s / soft_cap) * soft_cap
        s = s + _attn_scores_mask(q_pos, pci, window, causal)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bgrqk,bkgh->bgrqh", p, vci.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((B, Kv, rep, Sq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Kv, rep, Sq), jnp.float32)
    a0 = jnp.zeros((B, Kv, rep, Sq, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body), (m0, l0, a0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), pc))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    o = jnp.moveaxis(o, -2, 1).reshape(B, Sq, H, Dv)
    return o.astype(q.dtype)


def gqa_apply(p, x, positions, n_heads, n_kv, head_dim, *, window=None,
              causal=True, rope_theta=10000.0, chunk_kv=None,
              mrope_positions=None, mrope_sections=None,
              kv_override=None, k_positions=None, use_rope=True):
    """Full GQA block (no norm).

    positions: (S,) or (B, S) query positions (also key positions for
    self-attention without override).
    kv_override: (k, v) tensors — cross-attention or cached decode; keys
    are assumed already roped. k_positions gives their positions (default
    arange).
    Returns (out, (k, v)) so callers can populate KV caches.
    """
    B, S, D = x.shape
    q = masked_dense_apply(x, p["w_q"]).reshape(B, S, n_heads, head_dim)
    if "bias_q" in p:
        q = q + p["bias_q"].reshape(n_heads, head_dim).astype(q.dtype)
    if mrope_positions is not None:
        q = apply_mrope(q, mrope_positions, mrope_sections, rope_theta)
    elif use_rope:
        q = apply_rope(q, positions, rope_theta)

    if kv_override is not None:
        k, v = kv_override
        k_pos = (k_positions if k_positions is not None
                 else jnp.arange(k.shape[1]))
    else:
        k = masked_dense_apply(x, p["w_k"]).reshape(B, S, n_kv, head_dim)
        v = masked_dense_apply(x, p["w_v"]).reshape(B, S, n_kv, head_dim)
        if "bias_k" in p:
            k = k + p["bias_k"].reshape(n_kv, head_dim).astype(k.dtype)
            v = v + p["bias_v"].reshape(n_kv, head_dim).astype(v.dtype)
        if mrope_positions is not None:
            k = apply_mrope(k, mrope_positions, mrope_sections, rope_theta)
        elif use_rope:
            k = apply_rope(k, positions, rope_theta)
        k_pos = positions

    o = attention_core(q, k, v, positions, k_pos,
                       window=window, causal=causal, chunk_kv=chunk_kv)
    return masked_dense_apply(
        o.reshape(B, S, n_heads * head_dim), p["w_o"]), (k, v)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 Multi-head Latent Attention)
# ---------------------------------------------------------------------------


def mla_init(key, d_model, n_heads, kv_lora, q_lora, qk_nope, qk_rope,
             v_head, dtype=DEFAULT_DTYPE):
    ks = jax.random.split(key, 8)
    p = {
        # KV compression: d -> kv_lora (+ decoupled rope key)
        "w_dkv": dense_init(ks[0], (d_model, kv_lora + qk_rope), dtype),
        "kv_norm_scale": jnp.ones((kv_lora,), jnp.float32),
        "w_uk": dense_init(ks[1], (kv_lora, n_heads * qk_nope), dtype),
        "w_uv": dense_init(ks[2], (kv_lora, n_heads * v_head), dtype),
        "w_o": dense_init(ks[3], (n_heads * v_head, d_model), dtype,
                          fan_in=n_heads * v_head),
    }
    if q_lora:
        p["w_dq"] = dense_init(ks[4], (d_model, q_lora), dtype)
        p["q_norm_scale"] = jnp.ones((q_lora,), jnp.float32)
        p["w_uq"] = dense_init(ks[5], (q_lora, n_heads * (qk_nope + qk_rope)),
                               dtype)
    else:
        p["w_q"] = dense_init(ks[6], (d_model, n_heads * (qk_nope + qk_rope)),
                              dtype)
    return p


def mla_apply(p, x, positions, n_heads, kv_lora, qk_nope, qk_rope, v_head,
              rope_theta=10000.0, chunk_kv=None, cache_kv=None,
              yarn: Yarn | None = None):
    """MLA forward. cache_kv: (c_kv, k_rope) prefilled tensors for decode
    (the compressed-KV cache — MLA's memory saving).  `yarn`: YaRN
    rotary frequencies and amplitude on the decoupled rope dims, and the
    softmax scale (qk_nope + qk_rope)^-0.5 * mscale(factor,
    mscale_all_dim)^2, as DeepSeek-V2 publishes them."""
    B, S, D = x.shape
    if "w_dq" in p:
        cq = rms_norm({"scale": p["q_norm_scale"]},
                      masked_dense_apply(x, p["w_dq"]))
        q = masked_dense_apply(cq, p["w_uq"]).reshape(
            B, S, n_heads, qk_nope + qk_rope)
    else:
        q = masked_dense_apply(x, p["w_q"]).reshape(
            B, S, n_heads, qk_nope + qk_rope)
    q_nope, q_rope = q[..., :qk_nope], q[..., qk_nope:]
    q_rope = apply_rope(q_rope, positions, rope_theta, yarn)

    dkv = masked_dense_apply(x, p["w_dkv"])
    c_kv = rms_norm({"scale": p["kv_norm_scale"]}, dkv[..., :kv_lora])
    k_rope_new = apply_rope(dkv[..., kv_lora:][:, :, None, :], positions,
                            rope_theta, yarn)  # (B,S,1,qk_rope)

    if cache_kv is not None:
        c_kv_all, k_rope_all = cache_kv
        k_pos = jnp.arange(c_kv_all.shape[1])
        q_pos = positions
    else:
        c_kv_all, k_rope_all = c_kv, k_rope_new
        k_pos = positions
        q_pos = positions

    k_nope = masked_dense_apply(c_kv_all, p["w_uk"]).reshape(
        B, -1, n_heads, qk_nope)
    v = masked_dense_apply(c_kv_all, p["w_uv"]).reshape(
        B, -1, n_heads, v_head)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(
            k_rope_all, k_nope.shape[:3] + (qk_rope,))], axis=-1)
    qfull = jnp.concatenate([q_nope, q_rope], axis=-1)
    o = attention_core(qfull, k, v, q_pos, k_pos, window=None, causal=True,
                       chunk_kv=chunk_kv,
                       scale=yarn_attn_scale(qk_nope + qk_rope, yarn))
    # o: (B, S, H, v_head) — attention_core takes Dv from v
    return masked_dense_apply(o.reshape(B, S, -1), p["w_o"]), \
        (c_kv, k_rope_new)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_init(key, d_model, d_ff, dtype=DEFAULT_DTYPE, gated=True,
             act="silu"):
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], (d_model, d_ff), dtype),
         "w_down": dense_init(ks[1], (d_ff, d_model), dtype, fan_in=d_ff)}
    if gated:
        p["w_gate"] = dense_init(ks[2], (d_model, d_ff), dtype)
    return p


def mlp_apply(p, x, act="silu"):
    a = {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
         "gelu_tanh": functools.partial(jax.nn.gelu, approximate=True),
         "relu": jax.nn.relu}[act]
    up = masked_dense_apply(x, p["w_up"])
    if "w_gate" in p:
        up = a(masked_dense_apply(x, p["w_gate"])) * up
    else:
        up = a(up)
    return masked_dense_apply(up, p["w_down"])


# ---------------------------------------------------------------------------
# MoE (dropless sort/gather dispatch over the held experts)
# ---------------------------------------------------------------------------


def moe_init(key, d_model, moe_d_ff, n_experts, n_shared, n_held=0,
             dtype=DEFAULT_DTYPE):
    """Router over all `n_experts`; weights of experts [0, n_held) only
    (n_held 0: all of them).  A held expert keeps its index, so its
    stacked block sits at mask-stream offset e*K*N of the leaf."""
    n_held = n_held or n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router_w": dense_init(ks[0], (d_model, n_experts), jnp.float32),
        # stacked expert weights: (E_held, ...) — EP shards axis 0
        "w_up": dense_init(ks[1], (n_held, d_model, moe_d_ff), dtype),
        "w_gate": dense_init(ks[2], (n_held, d_model, moe_d_ff), dtype),
        "w_down": dense_init(ks[3], (n_held, moe_d_ff, d_model), dtype,
                             fan_in=moe_d_ff),
    }
    if n_shared:
        p["shared"] = mlp_init(ks[4], d_model, moe_d_ff * n_shared, dtype)
    return p


def moe_route(probs: jax.Array, top_k: int, n_held: int):
    """Greedy top-k over the router's probabilities: (gates, experts,
    held).  Gates are the top-k probabilities as they are (not
    renormalized); a (token, slot) pair is computed here when its
    expert is held, and no held pair is ever dropped."""
    gval, gidx = jax.lax.top_k(probs, top_k)
    return gval, gidx, gidx < n_held


def _expert_dim(p):
    w = p["w_up"]
    return (w.w if isinstance(w, MaskedLeaf) else w).shape


def moe_dispatch(gidx, held, n_held: int, plan: ops.GroupedPlan):
    """Sort the held (token, slot) pairs by expert into a group-
    contiguous buffer of `plan.tiles` row tiles.  Returns the layout
    and, per buffer row, its pair (T*k for rows that hold none)."""
    T, k = gidx.shape
    P = T * k
    key = jnp.where(held, gidx, n_held).reshape(P).astype(jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.sum(jax.nn.one_hot(key, n_held, dtype=jnp.int32), axis=0)
    layout = ops.group_layout(sizes, plan.bm, plan.tiles)
    first = jnp.cumsum(sizes) - sizes          # sorted index of each
    rows = jnp.arange(plan.tiles * plan.bm, dtype=jnp.int32)
    grp = jnp.repeat(layout.tile_group, plan.bm)
    rank = rows - layout.starts[grp]
    live = (rank < sizes[grp]) & (rows < layout.n_live[0] * plan.bm)
    pair = jnp.take(order, jnp.clip(first[grp] + rank, 0, P - 1))
    return layout, jnp.where(live, pair, P)


def moe_apply(p, x, n_experts, top_k, router_noise=0.0, key=None):
    """Dropless MoE over the experts this chip holds. x: (B, S, D).

    The router keeps all `n_experts` outputs (float32 softmax, greedy
    top-k, gates not renormalized).  The (token, slot) pairs routed to
    held experts [0, E_held) are sorted by expert and their rows
    gathered into a group-contiguous buffer (static bound
    T*min(top_k, E_held) rows plus per-group padding to the grouped
    kernels' row block, `ops.grouped_plan`); the experts run as grouped
    kernels over it, and a gate-weighted scatter-add brings the rows
    back to their tokens.  Pairs routed to experts not held here
    contribute nothing; no pair is dropped, whatever the imbalance.
    Shared experts run on every token.  Returns (y, aux), aux the
    Switch-style balance loss over all `n_experts`.
    """
    B, S, D = x.shape
    T = B * S
    xt = x.reshape(T, D)
    E_h, _, F = _expert_dim(p)
    with jax.named_scope("moe_router"):
        logits = xt.astype(jnp.float32) @ p["router_w"]
        if router_noise > 0 and key is not None:
            logits = logits + router_noise * jax.random.normal(
                key, logits.shape)
        probs = jax.nn.softmax(logits, axis=-1)             # (T, E)
        gval, gidx, held = moe_route(probs, top_k, E_h)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jnp.sum(jax.nn.one_hot(gidx, n_experts,
                                             dtype=jnp.float32), 1), 0)
        aux = n_experts * jnp.sum(me * ce)
    with jax.named_scope("moe_dispatch"):
        plan = ops.grouped_plan(T * min(top_k, E_h), D, F, E_h,
                                T * top_k / n_experts)
        layout, pair = moe_dispatch(gidx, held, E_h, plan)
        tok = pair // top_k                   # T for rows holding none
        xe = jnp.take(xt, tok, axis=0, mode="fill", fill_value=0)
        gate = jnp.take(gval.reshape(-1), pair, mode="fill",
                        fill_value=0)
    with jax.named_scope("moe_experts"):
        h = jax.nn.silu(masked_grouped_apply(xe, p["w_gate"], layout)) \
            * masked_grouped_apply(xe, p["w_up"], layout)
        ye = masked_grouped_apply(h, p["w_down"], layout)
    with jax.named_scope("moe_combine"):
        # rows holding no pair (padding, dead tiles: never written by
        # the kernels) are selected away, not multiplied by a zero gate
        contrib = jnp.where((pair < T * top_k)[:, None],
                            gate[:, None] * ye.astype(jnp.float32), 0.0)
        y = jnp.zeros((T, D), jnp.float32).at[tok].add(contrib,
                                                        mode="drop")
        y = y.astype(x.dtype).reshape(B, S, D)
    if "shared" in p:
        with jax.named_scope("moe_shared"):
            y = y + mlp_apply(p["shared"], x)
    return y, aux


# ---------------------------------------------------------------------------
# Causal temporal conv (mamba2 / recurrentgemma frontends)
# ---------------------------------------------------------------------------


def conv1d_init(key, width, channels, dtype=DEFAULT_DTYPE):
    return {"w_conv": dense_init(key, (width, channels), dtype,
                                 fan_in=width),
            "bias_conv": jnp.zeros((channels,), jnp.float32)}


def conv1d_causal(p, x):
    """Depthwise causal conv. x: (B, S, C); kernel (W, C).

    Dispatches through `masked_conv1d_apply`: MaskedLeaf kernels run
    the fused masked tap loop, plain kernels the mask-free twin — both
    one Pallas pass, with no (B, S, W, C) stacked-views temporary."""
    out = masked_conv1d_apply(x, p["w_conv"])
    return (out + p["bias_conv"]).astype(x.dtype)


def conv1d_step(p, buf, x_t):
    """Single decode step with rolling buffer. buf: (B, W-1, C).

    Decode-path note: `effective_weight` re-materializes m⊙w from a
    MaskedLeaf EVERY step — decode sessions must freeze the mask once
    at prefill (`masking.freeze_for_decode`, see `launch/serve.py`), so
    steady-state decode sees a plain array here and does zero mask
    resampling."""
    w_conv = effective_weight(p["w_conv"])
    W = w_conv.shape[0]
    full = jnp.concatenate([buf, x_t[:, None]], axis=1)  # (B, W, C)
    out = jnp.einsum("bwc,wc->bc", full.astype(jnp.float32),
                     w_conv.astype(jnp.float32)) + p["bias_conv"]
    return full[:, 1:], out.astype(x_t.dtype)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def embed_lookup(table, tokens):
    return jnp.take(table, tokens, axis=0)


def unembed(table, x):
    return x.astype(jnp.float32) @ table.astype(jnp.float32).T
