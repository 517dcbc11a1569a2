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


def masked_grouped_apply(x: jax.Array, p) -> jax.Array:
    """y[e] = x[e] @ w_eff[e] for a stacked (E, K, N) weight (MoE
    expert einsums; x: (E, ..., K)).

    Plain array: the batched einsum (float baselines, materialized
    effective params).  MaskedLeaf: ONE grouped Pallas launch for all
    E groups — per-group `seed`/`off` stream coordinates make each
    expert's mask exactly its slice of the leaf's flat uplink stream,
    and the stacked m⊙w never exists in HBM on either pass."""
    if isinstance(p, MaskedLeaf):
        if p.mode == "threshold":
            return ops.masked_dense_grouped_threshold(x, p.w, p.s, p.tau)
        return ops.masked_dense_grouped(x, p.w, p.s, p.seed, p.off)
    shape = x.shape
    y = jnp.einsum("ecd,edf->ecf", x.reshape(shape[0], -1, shape[-1]),
                   p)
    return y.reshape(shape[:-1] + (p.shape[-1],))


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


def apply_rope(x, positions, theta=10000.0):
    """x: (..., S, H, Hd), positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Hd/2)
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]
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
                   chunk_kv: int | None = None, soft_cap: float | None = None):
    """q: (B, Sq, H, Hd); k: (B, Sk, Kv, Hd); v: (B, Sk, Kv, Dv).
    GQA by head repetition; Dv may differ from Hd (MLA).

    chunk_kv: if set, run online-softmax over KV chunks (flash-style
    memory behaviour: never materializes the (Sq, Sk) matrix). This is
    the memory path for 32k prefill / 500k contexts.
    """
    B, Sq, H, Hd = q.shape
    Kv = k.shape[2]
    Dv = v.shape[-1]
    rep = H // Kv
    scale = 1.0 / math.sqrt(Hd)
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
        body, (m0, l0, a0),
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
              rope_theta=10000.0, chunk_kv=None, cache_kv=None):
    """MLA forward. cache_kv: (c_kv, k_rope) prefilled tensors for decode
    (the compressed-KV cache — MLA's memory saving)."""
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
    q_rope = apply_rope(q_rope, positions, rope_theta)

    dkv = masked_dense_apply(x, p["w_dkv"])
    c_kv = rms_norm({"scale": p["kv_norm_scale"]}, dkv[..., :kv_lora])
    k_rope_new = apply_rope(dkv[..., kv_lora:][:, :, None, :], positions,
                            rope_theta)  # (B,S,1,qk_rope)

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
                       chunk_kv=chunk_kv)
    # o has head_dim v_head? attention_core keeps q's Hd; v dims differ.
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
# MoE (capacity-based dispatch, EP-shardable on the expert axis)
# ---------------------------------------------------------------------------


def moe_init(key, d_model, moe_d_ff, n_experts, n_shared, dtype=DEFAULT_DTYPE):
    ks = jax.random.split(key, 5)
    p = {
        "router_w": dense_init(ks[0], (d_model, n_experts), jnp.float32),
        # stacked expert weights: (E, ...) — EP shards axis 0
        "w_up": dense_init(ks[1], (n_experts, d_model, moe_d_ff), dtype),
        "w_gate": dense_init(ks[2], (n_experts, d_model, moe_d_ff), dtype),
        "w_down": dense_init(ks[3], (n_experts, moe_d_ff, d_model), dtype,
                             fan_in=moe_d_ff),
    }
    if n_shared:
        p["shared"] = mlp_init(ks[4], d_model, moe_d_ff * n_shared, dtype)
    return p


def moe_apply(p, x, n_experts, top_k, capacity_factor=1.25,
              router_noise=0.0, key=None, block_dispatch=0):
    """GShard-style capacity dispatch. x: (B, S, D) -> (B, S, D).

    Dispatch/combine are einsums so GSPMD shards them (tokens on data,
    experts on model). Dropped tokens (over capacity) fall through on the
    residual path (plus shared experts for DeepSeek-V2).

    block_dispatch=G > 0: tokens are split into G blocks, each with its
    own (G x smaller) expert capacity, and dispatched independently.
    The (T, E, C) dispatch tensor shrinks Gx — the one-hot dispatch
    einsums cost O(T * E * C * D) = O(T^2 * top_k * cf * D / G), so
    block-local dispatch cuts the dominant non-useful FLOPs by G while
    matching real per-device capacity semantics (docs/DESIGN.md §7).
    """
    B, S, D = x.shape
    if block_dispatch and B * S % block_dispatch == 0 \
            and B * S // block_dispatch >= 8:
        G = block_dispatch
        xt = x.reshape(G, (B * S) // G, 1, D)
        y, aux = jax.vmap(
            lambda xb: moe_apply(p, xb, n_experts, top_k,
                                 capacity_factor, 0.0, None, 0))(xt)
        return y.reshape(B, S, D), jnp.mean(aux)
    T = B * S
    xt = x.reshape(T, D)
    logits = (xt.astype(jnp.float32) @ p["router_w"])
    if router_noise > 0 and key is not None:
        logits = logits + router_noise * jax.random.normal(
            key, logits.shape)
    probs = jax.nn.softmax(logits, axis=-1)                 # (T, E)
    gval, gidx = jax.lax.top_k(probs, top_k)                # (T, k)
    gval = gval / jnp.maximum(jnp.sum(gval, -1, keepdims=True), 1e-9)

    cap = max(int(T * top_k * capacity_factor / n_experts), 4)
    # position of each (token, slot) within its expert queue
    onehot = jax.nn.one_hot(gidx, n_experts, dtype=jnp.float32)  # (T,k,E)
    flat = onehot.reshape(T * top_k, n_experts)
    pos_in_e = (jnp.cumsum(flat, axis=0) - flat).reshape(
        T, top_k, n_experts)
    pos = jnp.sum(pos_in_e * onehot, axis=-1)               # (T, k)
    keep = pos < cap
    gval = gval * keep

    # dispatch tensor (T, E, C)
    pos_oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32) \
        * keep[..., None]                                    # (T,k,C)
    disp = jnp.einsum("tke,tkc->tec", onehot, pos_oh)        # (T,E,C)
    xe = jnp.einsum("tec,td->ecd", disp, xt.astype(jnp.float32))
    # xe stays f32 through the expert stack: the chain then carries NO
    # intermediate bf16 rounding, so the fused (Pallas) and plain
    # (einsum) branches of masked_grouped_apply are bit-identical —
    # XLA's excess-precision pass would elide a bf16 round-trip on the
    # einsum branch but not on a physical pallas output buffer

    # stacked (E, ., .) expert weights ride the GROUPED fused kernels:
    # one pallas_call per projection covers all E experts (per-expert
    # seed/off = expert's slice of the leaf's hash stream), so the
    # stacked m⊙w is never materialized — plain arrays (float
    # baselines, REPRO_EFF_PATH) take the batched einsum
    h = jax.nn.silu(masked_grouped_apply(xe, p["w_gate"])) \
        * masked_grouped_apply(xe, p["w_up"])
    ye = masked_grouped_apply(h, p["w_down"])                # (E,C,D)

    comb = jnp.einsum("tke,tkc,tk->tec", onehot, pos_oh,
                      gval.astype(jnp.float32))
    y = jnp.einsum("tec,ecd->td", comb, ye.astype(jnp.float32))
    y = y.astype(x.dtype).reshape(B, S, D)

    if "shared" in p:
        y = y + mlp_apply(p["shared"], x)

    # aux load-balancing loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(onehot.sum(1), axis=0)
    aux = n_experts * jnp.sum(me * ce)
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
