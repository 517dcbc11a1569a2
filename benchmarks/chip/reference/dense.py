"""Plain reference of a dense decoder with grouped-query attention
(InternLM2, arXiv:2403.17297): RMSNorm, rotary positions, causal GQA,
SwiGLU MLP, untied output head, next-token cross entropy.

Departures from the published model, which follow the program's model
definition and are listed in PERF.md: token embeddings are scaled by
sqrt(hidden_size) before the first layer.  The RMSNorm epsilon is the
published 1e-5 (the program uses 1e-6; the difference is far below
bfloat16 rounding).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.chip.reference import common as C

F32 = jnp.float32


def dims(cfg):
    d = cfg["hidden_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // nh
    return d, nh, nkv, hd


def specs(cfg):
    """{path: (shape, dtype, kind)} of one cohort's parameters."""
    d, nh, nkv, hd = dims(cfg)
    L, F, V = (cfg["num_hidden_layers"], cfg["intermediate_size"],
               cfg["vocab_size"])
    bf = jnp.bfloat16
    m = lambda *s: ((L,) + s, bf, "masked")
    return {
        ("embed", "table"): ((V, d), bf, "embed"),
        ("lm_head", "table"): ((V, d), bf, "embed"),
        ("final_norm", "scale"): ((d,), F32, "ones"),
        ("layers", "attn_norm", "scale"): ((L, d), F32, "ones"),
        ("layers", "ffn_norm", "scale"): ((L, d), F32, "ones"),
        ("layers", "attn", "w_q"): m(d, nh * hd),
        ("layers", "attn", "w_k"): m(d, nkv * hd),
        ("layers", "attn", "w_v"): m(d, nkv * hd),
        ("layers", "attn", "w_o"): m(nh * hd, d),
        ("layers", "mlp", "w_gate"): m(d, F),
        ("layers", "mlp", "w_up"): m(d, F),
        ("layers", "mlp", "w_down"): m(F, d),
    }


def float_init(rule, key, shape):
    if rule == "ones":
        return jnp.ones(shape, F32)
    if rule == "embed":
        return jax.random.normal(key, shape, F32) * 0.02
    raise ValueError(rule)


def program_arch(cfg):
    """The program's ArchConfig fields for this configuration."""
    d, nh, nkv, hd = dims(cfg)
    return dict(family="dense", n_layers=cfg["num_hidden_layers"],
                d_model=d, n_heads=nh, n_kv_heads=nkv,
                d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                head_dim=hd, rope_theta=float(cfg["rope_theta"]))


def rope(x, theta):
    """Rotary embedding on (B, S, H, hd), halves convention."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def loss(cfg, eff, floats, tokens, act):
    """Mean next-token cross entropy of `tokens` (B, S)."""
    d, nh, nkv, hd = dims(cfg)
    eps = cfg["rms_norm_eps"]
    theta = float(cfg["rope_theta"])
    q = lambda a: C.quantize(a, act)
    B, S = tokens.shape
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, w, f):
        h = q(C.rms_norm(x, f[("attn_norm", "scale")], eps))
        qh = rope((h @ w[("attn", "w_q")]).reshape(B, S, nh, hd), theta)
        kh = rope((h @ w[("attn", "w_k")]).reshape(B, S, nkv, hd), theta)
        vh = (h @ w[("attn", "w_v")]).reshape(B, S, nkv, hd)
        qh = q(qh).reshape(B, S, nkv, nh // nkv, hd)
        s = jnp.einsum("bqgrh,bkgh->bgrqk", qh, q(kh)) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bgrqk,bkgh->bqgrh", p, q(vh))
        x = x + q(o.reshape(B, S, nh * hd)) @ w[("attn", "w_o")]
        h = q(C.rms_norm(x, f[("ffn_norm", "scale")], eps))
        a = jax.nn.silu(h @ w[("mlp", "w_gate")]) * (h @ w[("mlp", "w_up")])
        return x + q(a) @ w[("mlp", "w_down")]

    emb = floats[("embed", "table")].astype(F32)
    x = emb[tokens] * F32(math.sqrt(d))
    x = C.scan_layers(layer, x, eff, floats)
    x = q(C.rms_norm(x, floats[("final_norm", "scale")], eps))
    logits = x @ floats[("lm_head", "table")].astype(F32).T
    return C.next_token_nll(logits, tokens)
