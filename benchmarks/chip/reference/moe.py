"""Plain reference of a DeepSeek-V2 decoder holding one chip's share of
its experts (arXiv:2405.04434; DeepSeek-V2-Lite's config.json):
RMSNorm, multi-head latent attention (no q compression) with YaRN
rotary positions, a dense SwiGLU layer first, then MoE layers whose
router scores all published experts (softmax, greedy top-k, gates not
renormalized) while only experts [0, n_routed_experts) are held and
computed here, plus the shared experts on every token; untied output
head over the vocabulary slice, next-token cross entropy.

The share: each MoE layer adds, for every token, the gate-weighted
outputs of the held experts among its top-k and nothing for the
others; the held experts are evaluated on every token here and
weighted by a gate that is zero where the expert is not chosen.

Departures from the published model, which follow the program's model
definition and are listed in PERF.md: token embeddings are scaled by
sqrt(hidden_size) before the first layer, and the loss adds 0.01 times
a Switch-style balance term over all router experts (E * sum_e of the
mean router probability times the mean count of top-k picks), where
the published config gives no weight.  The rotary dimensions use the
halves convention (the published code permutes interleaved pairs into
halves first; with random weights the two are a fixed relabelling).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.chip.reference import common as C

F32 = jnp.float32
Q_BLOCK = 1024          # query rows per attention block


def dims(cfg):
    """(d, heads, nope, rope, v, kv_rank, router width, held)."""
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["router_experts"],
            cfg["n_routed_experts"])


def _attn_specs(L, cfg):
    d, nh, nope, rope, vd, r, _, _ = dims(cfg)
    bf = jnp.bfloat16
    m = lambda *s: ((L,) + s, bf, "masked")
    return {
        ("attn", "w_q"): m(d, nh * (nope + rope)),
        ("attn", "w_dkv"): m(d, r + rope),
        ("attn", "kv_norm_scale"): ((L, r), F32, "ones"),
        ("attn", "w_uk"): m(r, nh * nope),
        ("attn", "w_uv"): m(r, nh * vd),
        ("attn", "w_o"): m(nh * vd, d),
        ("attn_norm", "scale"): ((L, d), F32, "ones"),
        ("ffn_norm", "scale"): ((L, d), F32, "ones"),
    }


def layer_counts(cfg):
    """(dense layers, MoE layers)."""
    n_dense = cfg["first_k_dense_replace"]
    return n_dense, cfg["num_hidden_layers"] - n_dense


def specs(cfg):
    """{path: (shape, dtype, kind)} of one cohort's parameters."""
    d, _, _, _, _, _, E, Eh = dims(cfg)
    V, F = cfg["vocab_size"], cfg["intermediate_size"]
    Fe = cfg["moe_intermediate_size"]
    Fs = Fe * cfg["n_shared_experts"]
    Ld, Lm = layer_counts(cfg)
    bf = jnp.bfloat16
    out = {
        ("embed", "table"): ((V, d), bf, "embed"),
        ("lm_head", "table"): ((V, d), bf, "embed"),
        ("final_norm", "scale"): ((d,), F32, "ones"),
    }
    for k, v in _attn_specs(Ld, cfg).items():
        out[("layers",) + k] = v
    for k, v in _attn_specs(Lm, cfg).items():
        out[("moe_layers",) + k] = v
    dm = lambda *s: ((Ld,) + s, bf, "masked")
    mm = lambda *s: ((Lm,) + s, bf, "masked")
    out.update({
        ("layers", "mlp", "w_gate"): dm(d, F),
        ("layers", "mlp", "w_up"): dm(d, F),
        ("layers", "mlp", "w_down"): dm(F, d),
        ("moe_layers", "moe", "router_w"): ((Lm, d, E), F32, "router"),
        ("moe_layers", "moe", "w_gate"): mm(Eh, d, Fe),
        ("moe_layers", "moe", "w_up"): mm(Eh, d, Fe),
        ("moe_layers", "moe", "w_down"): mm(Eh, Fe, d),
        ("moe_layers", "moe", "shared", "w_gate"): mm(d, Fs),
        ("moe_layers", "moe", "shared", "w_up"): mm(d, Fs),
        ("moe_layers", "moe", "shared", "w_down"): mm(Fs, d),
    })
    return out


def float_init(rule, key, shape):
    if rule == "ones":
        return jnp.ones(shape, F32)
    if rule == "embed":
        return jax.random.normal(key, shape, F32) * 0.02
    if rule == "router":
        return jax.random.normal(key, shape, F32) / math.sqrt(shape[-2])
    raise ValueError(rule)


def program_arch(cfg):
    """The program's ArchConfig fields for this configuration."""
    d, nh, nope, rope, vd, r, E, Eh = dims(cfg)
    y = cfg["rope_scaling"]
    return dict(family="moe", n_layers=cfg["num_hidden_layers"],
                d_model=d, n_heads=nh, n_kv_heads=nh,
                d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                kv_lora_rank=r, q_lora_rank=0, qk_nope_dim=nope,
                qk_rope_dim=rope, v_head_dim=vd, n_experts=E,
                n_held_experts=Eh, n_shared_experts=cfg["n_shared_experts"],
                top_k=cfg["num_experts_per_tok"],
                moe_d_ff=cfg["moe_intermediate_size"],
                first_dense_layers=cfg["first_k_dense_replace"],
                rope_theta=float(cfg["rope_theta"]),
                yarn_factor=float(y["factor"]),
                yarn_original_max_pos=y["original_max_position_embeddings"],
                yarn_beta_fast=float(y["beta_fast"]),
                yarn_beta_slow=float(y["beta_slow"]),
                yarn_mscale=float(y["mscale"]),
                yarn_mscale_all_dim=float(y["mscale_all_dim"]),
                **cfg["program_settings"])


# ---------------------------------------------------------------------------
# YaRN rotary positions (DeepseekV2YarnRotaryEmbedding)
# ---------------------------------------------------------------------------


def yarn_mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn_inv_freq(dim, base, y):
    """Frequencies for `dim` rotary dims: base^(-2i/dim) where i lies
    above the correction range, base^(-2i/dim) / factor below it, a
    linear ramp between."""
    def corr(rot):
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    inter = extra / y["factor"]
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low)
                    / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return inter * (1.0 - mask) + extra * mask


def rope(x, cfg):
    """YaRN rotary embedding on (B, S, H, dim), halves convention."""
    y = cfg["rope_scaling"]
    S, dim = x.shape[1], x.shape[-1]
    inv = yarn_inv_freq(dim, float(cfg["rope_theta"]), y)
    amp = (yarn_mscale(y["factor"], y["mscale"])
           / yarn_mscale(y["factor"], y["mscale_all_dim"]))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv
    sin = (jnp.sin(ang) * amp)[:, None, :]
    cos = (jnp.cos(ang) * amp)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def softmax_scale(cfg):
    y = cfg["rope_scaling"]
    m = yarn_mscale(y["factor"], y["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 \
        * m * m


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------


def block_weights(w, s, seed, layer):
    """Effective weights of one layer of a stacked leaf whose layer
    slice is lead + (K, N): its blocks follow one another in the leaf's
    flat stream, block b of layer l at (l * blocks + b) * K * N."""
    lead = w.shape[:-2]
    K, N = w.shape[-2:]
    nb = math.prod(lead)
    first = (layer.astype(jnp.uint32) * jnp.uint32(nb)
             + jnp.arange(nb, dtype=jnp.uint32)) * jnp.uint32(K * N)
    idx = (first.reshape(lead + (1, 1))
           + jnp.arange(K * N, dtype=jnp.uint32).reshape(K, N))
    return C.masked_weight(w, s, C.hash_uniform(idx, seed))


def attention(h, w, f, cfg, q):
    """MLA over (B, S, d) in blocks of query rows."""
    d, nh, nope, rope_d, vd, r, _, _ = dims(cfg)
    eps = cfg["rms_norm_eps"]
    B, S, _ = h.shape
    qh = (h @ w[("attn", "w_q")]).reshape(B, S, nh, nope + rope_d)
    dkv = h @ w[("attn", "w_dkv")]
    c = q(C.rms_norm(dkv[..., :r], f[("attn", "kv_norm_scale")], eps))
    k_pe = rope(dkv[..., r:][:, :, None, :], cfg)
    q_full = jnp.concatenate([qh[..., :nope], rope(qh[..., nope:], cfg)],
                             axis=-1)
    k_nope = (c @ w[("attn", "w_uk")]).reshape(B, S, nh, nope)
    v = q((c @ w[("attn", "w_uv")]).reshape(B, S, nh, vd))
    k = q(jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (B, S, nh, rope_d))], axis=-1))
    q_full = q(q_full) * F32(softmax_scale(cfg))
    qb = min(Q_BLOCK, S)
    nb = S // qb

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q_full, i * qb, qb, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k)
        rows = i * qb + jnp.arange(qb)
        ok = rows[:, None] >= jnp.arange(S)[None, :]
        p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    o = jax.lax.map(block, jnp.arange(nb))            # (nb, B, qb, nh, vd)
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, nh * vd)
    return q(o) @ w[("attn", "w_o")]


def swiglu(h, wg, wu, wd, q):
    return q(jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def moe(h, w, f, cfg, q):
    """The held experts' part of the routed layer, the shared experts,
    and the balance term."""
    _, _, _, _, _, _, E, Eh = dims(cfg)
    k = cfg["num_experts_per_tok"]
    B, S, D = h.shape
    x = h.reshape(B * S, D)
    probs = jax.nn.softmax(x @ f[("moe", "router_w")], axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    picks = jax.nn.one_hot(idx, E, dtype=F32)               # (T, k, E)
    gates = jnp.einsum("tk,tke->te", top, picks)[:, :Eh]    # 0 unpicked
    wg, wu, wd = (w[("moe", n)] for n in ("w_gate", "w_up", "w_down"))
    a = q(jax.nn.silu(jnp.einsum("td,edf->tef", x, wg))
          * jnp.einsum("td,edf->tef", x, wu))
    y = jnp.einsum("tef,efd,te->td", a, wd, gates)
    y = y + swiglu(x, w[("moe", "shared", "w_gate")],
                   w[("moe", "shared", "w_up")],
                   w[("moe", "shared", "w_down")], q)
    aux = E * jnp.sum(jnp.mean(probs, 0) * jnp.mean(picks.sum(1), 0))
    return y.reshape(B, S, D), aux


def _scan(body, carry, eff, floats, prefix):
    mp = {p[1:]: v for p, v in eff.items() if p[0] == prefix}
    fp = {p[1:]: v for p, v in floats.items() if p[0] == prefix}
    n = next(iter(mp.values()))[0].shape[0]

    @jax.checkpoint
    def step(carry, xs):
        l, ws, ss, fs = xs
        lw = {k: block_weights(ws[k], ss[k], mp[k][2], l) for k in ws}
        return body(carry, lw, fs), None

    xs = (jnp.arange(n, dtype=jnp.int32),
          {k: v[0] for k, v in mp.items()},
          {k: v[1] for k, v in mp.items()}, fp)
    carry, _ = jax.lax.scan(step, carry, xs)
    return carry


def loss(cfg, eff, floats, tokens, act):
    """Mean next-token cross entropy of `tokens` (B, S) over the
    vocabulary slice, plus 0.01 times the balance term summed over the
    MoE layers."""
    d = cfg["hidden_size"]
    eps = cfg["rms_norm_eps"]
    q = lambda a: C.quantize(a, act)

    def dense_layer(x, w, f):
        h = q(C.rms_norm(x, f[("attn_norm", "scale")], eps))
        x = x + attention(h, w, f, cfg, q)
        h = q(C.rms_norm(x, f[("ffn_norm", "scale")], eps))
        return x + swiglu(h, w[("mlp", "w_gate")], w[("mlp", "w_up")],
                          w[("mlp", "w_down")], q)

    def moe_layer(carry, w, f):
        x, aux = carry
        h = q(C.rms_norm(x, f[("attn_norm", "scale")], eps))
        x = x + attention(h, w, f, cfg, q)
        h = q(C.rms_norm(x, f[("ffn_norm", "scale")], eps))
        y, a = moe(h, w, f, cfg, q)
        return x + y, aux + a

    emb = floats[("embed", "table")].astype(F32)
    x = emb[tokens] * F32(math.sqrt(d))
    x = _scan(dense_layer, x, eff, floats, "layers")
    x, aux = _scan(moe_layer, (x, F32(0.0)), eff, floats, "moe_layers")
    x = q(C.rms_norm(x, floats[("final_norm", "scale")], eps))
    logits = x @ floats[("lm_head", "table")].astype(F32).T
    return C.next_token_nll(logits, tokens) + 0.01 * aux
