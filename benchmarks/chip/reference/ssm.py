"""Plain reference of a Mamba-2 language model (SSD, arXiv:2405.21060):
per layer RMSNorm, a fused input projection to (z, x, B, C, dt), a
depthwise causal conv with bias and SiLU over (x, B, C), the selective
state-space recurrence with A = -exp(A_log), dt = softplus(dt + dt_bias)
and a D skip, the gated RMSNorm of y * silu(z), the output projection;
tied embeddings, next-token cross entropy.

The recurrence is evaluated in its quadratic (dual) form over the whole
sequence, y_t = sum_{s<=t} (C_t . B_s) exp(sum_{r=s+1..t} dt_r A) dt_s x_s,
with no chunking, so it is independent of the program's chunked scan.

Departures from the published model, which follow the program's model
definition and are listed in PERF.md: the residual stream is not kept
in float32 between layers (this reference is float32 throughout), and
every norm uses epsilon 1e-6.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip.reference import common as C

F32 = jnp.float32
EPS = 1e-6


def dims(cfg):
    d = cfg["d_model"]
    d_in = cfg["expand"] * d
    nh = d_in // cfg["headdim"]
    return d, d_in, nh, cfg["headdim"], cfg["d_state"], cfg["ngroups"]


def specs(cfg):
    d, d_in, nh, P, N, G = dims(cfg)
    L, V, W = cfg["n_layer"], cfg["vocab_size_padded"], cfg["d_conv"]
    bf = jnp.bfloat16
    ch = d_in + 2 * G * N
    return {
        ("embed", "table"): ((V, d), bf, "embed"),
        ("final_norm", "scale"): ((d,), F32, "ones"),
        ("layers", "norm", "scale"): ((L, d), F32, "ones"),
        ("layers", "gate_norm_scale"): ((L, d_in), F32, "ones"),
        ("layers", "A_log"): ((L, nh), F32, "a_log"),
        ("layers", "D"): ((L, nh), F32, "ones"),
        ("layers", "dt_bias"): ((L, nh), F32, "zeros"),
        ("layers", "conv", "bias_conv"): ((L, ch), F32, "zeros"),
        ("layers", "conv", "w_conv"): ((L, W, ch), bf, "masked"),
        ("layers", "w_in"): ((L, d, 2 * d_in + 2 * G * N + nh), bf,
                             "masked"),
        ("layers", "w_out"): ((L, d_in, d), bf, "masked"),
    }


def float_init(rule, key, shape):
    if rule == "ones":
        return jnp.ones(shape, F32)
    if rule == "zeros":
        return jnp.zeros(shape, F32)
    if rule == "embed":
        return jax.random.normal(key, shape, F32) * 0.02
    if rule == "a_log":     # A = -1 .. -16 across heads
        a = jnp.linspace(1.0, 16.0, shape[-1], dtype=F32)
        return jnp.broadcast_to(jnp.log(a), shape)
    raise ValueError(rule)


def program_arch(cfg):
    d, d_in, nh, P, N, G = dims(cfg)
    return dict(family="ssm", n_layers=cfg["n_layer"], d_model=d,
                n_heads=0, n_kv_heads=0, d_ff=0,
                vocab=cfg["vocab_size_padded"], ssm_state=N,
                ssm_expand=cfg["expand"], ssm_headdim=P, ssm_ngroups=G,
                conv_width=cfg["d_conv"],
                tie_embeddings=bool(cfg["tie_embeddings"]))


def ssd(x, dt, A, Bm, Cm):
    """x (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,G,N) -> (B,S,H,P)."""
    H, G = x.shape[2], Bm.shape[2]
    Bh = jnp.repeat(Bm, H // G, axis=2)
    Ch = jnp.repeat(Cm, H // G, axis=2)
    cs = jnp.cumsum(dt * A, axis=1)                        # (B,S,H)
    S = x.shape[1]
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    seg = cs[:, :, None, :] - cs[:, None, :, :]            # t, s
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    cb = jnp.einsum("bthn,bshn->btsh", Ch, Bh)
    return jnp.einsum("btsh,bsh,bshp->bthp", cb * decay, dt, x)


def conv(x, w, b):
    """Depthwise causal conv: y[s] = sum_t x[s + t - (W-1)] w[t] + b."""
    W, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    return sum(xp[:, t:t + S] * w[t] for t in range(W)) + b


def loss(cfg, eff, floats, tokens, act):
    d, d_in, nh, P, N, G = dims(cfg)
    q = lambda a: C.quantize(a, act)
    B, S = tokens.shape

    def layer(x, w, f):
        h = q(C.rms_norm(x, f[("norm", "scale")], EPS))
        zx = h @ w[("w_in",)]
        z, xs, Bm, Cm, dt = jnp.split(
            zx, [d_in, 2 * d_in, 2 * d_in + G * N, 2 * d_in + 2 * G * N],
            axis=-1)
        c = jax.nn.silu(conv(q(jnp.concatenate([xs, Bm, Cm], axis=-1)),
                             w[("conv", "w_conv")],
                             f[("conv", "bias_conv")]))
        xs = c[..., :d_in].reshape(B, S, nh, P)
        Bm = c[..., d_in:d_in + G * N].reshape(B, S, G, N)
        Cm = c[..., d_in + G * N:].reshape(B, S, G, N)
        dt = jax.nn.softplus(dt + f[("dt_bias",)])
        A = -jnp.exp(f[("A_log",)])
        y = ssd(q(xs), dt, A, q(Bm), q(Cm)) + xs * f[("D",)][:, None]
        y = y.reshape(B, S, d_in) * jax.nn.silu(z)
        y = q(C.rms_norm(y, f[("gate_norm_scale",)], EPS))
        return x + y @ w[("w_out",)]

    emb = floats[("embed", "table")].astype(F32)
    x = emb[tokens]
    x = C.scan_layers(layer, x, eff, floats)
    x = q(C.rms_norm(x, floats[("final_norm", "scale")], EPS))
    return C.next_token_nll(x @ emb.T, tokens)
