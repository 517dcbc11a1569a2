"""Plain float32 reference of masked federated training and of the mask
round, written from the method's description (FedPM with the entropy
regularizer, arXiv:2309.10834) and the program's stated conventions.
It imports nothing of the program.

Conventions the reference shares with the system under test, because
they define *which* random draws a run makes, not how it computes:

* the counter-based mask stream: a masked leaf's mask element at flat
  index ``idx`` is ``1[hash_u(idx, seed) < sigmoid(score)]`` with
  ``seed = stream_seed(step, shard, leaf index, cohort, run seed)``;
  leaf indices enumerate the parameter tree in sorted-key order;
* the arithmetic coder's size formula (the wire format's length);
* the state: weights frozen in bfloat16, scores in float32, float
  leaves in their own dtype, momentum on the scores.

Everything else (the forward pass, the STE gradient, the optimizer, the
fold, the downlink rounding) is computed here in float32 at "highest"
matmul precision.  `act` is the precision activations are held in
before each matmul: float32 for the reference, float8 (e4m3) for the
control, which stands in the program's place one precision below the
bfloat16 that the configurations state.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


# ---------------------------------------------------------------------------
# The mask stream
# ---------------------------------------------------------------------------


def hash_uniform(idx, seed):
    """uint32 counter hash -> uniform in [0, 1) on a 2^-24 grid."""
    s = jnp.asarray(seed, jnp.uint32) + jnp.uint32(1)
    s = (s ^ (s >> 16)) * jnp.uint32(0x45D9F3B5)
    s = s ^ (s >> 11)
    x = idx.astype(jnp.uint32) + jnp.uint32(0x9E3779B9) * s
    x = (x ^ (x >> 16)) * jnp.uint32(0x85EBCA6B)
    x = (x ^ s ^ (x >> 13)) * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return (x >> 8).astype(F32) * F32(1.0 / (1 << 24))


def stream_seed(step, shard, leaf_idx: int, cohort, run_seed):
    """(step, shard, leaf, cohort, run seed) -> uint32 stream seed."""
    u = lambda v: jnp.asarray(v, jnp.uint32)
    base = (u(step) * u(0x9E3779B9)
            ^ (u(shard) + u(1)) * u(0x85EBCA6B)
            ^ u(leaf_idx * 0xC2B2AE35 & 0xFFFFFFFF)
            ^ u(run_seed) * u(0x7FEB352D))
    return base + u(cohort) * u(0x01000193)


def leaf_uniform(shape, seed):
    """The stream's uniforms for a whole leaf: flat row-major index."""
    n = int(np.prod(shape))
    return hash_uniform(jnp.arange(n, dtype=jnp.uint32).reshape(shape),
                        seed)


def masked_weight(w, s, u):
    """m * w with m = 1[u < sigmoid(s)], straight-through to s:
    d(m*w)/ds = w * sigmoid'(s)."""
    sig = jax.nn.sigmoid(s.astype(F32))
    m = (u < jax.lax.stop_gradient(sig)).astype(F32)
    return w.astype(F32) * (m + (sig - jax.lax.stop_gradient(sig)))


# ---------------------------------------------------------------------------
# Parameters from the seed
# ---------------------------------------------------------------------------


def sorted_paths(specs):
    """Leaf paths in the tree's flattening order (sorted dict keys)."""
    return sorted(specs)


def init_leaves(specs, key, float_init):
    """Every leaf from the key, in one traceable function.

    specs: {path tuple: (shape, dtype, kind)}, kind "masked" or a float
    rule name that `float_init(rule, key, shape)` understands.
    Masked leaves get frozen weights w = +-sqrt(2 / fan_in) (the paper's
    signed constant, fan_in = the leaf's input width) in bfloat16 and
    scores logit(theta), theta ~ U(1e-4, 1 - 1e-4), in float32.
    Returns {path: w or float leaf}, {path: score}.
    """
    vals, scores = {}, {}
    for i, path in enumerate(sorted_paths(specs)):
        shape, dtype, kind = specs[path]
        k = jax.random.fold_in(key, i)
        if kind == "masked":
            fan_in = shape[-2]
            c = math.sqrt(2.0 / fan_in)
            sign = jax.random.rademacher(jax.random.fold_in(k, 0), shape,
                                         dtype=F32)
            vals[path] = (sign * F32(c)).astype(dtype)
            th = jax.random.uniform(jax.random.fold_in(k, 1), shape, F32,
                                    minval=1e-4, maxval=1 - 1e-4)
            scores[path] = jnp.log(th) - jnp.log1p(-th)
        else:
            vals[path] = float_init(kind, jax.random.fold_in(k, 2),
                                    shape).astype(dtype)
    return vals, scores


def masked_paths(specs):
    return [p for p in sorted_paths(specs) if specs[p][2] == "masked"]


def leaf_index(specs):
    """{path: index in the flattened tree} (float leaves included)."""
    return {p: i for i, p in enumerate(sorted_paths(specs))}


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def quantize(x, act):
    """Hold an activation in `act` on the forward pass (identity for
    float32); the gradient passes through unrounded, so the control's
    backward differs only by what its forward rounding feeds it."""
    if act == F32 or act is None:
        return x
    return x + jax.lax.stop_gradient(x.astype(act).astype(F32) - x)


def next_token_nll(logits, tokens):
    """Mean cross entropy of token t+1 given the prefix up to t."""
    lg = logits[:, :-1]
    tgt = tokens[:, 1:]
    lse = jax.nn.logsumexp(lg, axis=-1)
    at = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - at)


# ---------------------------------------------------------------------------
# Training: per-cohort steps
# ---------------------------------------------------------------------------


def make_cohort_step(family, cfg, specs, opt, run_seed, act=F32,
                     batch_rows=None):
    """One local step of one cohort:
    (scores, floats, momentum, weights, tokens, tick, cohort) ->
    (scores, floats, momentum, loss, score grads, float grads).

    `scores`/`momentum` are {masked path: array}, `floats` {float path:
    array}, `weights` {masked path: bf16 array}.  The loss the optimizer
    sees is the next-token cross entropy plus lam times the mean
    sigmoid of every score (eq. 12); the reported loss is the cross
    entropy alone.  `batch_rows` keeps only the first rows of the
    batch (a fault the checks must catch)."""
    idx = leaf_index(specs)
    mpaths = masked_paths(specs)
    n_masked = sum(int(np.prod(specs[p][0])) for p in mpaths)
    lam, lr, mom_c, flr = (opt["lam"], opt["lr"], opt["momentum"],
                           opt["float_lr"])

    def loss_fn(scores, floats, weights, tokens, tick, cohort):
        eff = {}
        for p in mpaths:
            seed = stream_seed(tick, 0, idx[p], cohort, run_seed)
            eff[p] = (weights[p], scores[p], seed)
        if batch_rows is not None:
            tokens = tokens[:batch_rows]
        nll = family.loss(cfg, eff, floats, tokens, act)
        reg = sum(jnp.sum(jax.nn.sigmoid(scores[p])) for p in mpaths)
        return nll + lam * reg / F32(n_masked), nll

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(scores, floats, mom, weights, tokens, tick, cohort):
        with jax.default_matmul_precision("highest"):
            (_, nll), (gs, gf) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(
                    scores, floats, weights, tokens, tick, cohort)
        mom = {p: mom_c * mom[p] + gs[p] for p in mom}
        scores = {p: scores[p] - lr * mom[p] for p in scores}
        floats = {p: (floats[p].astype(F32) - flr * gf[p].astype(F32))
                  .astype(floats[p].dtype) for p in floats}
        return scores, floats, mom, nll, gs, gf

    return step


def worst_leaf_gap(prog: dict, ref: dict, keep=None):
    """Largest |prog norm - ref norm| over leaves, each measured against
    the larger of that leaf's reference norm and the median leaf's."""
    keys = [k for k in ref if keep is None or k in keep]
    med = float(np.median([ref[k] for k in keys]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
               for k in keys)


# ---------------------------------------------------------------------------
# The mask round
# ---------------------------------------------------------------------------

_PSCALE = 1 << 16


def arithmetic_bits(ones, n: int):
    """Length of the arithmetic-coded uplink of one cohort's pooled bits:
    word-aligned ideal Bernoulli code length at the 16-bit quantized
    prior, plus a 32-bit header and the coder's termination slack."""
    p = ones.astype(F32) / F32(n)
    p1c = jnp.clip(jnp.round(p * F32(_PSCALE)).astype(jnp.int32), 1,
                   _PSCALE - 1)
    p1 = p1c.astype(F32) / F32(_PSCALE)
    ideal = -(ones.astype(F32) * jnp.log2(p1)
              + (jnp.int32(n) - ones).astype(F32) * jnp.log2(1 - p1))
    tot = jnp.ceil(ideal).astype(jnp.int32) + 32 + 48 + (n >> 13)
    return (tot + 31) // 32 * 32


def round_theta(score_leaf, seeds, sig_dtype=F32):
    """theta = mean over cohorts of each cohort's sampled mask, and the
    cohorts' one-counts.  score_leaf: (C, ...) scores, seeds: (C,)."""
    C = score_leaf.shape[0]
    ones, acc = [], jnp.zeros(score_leaf.shape[1:], F32)
    for c in range(C):
        u = leaf_uniform(score_leaf.shape[1:], seeds[c])
        sig = jax.nn.sigmoid(score_leaf[c].astype(sig_dtype).astype(F32))
        m = (u < sig).astype(F32)
        ones.append(jnp.sum(m.astype(jnp.int32)))
        acc = acc + m
    return acc / F32(C), jnp.stack(ones)


def downlink_support(theta, bits: int):
    """The two levels a stochastic k-bit rounding of theta may take."""
    levels = (1 << bits) - 1
    x = jnp.clip(theta, 0.0, 1.0) * levels
    return jnp.floor(x), jnp.ceil(x)


def layer_weights(w, s, seed, layer):
    """Effective weights of one layer of a stacked (L, K, N) leaf: the
    layer's block of the leaf's flat stream starts at layer * K * N."""
    K, N = w.shape[-2:]
    off = (layer * (K * N)).astype(jnp.uint32)
    idx = off + jnp.arange(K * N, dtype=jnp.uint32).reshape(K, N)
    return masked_weight(w, s, hash_uniform(idx, seed))


def scan_layers(body, x, eff, floats, prefix="layers", remat=True):
    """Run `body(x, weights, floats)` over the stacked layers; `weights`
    maps each masked leaf name under `prefix` to its effective layer
    weights, `floats` each float leaf name to its layer slice."""
    mp = {p[1:]: v for p, v in eff.items() if p[0] == prefix}
    fp = {p[1:]: v for p, v in floats.items() if p[0] == prefix}
    n = next(iter(mp.values()))[0].shape[0]

    def step(x, xs):
        l, ws, ss, fs = xs
        lw = {k: layer_weights(ws[k], ss[k], mp[k][2], l) for k in ws}
        return body(x, lw, fs), None

    if remat:
        step = jax.checkpoint(step)
    xs = (jnp.arange(n, dtype=jnp.int32),
          {k: v[0] for k, v in mp.items()},
          {k: v[1] for k, v in mp.items()}, fp)
    x, _ = jax.lax.scan(step, x, xs)
    return x
