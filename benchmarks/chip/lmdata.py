"""Token traffic from the seed: a Zipf-unigram stream with a short-range
Markov drift (a copy of the program's synthetic LM stream generator),
and per-step cohort batches sliced from it on the device."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make_lm_stream(key, n_tokens: int, vocab: int, alpha: float = 1.2):
    """Zipf(alpha) draws mixed half and half with the drift
    next = (prev * 7 + 3) mod vocab."""
    kz, km = jax.random.split(key)
    ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
    probs = ranks ** (-alpha)
    probs = probs / jnp.sum(probs)
    z = jax.random.choice(kz, vocab, (n_tokens,), p=probs)
    mix = jax.random.bernoulli(km, 0.5, (n_tokens,))

    def step(prev, xs):
        zi, mi = xs
        nxt = jnp.where(mi, (prev * 7 + 3) % vocab, zi)
        return nxt, nxt

    _, toks = jax.lax.scan(step, jnp.int32(0),
                           (z.astype(jnp.int32), mix), unroll=8)
    return toks


def cohort_batch(cohorts: int, batch: int, seq: int):
    """(key, stream) -> {"tokens": (cohorts, batch, seq)} of windows at
    uniform offsets of the stream."""
    def make(key, toks):
        idx = jax.random.randint(key, (cohorts, batch), 0,
                                 toks.shape[0] - seq - 1)
        return {"tokens": jax.vmap(jax.vmap(
            lambda i: jax.lax.dynamic_slice(toks, (i,), (seq,))))(idx)}
    return jax.jit(make)


def stream_on_host_cpu(key, n_tokens, vocab, alpha):
    """Build the stream with XLA's CPU backend (its sequential scan is
    far cheaper there than on an accelerator) and return it on the
    default device."""
    try:
        cpu = jax.devices("cpu")[0]
    except RuntimeError:
        return jax.jit(make_lm_stream, static_argnums=(1, 2))(
            key, n_tokens, vocab, alpha)
    with jax.default_device(cpu):
        toks = jax.jit(make_lm_stream, static_argnums=(1, 2))(
            jax.device_put(key, cpu), n_tokens, vocab, alpha)
    return jax.device_put(toks, jax.devices()[0])
