"""Serving launcher: single-tenant batched decode or the multi-tenant
continuous-batching engine over one shared frozen weight copy.

Serving deliberately consumes the REFERENCE path (docs/DESIGN.md §3):
a deployed mask is static, so each tenant's masked tree is frozen ONCE
(`masking.freeze_identity` — the threshold-mode deterministic mask a
FedMask artifact ships) and every decode step reuses the materialized
params, doing ZERO mask resampling in steady state.  The fused
(w, s, seed) path is the *training* hot path
(`launch.steps.make_train_step`).

Single tenant (the original demo, timing fixed: warmup step off the
clock, `time.perf_counter`, prefill and decode tok/s reported
separately):

    python -m repro.launch.serve --arch gemma3-4b --smoke --tokens 16

Multi-tenant (the `repro.runtime.serve_engine.ServeEngine` engine:
per-slot mask identity, bounded LRU freeze-cache, prefill/decode
continuous batching — resident weight HBM stays ONE shared `w` while
tenants grow past the cache capacity):

    python -m repro.launch.serve --arch gemma3-4b --smoke \
        --tenants 4 --slots 2 --cache-capacity 2 --tokens 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import masking
from repro.models import build_model
from repro.launch import steps as steplib
from repro.launch.compile_cache import enable_compile_cache


def _serve_single(args, cfg, api, key, mp):
    """The original single-tenant batched greedy decode, timing fixed:
    jit compilation happens in a warmup step OFF the clock, timing uses
    `time.perf_counter`, and prefill vs decode tok/s are reported
    separately."""
    ident = masking.MaskIdentity(seed=args.seed, mode="threshold")
    eff = masking.freeze_identity(mp, ident)

    B = args.batch
    P = args.prompt_len
    S = P + args.tokens
    serve = jax.jit(steplib.make_serve_step(api))
    cache = api.init_cache(B, S)
    prompt = jax.random.randint(key, (B, P), 0, cfg.vocab)

    # warmup: one step on a scratch cache so the first TIMED step is
    # compile-free (t0 used to include the whole jit compile)
    scratch = api.init_cache(B, S)
    out = serve(eff, scratch, prompt[:, 0], jnp.asarray(0, jnp.int32))
    jax.block_until_ready(out[0])

    tok = prompt[:, 0]
    prefill_s = decode_s = 0.0
    generated = []
    for t in range(S - 1):
        t0 = time.perf_counter()
        logits, cache = serve(eff, cache, tok, jnp.asarray(t, jnp.int32))
        jax.block_until_ready(logits)
        dt = time.perf_counter() - t0
        if t + 1 < P:
            prefill_s += dt
            tok = prompt[:, t + 1]
        else:
            decode_s += dt
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            generated.append(tok)
    pre_tok = B * (P - 1)
    dec_tok = B * args.tokens
    print(f"{cfg.name}: {B} requests, prefill {pre_tok} tok in "
          f"{prefill_s:.3f}s ({pre_tok / max(prefill_s, 1e-9):.1f} tok/s), "
          f"decode {dec_tok} tok in {decode_s:.3f}s "
          f"({dec_tok / max(decode_s, 1e-9):.1f} tok/s)")
    return {"tokens": jnp.stack(generated, axis=1), "last_logits": logits,
            "prefill_s": prefill_s, "decode_s": decode_s}


def _serve_multi(args, cfg, api, key, mp):
    """Multi-tenant continuous batching: every tenant is a mask
    identity over the SAME `mp.weights`; the engine's freeze-cache
    bounds resident materialized trees to --cache-capacity."""
    from repro.runtime.serve_engine import ServeEngine

    eng = ServeEngine(api, mp, slots=args.slots,
                      cache_capacity=args.cache_capacity,
                      max_seq=args.prompt_len + args.tokens,
                      lockstep=args.lockstep)
    prompts = jax.random.randint(
        key, (args.tenants, args.prompt_len), 0, cfg.vocab)
    import numpy as np
    prompts = np.asarray(prompts)
    for i in range(args.tenants):
        eng.register_tenant(f"tenant{i}", seed=args.seed + i)
        eng.submit(f"tenant{i}", prompts[i], args.tokens)
    done = eng.run()
    st = eng.stats()
    print(f"{cfg.name}: {len(done)}/{args.tenants} tenants served on "
          f"{args.slots} slots (freeze-cache {st['occupancy']}/"
          f"{st['capacity']}, {st['hits']} hits / {st['misses']} misses"
          f" / {st['evictions']} evictions)")
    print(f"  prefill {st['prefill_tokens']} tok "
          f"({st['prefill_tok_s']:.1f} tok/s), "
          f"decode {st['decode_tokens']} tok "
          f"({st['decode_tok_s']:.1f} tok/s)")
    print(f"  resident HBM: 1 x w ({st['weight_bytes']} B) + "
          f"{st['resident_tree_count']} x delta "
          f"({st['delta_bytes_per_tree']} B) = {st['resident_bytes']} B "
          f"for {st['tenants']} tenants "
          f"(mask artifact {st['mask_artifact_bytes']} B/tenant)")
    return {"completions": done, "stats": st}


def main(argv=None) -> dict:
    """Run the launcher; returns the generated tokens and the logits
    that chose them (single tenant: ``tokens``/``last_logits``;
    several: the engine's ``completions`` and ``stats``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-4b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the depth to N layers at published widths "
                         "(0 = the config's own depth)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=16)
    # default 0 = the behaviour before --seed existed (PRNGKey(0)
    # network), so unflagged invocations stay reproducible
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tenants", type=int, default=1,
                    help=">1 drives the multi-tenant engine: one "
                         "request per tenant, distinct mask seeds")
    ap.add_argument("--slots", type=int, default=2,
                    help="concurrent batch slots (multi-tenant)")
    ap.add_argument("--cache-capacity", type=int, default=2,
                    help="freeze-cache bound on resident trees")
    ap.add_argument("--lockstep", action="store_true",
                    help="one vmapped step for all slots per tick "
                         "(throughput mode; not bit-exact)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke, layers=args.layers)
    api = build_model(cfg)
    # --seed picks the frozen random network (the artifact's RNG seed);
    # the deployed threshold mask is deterministic given the scores
    key = jax.random.PRNGKey(args.seed)
    mp = masking.init_masked(key, api.init_params(key),
                             masking.MaskSpec())
    if args.tenants > 1:
        return _serve_multi(args, cfg, api, key, mp)
    return _serve_single(args, cfg, api, key, mp)


if __name__ == "__main__":
    main()
