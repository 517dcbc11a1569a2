#!/usr/bin/env python3
"""Bring-up smoke of the main path on TPU, through the normal entry points.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips: the cross-pod round only

The model is internlm2-1.8b at its published widths (d_model 2048, 16/8
heads of 128, d_ff 8192, vocab 92544) with the depth cut to 4 layers:
the federated state of 24 layers does not fit one 16 GB v5e (see the
printed memory analysis).  Weights and data are random, made from --seed.

One chip, in one process:
  (a) `repro.launch.train.main`: fedpm_reg, 2 cohorts of batch 2 x seq
      512, a few masked train steps and two rounds.  The loss must be
      finite, `uplink_bpp` in (0, 1], the measured bits > 0, and the
      compiled train step must hold Pallas kernels (`tpu_custom_call`).
      Prints each masked projection's block plan (`ops.dense_plan`):
      `passes` is how often the forward and dx stream `w` and `s`; and
      the grouped kernels' plan (`ops.grouped_plan`) at the
      deepseek-v2-lite share cell's expert shapes: row block, static
      tile bound, live tiles at the mean load.
  (b) `sample_and_pack` words of one 2048 x 8192 leaf at C=2, read
      flat (as rows of 4096) and at its stacked shape (C, 1, 2048,
      8192) as the round reads it in place, must EQUAL the pure-jnp oracle
      `kernels.ref.sample_and_pack`.  Prints `ops.pack_plan` for every
      masked leaf of both benchmark configurations; every one must be
      packed in place.
  (c) `ops.masked_dense` forward, dx and ds at that leaf against the jnp
      oracle under `default_matmul_precision("highest")`, each inside a
      tolerance derived below.
  (d) `repro.launch.serve.main` decodes a few tokens for 2 tenants with
      finite logits.

--four-chips runs only the round step on `make_debug_pod_mesh()` over
the four devices, with the state placed by `fed_state_shardings`, and
compares the gathered words, theta and the measured bits against the
pure-jnp oracle and the static comm model.

It fails (non-zero exit, no result line) without a TPU, when the kernels
would run in interpret mode, when a REPRO_* path knob is set, and when
it is not next to the repo's `src/`.  No phase's exception is caught.
The last line of stdout is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ARCH = "internlm2-1.8b"
LAYERS = 4            # the deepest cut whose train step fits 16 GB
COHORTS = 2
BATCH, SEQ = 2, 512   # per cohort
STEPS, ROUND_EVERY = 4, 2
LEAF_K, LEAF_N = 2048, 8192   # one real MLP leaf (d_model x d_ff)
KNOBS = ("REPRO_REF_BWD", "REPRO_EFF_PATH", "REPRO_FORCE_INTERPRET")


class SmokeFailure(AssertionError):
    pass


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _gb(nbytes) -> str:
    return "n/a" if nbytes is None else f"{nbytes / 2**30:.2f} GiB"


def _mem(dev, key: str = "peak_bytes_in_use"):
    return (dev.memory_stats() or {}).get(key)


def preflight():
    """Everything that must hold before any phase runs; returns jax."""
    set_knobs = [k for k in KNOBS if os.environ.get(k)]
    require(not set_knobs, f"path knobs set: {set_knobs}; the smoke runs "
                           "only the default fused path")
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    require(os.path.isdir(os.path.join(src, "repro")),
            f"no repro package at {src}: run from a checkout")
    sys.path.insert(0, src)
    import jax
    dev = jax.devices()[0]
    require(dev.platform == "tpu",
            f"no TPU: JAX's first device is {dev.platform}")
    from repro.launch.compile_cache import enable_compile_cache
    from repro.kernels import ops
    print(f"compile cache: {enable_compile_cache()}")
    require(not ops._use_interpret(), "Pallas kernels would be "
                                      "interpreted, not compiled")
    return jax


def phase_train(seed: int) -> dict:
    from repro.launch import train
    out = train.main(["--arch", ARCH, "--layers", str(LAYERS),
                      "--algo", "fedpm_reg", "--cohorts", str(COHORTS),
                      "--batch", str(BATCH), "--seq", str(SEQ),
                      "--steps", str(STEPS),
                      "--round-every", str(ROUND_EVERY),
                      "--seed", str(seed)])
    step = out["compiled_step"]
    n_kernels = step.as_text().count("tpu_custom_call")
    ma = step.memory_analysis()
    print(f"(a) train step: {n_kernels} tpu_custom_call sites; memory "
          f"args {_gb(ma.argument_size_in_bytes)} + temp "
          f"{_gb(ma.temp_size_in_bytes)} (outputs alias the donated "
          f"state: {_gb(ma.alias_size_in_bytes)})")
    require(n_kernels > 0, "compiled train step holds no Pallas kernel")
    print(f"(a) loss {out['loss']!r}, uplink_bpp {out['uplink_bpp']!r}, "
          f"bits_measured {out['bits_measured']!r} after "
          f"{out['rounds']} rounds")
    require(math.isfinite(out["loss"]), "loss is not finite")
    require(out["rounds"] >= 1, "no round ran")
    require(0.0 < out["uplink_bpp"] <= 1.0,
            f"uplink_bpp {out['uplink_bpp']} not in (0, 1]")
    require(out["bits_measured"] > 0, "no measured uplink bits")
    return out


def _score_leaves(jax, arch, cohorts: int):
    """(path, shape) of every masked score leaf of the federated state."""
    from repro.core import masking
    from repro.launch import steps as steplib
    from repro.models import build_model
    api = build_model(arch)
    state = jax.eval_shape(lambda k: steplib.init_fed_state(
        k, api, masking.MaskSpec(), C=cohorts), jax.random.PRNGKey(0))
    return [(jax.tree_util.keystr(path), leaf.shape)
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                state["scores"], is_leaf=lambda x: x is None)[0]
            if leaf is not None]


def projection_passes(jax) -> None:
    """How many times the forward and dx kernels stream each tile of `w`
    and `s` per call (`ops.dense_plan(...).passes`), for every masked
    projection of the train step at one cohort's BATCH x SEQ tokens."""
    from repro.configs import get_config
    from repro.kernels import ops
    for path, shape in _score_leaves(jax, get_config(ARCH, layers=LAYERS),
                                     COHORTS):
        K, N = shape[-2:]
        plan = ops.dense_plan(BATCH * SEQ, K, N)
        print(f"(a) {path} ({K} x {N}) at M={BATCH * SEQ}: bm {plan.bm}, "
              f"bn {plan.bn}, bk {plan.bk}, passes {plan.passes}")


def pack_plans(jax) -> None:
    """`ops.pack_plan` for every masked leaf of both benchmark
    configurations (this cut at COHORTS cohorts; the deepseek-v2-lite
    share at one: 5 layers, 8 experts held), and the share of masked
    parameters whose scores the uplink reads in place."""
    import dataclasses
    from repro.configs import get_config
    from repro.kernels import ops
    share = dataclasses.replace(get_config("deepseek-v2-lite-16b"),
                                n_layers=5, n_held_experts=8)
    for name, arch, cohorts in ((ARCH, get_config(ARCH, layers=LAYERS),
                                 COHORTS), ("deepseek-v2-lite-16b share",
                                            share, 1)):
        total = in_place = 0
        for path, shape in _score_leaves(jax, arch, cohorts):
            pack = ops.pack_plan(shape)
            total += math.prod(shape)
            in_place += math.prod(shape) * pack.in_place
            print(f"(b) pack_plan {name} {path} {shape}: in_place "
                  f"{pack.in_place}, br {pack.br}, grid_steps "
                  f"{pack.grid_steps}")
        print(f"(b) {name}: {in_place} of {total} masked parameters "
              f"packed in place ({100 * in_place / total:g}%)")
        require(in_place == total, f"{name}: a masked leaf is not packed "
                                   "in place")


def grouped_plans() -> None:
    """The grouped kernels' blocks at the deepseek-v2-lite share cell
    (seq 8192, top-6 of 64 router experts, 8 held, experts 2048 <->
    1408): `tiles` bounds the row tiles of the routed buffer, `live`
    is how many hold rows at the mean load (each streams its expert's
    `w` and `s` once)."""
    from repro.kernels import ops
    T, k, E, held = 8192, 6, 64, 8
    for K, N in ((2048, 1408), (1408, 2048)):
        p = ops.grouped_plan(T * min(k, held), K, N, held, T * k / E)
        print(f"(a) grouped ({held} x {K} x {N}) at {T} tokens: bm "
              f"{p.bm}, bn {p.bn}, bk {p.bk}, tiles {p.tiles}, live "
              f"{p.live} ({p.live / held:g} passes an expert)")


def phase_pack(jax, seed: int) -> None:
    """The leaf's words, read flat and at its own stacked shape (the
    round's in-place path), against the oracle of its flattened rows."""
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    s = jax.random.normal(k1, (COHORTS, LEAF_K * LEAF_N), jnp.float32)
    seeds = jax.random.bits(k2, (COHORTS,), jnp.uint32)
    oracle = jax.jit(ref.sample_and_pack, static_argnames="mode")
    for mode in ("sample", "threshold"):
        want = oracle(s, seeds, mode=mode)
        for shape in ((COHORTS, LEAF_K * LEAF_N),
                      (COHORTS, 1, LEAF_K, LEAF_N)):
            got = ops.sample_and_pack(s.reshape(shape), seeds, mode=mode)
            bad = int(jnp.sum(got != want))
            print(f"(b) sample_and_pack {mode} at {shape} (in_place "
                  f"{ops.pack_plan(shape).in_place}): {got.shape[0]} x "
                  f"{got.shape[1]} words, {bad} differ from "
                  f"ref.sample_and_pack")
            require(got.shape == want.shape and bad == 0,
                    f"sample_and_pack {mode} words differ from the oracle")


def phase_dense(jax, seed: int) -> None:
    """masked_dense fwd / dx / ds against the f32 jnp oracle.

    Tolerances, elementwise.  x, w and g are bf16, so every product the
    MXU forms from them (and from the 0/1 mask) is exact in f32 at any
    pass count; the kernel and the oracle differ only in the order they
    sum T terms, which moves an f32 sum by at most T * 2^-24 times the
    sum of the terms' magnitudes (`acc`).  y and dx come back in bf16,
    whose rounding adds at most half an ulp: 2^-8 of the value.  ds
    comes back in f32 after an epilogue with the kernel's own sigmoid
    (Mosaic exp and divide) where the oracle uses XLA's logistic; their
    few-ulp difference is amplified by up to 1/(1-sigmoid) ~ 150 at
    |s| <= 5 in sigmoid*(1-sigmoid), and 2^-12 of the value covers
    that with margin.
    """
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    M, K, N = BATCH * SEQ, LEAF_K, LEAF_N
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 5)
    x = jax.random.normal(ks[0], (M, K), jnp.bfloat16)
    w = (jax.random.normal(ks[1], (K, N), jnp.float32)
         * 0.02).astype(jnp.bfloat16)
    s = jax.random.normal(ks[2], (K, N), jnp.float32)
    g = jax.random.normal(ks[3], (M, N), jnp.bfloat16)
    sd = jax.random.bits(ks[4], (), jnp.uint32)
    off = jnp.uint32(3 * K * N)     # as if layer 3 of a stacked leaf

    @jax.jit
    def fused(x, w, s, g):
        y, vjp = jax.vjp(lambda x, s: ops.masked_dense(x, w, s, sd, off),
                         x, s)
        dx, ds = vjp(g)
        return y, dx, ds

    @jax.jit
    def oracle(x, w, s, g):
        f = lambda a: a.astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            wm = f(ref.sample_mask(s, sd, off)) * f(w)
            sig = jax.nn.sigmoid(s)
            y = f(x) @ wm
            dx = f(g) @ wm.T
            xg = f(x).T @ f(g)
            ds = xg * f(w) * sig * (1.0 - sig)
            y_acc = K * 2.0**-24 * (jnp.abs(f(x)) @ jnp.abs(wm))
            dx_acc = N * 2.0**-24 * (jnp.abs(f(g)) @ jnp.abs(wm).T)
            ds_acc = (M * 2.0**-24 * (jnp.abs(f(x)).T @ jnp.abs(f(g)))
                      * jnp.abs(f(w)) * sig * (1.0 - sig))
        return ((y, 2.0**-8 * (jnp.abs(y) + y_acc) + y_acc),
                (dx, 2.0**-8 * (jnp.abs(dx) + dx_acc) + dx_acc),
                (ds, 2.0**-12 * jnp.abs(ds) + ds_acc))

    got = fused(x, w, s, g)
    for name, v, (want, tol) in zip(("fwd", "dx", "ds"), got,
                                    oracle(x, w, s, g)):
        err = jnp.abs(v.astype(jnp.float32) - want)
        worst = float(jnp.max(err / jnp.maximum(tol, 1e-30)))
        print(f"(c) masked_dense {name} {tuple(v.shape)}: max |err|/tol "
              f"{worst!r}, max |err| {float(jnp.max(err))!r}")
        require(bool(jnp.all(jnp.isfinite(v))), f"{name}: not finite")
        require(worst <= 1.0, f"{name}: outside its tolerance")


def phase_serve(seed: int) -> None:
    import numpy as np
    from repro.launch import serve
    out = serve.main(["--arch", ARCH, "--layers", str(LAYERS),
                      "--tenants", "2", "--slots", "2",
                      "--cache-capacity", "2", "--prompt-len", "8",
                      "--tokens", "4", "--seed", str(seed)])
    done = out["completions"]
    require(len(done) == 2, f"{len(done)} of 2 tenants served")
    for c in sorted(done.values(), key=lambda c: c.rid):
        finite = all(np.all(np.isfinite(l)) for l in c.decode_logits)
        print(f"(d) {c.tenant}: decoded {c.tokens}, logits finite "
              f"{finite}")
        require(len(c.tokens) == 4 and finite,
                f"{c.tenant}: bad decode")


def run_one_chip(jax, seed: int) -> None:
    print(f"cut: {ARCH} at published widths, {LAYERS} layers "
          f"(of 24), {COHORTS} cohorts x batch {BATCH} x seq {SEQ}")
    t0 = time.perf_counter()
    out = phase_train(seed)
    t_train = time.perf_counter() - t0
    peak_train = _mem(jax.devices()[0])
    del out["compiled_step"]
    projection_passes(jax)
    grouped_plans()
    pack_plans(jax)
    phase_pack(jax, seed)
    phase_dense(jax, seed)
    phase_serve(seed)
    print(f"train step compile {out['compile_s']!r} s; step wall "
          f"{out['step_s']!r} s (first {out['first_step_s']!r} s); round "
          f"wall {out['round_s']!r} s (first, with its compile, "
          f"{out['first_round_s']!r} s); phase (a) {t_train!r} s")
    dev = jax.devices()[0]
    print(f"peak_bytes_in_use {_mem(dev)} ({_gb(_mem(dev))}; "
          f"{_gb(peak_train)} after phase (a)) of bytes_limit "
          f"{_mem(dev, 'bytes_limit')}")


def run_four_chips(jax, seed: int) -> None:
    """The cross-pod round step on a (pod, data, model) = (2, 2, 1) mesh
    against the pure-jnp oracle: per-shard words, theta, measured bits."""
    import numpy as np
    import jax.numpy as jnp
    from repro.analysis import comm_model
    from repro.configs import get_config
    from repro.core import masking
    from repro.kernels import ops, ref
    from repro.launch import mesh as meshlib
    from repro.launch import steps as steplib
    from repro.models import build_model

    require(len(jax.devices()) == 4,
            f"--four-chips needs 4 devices, JAX sees {len(jax.devices())}")
    mesh = meshlib.make_debug_pod_mesh()
    print(f"cut: {ARCH} at published widths, {LAYERS} layers (of 24), "
          f"{COHORTS} cohorts; mesh {dict(mesh.shape)}")
    api = build_model(get_config(ARCH, layers=LAYERS))
    scfg = steplib.StepConfig(seed=seed)   # f32 downlink, packed words
    codec = "bitpack"                      # word-exact wire meter
    jxp, shapes, state_sh = comm_model.trace_round_jaxpr(
        api, scfg, mesh, COHORTS, codec=codec)
    static = comm_model.round_comm_model(jxp, shapes, state_sh, mesh, scfg)
    with jax.default_device(jax.devices("cpu")[0]):
        host_state = steplib.init_fed_state(
            jax.random.PRNGKey(seed), api, masking.MaskSpec(), C=COHORTS)
    state = jax.device_put(host_state, state_sh)
    del host_state
    scores_in = [None if l is None else
                 {sh.device: sh for sh in l.addressable_shards}
                 for l in jax.tree_util.tree_leaves(
                     state["scores"], is_leaf=lambda x: x is None)]
    step_no = int(state["step"])
    round_step = jax.jit(
        steplib.make_round_step(api, scfg, mesh=mesh, state_sh=state_sh,
                                codec=codec),
        out_shardings=(state_sh, None))
    t0 = time.perf_counter()
    new, rm = round_step(state)
    bits = float(rm["bits_measured"])
    print(f"round step (compile + run) {time.perf_counter() - t0!r} s")
    t0 = time.perf_counter()
    jax.block_until_ready(round_step(state))
    print(f"round step wall {time.perf_counter() - t0!r} s")

    # oracle: every shard samples its own block with its own seeds (its
    # index within the cohort, the pod's global cohort numbers), the
    # pods' words are gathered, theta is their mean
    pos = {d: np.argwhere(mesh.devices == d)[0] for d in mesh.devices.flat}
    linear = {d: int(np.ravel_multi_index(p[1:], mesh.devices.shape[1:]))
              for d, p in pos.items()}
    outs = jax.tree_util.tree_leaves(new["scores"],
                                     is_leaf=lambda x: x is None)
    logit_of = jax.jit(masking.logit)
    n_words = n_params = 0
    for i, (shards, out_leaf) in enumerate(zip(scores_in, outs)):
        if shards is None:
            continue
        words = {}
        for d, sh in shards.items():
            block = sh.data
            seeds = steplib._mask_stream_seeds(
                step_no, linear[d], i, block.shape[0], run_seed=scfg.seed,
                first=pos[d][0] * block.shape[0])
            words[d] = np.asarray(jax.jit(ref.sample_and_pack)(
                block.reshape(block.shape[0], -1), seeds))
            n_words += words[d].size
        for osh in out_leaf.addressable_shards:
            d = osh.device
            peers = [e for e in words if all(
                pos[e][1:] == pos[d][1:])]          # same block, all pods
            gathered = np.concatenate([words[e] for e in sorted(
                peers, key=lambda e: pos[e][0])])
            n = int(np.prod(osh.data.shape[1:]))
            bits_all = np.unpackbits(gathered.view(np.uint8),
                                     bitorder="little", axis=1)[:, :n]
            theta = bits_all.mean(axis=0, dtype=np.float64)
            # decode the round's theta from its scores: the nearest of
            # the C+1 values logit(k/C) it can take
            cand = np.asarray(logit_of(
                jnp.arange(COHORTS + 1, dtype=jnp.float32) / COHORTS))
            got = np.asarray(osh.data).reshape(osh.data.shape[0], n)
            k = np.abs(got[..., None] - cand).argmin(axis=-1)
            require(np.array_equal(k / COHORTS,
                                   np.broadcast_to(theta, k.shape)),
                    f"leaf {i} on {d}: theta differs from the oracle")
            n_params += n
        # the gathered words themselves: the round's own sample_and_pack
        # kernel on each shard against the oracle above
        for d, sh in shards.items():
            seeds = steplib._mask_stream_seeds(
                step_no, linear[d], i, sh.data.shape[0],
                run_seed=scfg.seed, first=pos[d][0] * sh.data.shape[0])
            kw = np.asarray(jax.jit(ops.sample_and_pack)(sh.data, seeds))
            require(np.array_equal(kw, words[d]),
                    f"leaf {i} on {d}: kernel words differ from oracle")
    print(f"words: {n_words} per-shard words equal ref.sample_and_pack; "
          f"theta equals the mean of the gathered words at {n_params} "
          f"shard-local params")
    print(f"bits: measured {bits!r}, static comm model "
          f"{float(static['uplink_bits'])!r}")
    require(bits == float(static["uplink_bits"]),
            "measured uplink bits differ from the static comm model")
    peaks = [_mem(d) for d in jax.devices()]
    print(f"peak_bytes_in_use per device {peaks}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cross-pod round on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    jax = preflight()
    if args.four_chips:
        run_four_chips(jax, args.seed)
    else:
        run_one_chip(jax, args.seed)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
