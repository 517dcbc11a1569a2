"""Kernel benchmark harness: fused masked-matmul forward/backward and
the fused sample+pack uplink kernel vs their pure-jnp oracles.

Three kinds of output:

  * Timings — median-of-N `time.perf_counter` wall clock (after separate
    warmup calls) for fwd / bwd / sample+pack across a shape zoo drawn
    from the real model configs (`repro.configs`), written to
    ``BENCH_kernels.json`` and printed as CSV.  On CPU the kernels run
    in interpret mode, so the numbers are indicative only.

  * Structural assertions — the memory-term argument that holds on any
    backend: counting weight-shaped (K, N) f32 values defined OUTSIDE
    the pallas_call boundary.  The count runs on the jaxpr (where
    `pallas_call` is a single opaque equation) rather than compiled HLO
    text, because interpret-mode emulation inlines full-size plumbing
    buffers into the compiled module that do not exist on TPU.  Pure
    view/layout equations (squeeze/reshape — how `lax.scan` feeds the
    per-layer score slice to the kernel; XLA aliases them) are not
    counted: the invariant is about weight-sized values COMPUTED
    outside the kernel.  The naive path materializes sigmoid(s), the
    hash uniforms, m*w and x^T@g at weight size; the fused forward AND
    backward must define zero such values.  Compiled-HLO substring
    counts are still reported (informational) for continuity with the
    original forward check.

  * Whole-model step — the same invariant asserted END-TO-END on a
    jitted `launch.steps.make_train_step` for an MXU-aligned config of
    EACH kernel-bearing family: dense transformer (2-D blocks),
    deepseek-style MoE (stacked (E, K, N) expert leaves through the
    GROUPED kernel) and recurrentgemma-style hybrid ((W, C) conv
    leaves through the fused conv kernel): the jaxpr of the full train
    step (forward AND backward, scores as a first-class grad argument)
    defines zero weight-shaped f32 values outside `pallas_call` for
    EVERY masked block shape, while the materialized reference path
    (`REPRO_EFF_PATH=1`) scores > 0 on each leaf — proving the model
    zoo's masked-execution routing delivers the kernel win at the
    training hot path for every maskable leaf shape, not just per
    layer.  Timed fused vs. materialized.

`tools/check_bench.py` diffs a fresh JSON against the committed
baseline (structural counts asserted; fused-vs-ref timing ratios gated
on real hardware, informational under interpret).

Run:  PYTHONPATH=src python benchmarks/kernels_bench.py [--iters N]
      [--warmup N] [--max-dim D] [--json PATH]
"""
from __future__ import annotations

import argparse
import json
import statistics
import time

import jax
import jax.numpy as jnp

from repro.analysis import count_weight_f32_defs
from repro.analysis import model_check
from repro.configs import get_config
from repro.kernels import ref, ops
from repro.launch import steps as steplib


# ---------------------------------------------------------------------------
# Timing
# ---------------------------------------------------------------------------


def timed(fn, *args, iters: int = 5, warmup: int = 2) -> float:
    """Median wall time per call in us: `warmup` untimed calls first
    (compile + cache effects), then `iters` timed calls, each fully
    blocked on, reported as the median (robust to scheduler noise where
    a mean is not)."""
    for _ in range(max(warmup, 1)):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts) * 1e6


# ---------------------------------------------------------------------------
# Shape zoo: the hot matmuls of the real model configs
# ---------------------------------------------------------------------------

ZOO_ARCHS = ("internlm2-1.8b", "gemma3-4b", "qwen2-7b")


def _shrink(d: int, max_dim: int) -> int:
    """Halve until <= max_dim, then round down to lane (128) alignment
    so interpret-mode (CPU) runs stay tractable; actual dims are
    recorded in the JSON."""
    while d > max_dim:
        d //= 2
    return max(d - d % 128, 128)


def shape_zoo(max_dim: int = 1536, m: int = 256):
    """(label, M, K, N) for the per-layer hot matmuls — the attention
    qkv projection (d_model -> (H + 2*H_kv) * hd) and the FFN up
    projection (d_model -> d_ff) — of each zoo arch, deduplicated."""
    out, seen = [], set()
    for name in ZOO_ARCHS:
        cfg = get_config(name)
        qkv = (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.hd
        for tag, k, n in (("qkv", cfg.d_model, qkv),
                          ("ffn_up", cfg.d_model, cfg.d_ff)):
            K, N = _shrink(k, max_dim), _shrink(n, max_dim)
            if (K, N) in seen:
                continue
            seen.add((K, N))
            out.append((f"{name}:{tag}", m, K, N))
    return out


GROUPED_ZOO_ARCHS = ("deepseek-v2-lite-16b", "deepseek-v2-236b")


def grouped_shape_zoo(max_dim: int = 1536, m: int = 128,
                      max_experts: int = 4):
    """(label, E, M, K, N) for the stacked MoE expert matmuls
    (d_model -> moe_d_ff per routed expert) of the MoE zoo archs,
    expert count capped for CPU-interpret tractability."""
    out, seen = [], set()
    for name in GROUPED_ZOO_ARCHS:
        cfg = get_config(name)
        E = min(cfg.n_experts, max_experts)
        K, N = (_shrink(cfg.d_model, max_dim // 2),
                _shrink(cfg.moe_d_ff, max_dim // 2))
        if (E, K, N) in seen:
            continue
        seen.add((E, K, N))
        out.append((f"{name}:moe_up", E, m, K, N))
    return out


# ---------------------------------------------------------------------------
# Structural check: weight-shaped f32 values outside the pallas boundary
# ---------------------------------------------------------------------------


_CHECK_SHAPE = (256, 1024, 1024)  # MXU-aligned so no pad/slice eqns


# the counter itself — the rule-based jaxpr walker — lives in
# repro.analysis (jaxpr_lint.count_weight_f32_defs); this harness, the
# tier-1 twin in tests/test_steps.py and tools/repro_lint.py are thin
# callers of that ONE traversal, so counts stay comparable everywhere


def _check_operands(M, K, N):
    x = jnp.zeros((M, K), jnp.bfloat16)
    w = jnp.zeros((K, N), jnp.bfloat16)
    s = jnp.zeros((K, N), jnp.float32)
    g = jnp.zeros((M, N), jnp.bfloat16)
    return x, w, s, g


def weight_temporaries_fwd():
    """(naive, fused) weight-f32 def counts for the forward."""
    M, K, N = _CHECK_SHAPE
    x, w, s, _ = _check_operands(M, K, N)
    naive = count_weight_f32_defs(
        lambda x, w, s: ref.masked_matmul(x, w, s, 0), (x, w, s), (K, N))
    fused = count_weight_f32_defs(
        lambda x, w, s: ops.masked_dense(x, w, s, 0), (x, w, s), (K, N))
    return naive, fused


def weight_temporaries_bwd():
    """(naive, fused) weight-f32 def counts for the STE backward."""
    M, K, N = _CHECK_SHAPE
    x, w, s, g = _check_operands(M, K, N)

    def fused(x, w, s, g):
        _, vjp = jax.vjp(
            lambda x_, s_: ops.masked_dense(x_, w, s_, 0), x, s)
        return vjp(g)

    def naive(x, w, s, g):
        return ref.masked_dense_bwd(x, w, s, 0, g)

    args = (x, w, s, g)
    return (count_weight_f32_defs(naive, args, (K, N)),
            count_weight_f32_defs(fused, args, (K, N)))


# ---------------------------------------------------------------------------
# Whole-model check: the invariant on a full transformer-block train step
# ---------------------------------------------------------------------------

# the aligned check configs and the tracing/counting helpers live in
# repro.analysis.model_check (shared with the tier-1 twin and
# tools/repro_lint.py); the bench layers TIMING on top of its counts
MODEL_CHECK_CFGS = model_check.MODEL_CHECK_CFGS


def model_step_weight_defs(cfg, iters: int = 0, warmup: int = 1,
                           S: int = 64):
    """`model_check.model_step_weight_defs` counts, plus (iters > 0)
    fused-vs-materialized wall time of the compiled train step."""
    out = model_check.model_step_weight_defs(cfg, S=S)
    if iters:
        api, state, batch = model_check.model_step_setup(cfg, S=S)
        scfg = steplib.StepConfig(lam=0.1, lr=0.5)
        _, fused_fn = model_check.trace_model_step(
            api, state, batch, scfg, eff_path=False, jit_compile=True)
        _, eff_fn = model_check.trace_model_step(
            api, state, batch, scfg, eff_path=True, jit_compile=True)
        out["train_step_us"] = timed(fused_fn, state, batch,
                                     iters=iters, warmup=warmup)
        out["train_step_eff_us"] = timed(eff_fn, state, batch,
                                         iters=iters, warmup=warmup)
    return out


def hbm_weight_tensors_baseline_vs_fused():
    """Compiled-HLO substring counts for the forward (the original,
    informational check; interpret-mode emulation inflates the fused
    number with plumbing buffers that do not exist on TPU — the jaxpr
    counts above are the asserted invariant)."""
    M, K, N = _CHECK_SHAPE
    x, w, s, _ = _check_operands(M, K, N)
    txt_base = jax.jit(
        lambda x, w, s: ref.masked_matmul(x, w, s, 0)
    ).lower(x, w, s).compile().as_text()
    txt_fused = jax.jit(
        lambda x, w, s: ops.masked_dense(x, w, s, 0)
    ).lower(x, w, s).compile().as_text()
    return txt_base.count(f"{K},{N}"), txt_fused.count(f"{K},{N}")


# ---------------------------------------------------------------------------
# Benchmarks
# ---------------------------------------------------------------------------


def bench_shape(label, M, K, N, iters, warmup, key):
    kx, kw, ks, kg = jax.random.split(key, 4)
    x = jax.random.normal(kx, (M, K), jnp.float32).astype(jnp.bfloat16)
    w = jax.random.normal(kw, (K, N), jnp.float32).astype(jnp.bfloat16)
    s = jax.random.normal(ks, (K, N), jnp.float32)
    g = jax.random.normal(kg, (M, N), jnp.float32).astype(jnp.bfloat16)

    fwd = jax.jit(lambda x, w, s: ops.masked_dense(x, w, s, 7))
    fwd_ref = jax.jit(lambda x, w, s: ref.masked_matmul(x, w, s, 7))

    # grad step = forward + backward on BOTH sides (jax.vjp re-runs the
    # forward, so the naive baseline gets its forward too — symmetric),
    # 3 weight-sized matmuls total (y, dx, ds)
    def _bwd(x, w, s, g):
        _, vjp = jax.vjp(
            lambda x_, s_: ops.masked_dense(x_, w, s_, 7), x, s)
        return vjp(g)

    bwd = jax.jit(_bwd)

    def _bwd_ref(x, w, s, g):
        y = ref.masked_matmul(x, w, s, 7)
        dx, ds = ref.masked_dense_bwd(x, w, s, 7, g)
        return y, dx, ds

    bwd_ref = jax.jit(_bwd_ref)

    # one cohort row of K*N scores: the per-round uplink sampling
    flat = s.reshape(1, -1)
    seeds = jnp.asarray([7], jnp.uint32)
    sap = jax.jit(lambda f, sd: ops.sample_and_pack(f, sd))
    sap_ref = jax.jit(lambda f, sd: ref.sample_and_pack(f, sd))

    t = dict(
        fwd_us=timed(fwd, x, w, s, iters=iters, warmup=warmup),
        fwd_ref_us=timed(fwd_ref, x, w, s, iters=iters, warmup=warmup),
        bwd_us=timed(bwd, x, w, s, g, iters=iters, warmup=warmup),
        bwd_ref_us=timed(bwd_ref, x, w, s, g, iters=iters,
                         warmup=warmup),
        sample_pack_us=timed(sap, flat, seeds, iters=iters,
                             warmup=warmup),
        sample_pack_ref_us=timed(sap_ref, flat, seeds, iters=iters,
                                 warmup=warmup),
    )
    fwd_flops = 2 * M * K * N
    t["fwd_gflops"] = fwd_flops / t["fwd_us"] / 1e3
    t["bwd_gflops"] = 3 * fwd_flops / t["bwd_us"] / 1e3  # y + dx + ds
    t["sample_pack_gbit_s"] = K * N / t["sample_pack_us"] / 1e3
    return {"name": label, "M": M, "K": K, "N": N, **t}


def bench_grouped_shape(label, E, M, K, N, iters, warmup, key):
    """Fused grouped kernels vs the materializing einsum baseline for
    one stacked (E, K, N) expert shape."""
    kx, kw, ks, kg = jax.random.split(key, 4)
    x = jax.random.normal(kx, (E, M, K), jnp.float32).astype(jnp.bfloat16)
    w = jax.random.normal(kw, (E, K, N), jnp.float32).astype(jnp.bfloat16)
    s = jax.random.normal(ks, (E, K, N), jnp.float32)
    g = jax.random.normal(kg, (E, M, N), jnp.float32).astype(jnp.bfloat16)
    seeds = jnp.full((E,), 7, jnp.uint32)
    offs = jnp.arange(E, dtype=jnp.uint32) * jnp.uint32(K * N)
    # E equal groups of M rows in the grouped kernels' row layout
    x, tg, nl = ref.uniform_groups(x)
    g, _, _ = ref.uniform_groups(g)
    lay = ops.GroupLayout(tg, nl, jnp.arange(E, dtype=jnp.int32)
                          * (x.shape[0] // E))

    fwd = jax.jit(lambda x, w, s: ops.masked_dense_grouped(x, w, s, 7,
                                                           offs, lay))
    fwd_ref = jax.jit(lambda x, w, s: ref.masked_matmul_grouped(
        x, w, s, seeds, offs, tg, nl))

    def _bwd(x, w, s, g):
        _, vjp = jax.vjp(lambda x_, s_: ops.masked_dense_grouped(
            x_, w, s_, 7, offs, lay), x, s)
        return vjp(g)

    bwd = jax.jit(_bwd)

    def _bwd_ref(x, w, s, g):
        y = ref.masked_matmul_grouped(x, w, s, seeds, offs, tg, nl)
        dx, ds = ref.masked_dense_grouped_bwd(x, w, s, seeds, offs, g,
                                              tg, nl)
        return y, dx, ds

    bwd_ref = jax.jit(_bwd_ref)

    t = dict(
        fwd_us=timed(fwd, x, w, s, iters=iters, warmup=warmup),
        fwd_ref_us=timed(fwd_ref, x, w, s, iters=iters, warmup=warmup),
        bwd_us=timed(bwd, x, w, s, g, iters=iters, warmup=warmup),
        bwd_ref_us=timed(bwd_ref, x, w, s, g, iters=iters,
                         warmup=warmup),
    )
    fwd_flops = 2 * E * M * K * N
    t["fwd_gflops"] = fwd_flops / t["fwd_us"] / 1e3
    t["bwd_gflops"] = 3 * fwd_flops / t["bwd_us"] / 1e3
    return {"name": label, "E": E, "M": M, "K": K, "N": N, **t}


def main(argv=None) -> dict:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--iters", type=int, default=3,
                   help="timed iterations per benchmark (median taken)")
    p.add_argument("--warmup", type=int, default=1,
                   help="untimed warmup iterations")
    p.add_argument("--max-dim", type=int, default=1536,
                   help="shrink zoo dims to <= this (CPU tractability)")
    p.add_argument("--json", default="BENCH_kernels.json",
                   help="output path for the results JSON")
    args = p.parse_args([] if argv is None else argv)

    # a caller (or test) may have flipped REPRO_FORCE_INTERPRET since
    # the first kernel dispatch — make this run see the current env
    ops.reset_backend_cache()
    interpret = ops._use_interpret()
    results = {
        "backend": ops.repro_backend(),
        "interpret": interpret,
        "iters": args.iters,
        "warmup": args.warmup,
        "check_shape": dict(zip("MKN", _CHECK_SHAPE)),
        "shapes": [],
    }

    print("name,us_per_call,derived")
    key = jax.random.PRNGKey(0)
    for label, M, K, N in shape_zoo(max_dim=args.max_dim):
        key, sub = jax.random.split(key)
        row = bench_shape(label, M, K, N, args.iters, args.warmup, sub)
        results["shapes"].append(row)
        for op in ("fwd", "bwd", "sample_pack"):
            d = (f"{row[f'{op}_gflops']:.1f}GFLOP/s"
                 if op != "sample_pack"
                 else f"{row['sample_pack_gbit_s']:.2f}Gbit/s")
            print(f"{label}:{op}_{M}x{K}x{N},{row[f'{op}_us']:.0f},{d}")
            print(f"{label}:{op}_ref_{M}x{K}x{N},"
                  f"{row[f'{op}_ref_us']:.0f},baseline")

    # grouped (E, K, N) expert shapes: the MoE hot matmuls
    results["grouped_shapes"] = []
    for label, E, M, K, N in grouped_shape_zoo(max_dim=args.max_dim):
        key, sub = jax.random.split(key)
        row = bench_grouped_shape(label, E, M, K, N, args.iters,
                                  args.warmup, sub)
        results["grouped_shapes"].append(row)
        for op in ("fwd", "bwd"):
            print(f"{label}:{op}_{E}x{M}x{K}x{N},"
                  f"{row[f'{op}_us']:.0f},"
                  f"{row[f'{op}_gflops']:.1f}GFLOP/s")
            print(f"{label}:{op}_ref_{E}x{M}x{K}x{N},"
                  f"{row[f'{op}_ref_us']:.0f},baseline")

    # structural invariants: no weight-shaped f32 value may be defined
    # outside the pallas_call on either pass
    fwd_naive, fwd_fused = weight_temporaries_fwd()
    bwd_naive, bwd_fused = weight_temporaries_bwd()
    results["weight_f32_defs"] = {
        "fwd_naive": fwd_naive, "fwd_fused": fwd_fused,
        "bwd_naive": bwd_naive, "bwd_fused": bwd_fused,
    }
    print(f"weight_f32_defs_fwd_naive,{fwd_naive},count")
    print(f"weight_f32_defs_fwd_fused,{fwd_fused},count")
    print(f"weight_f32_defs_bwd_naive,{bwd_naive},count")
    print(f"weight_f32_defs_bwd_fused,{bwd_fused},count")
    assert fwd_fused == 0, \
        f"fused forward defines {fwd_fused} weight-f32 temporaries"
    assert bwd_fused == 0, \
        f"fused backward defines {bwd_fused} weight-f32 temporaries"
    assert fwd_naive > 0 and bwd_naive > 0, \
        "naive baseline lost its temporaries — check the counter"

    # compiled-HLO substring counts: under interpret-mode emulation the
    # fused number is inflated by plumbing buffers that do not exist on
    # TPU, so the field is explicitly labeled (the jaxpr counts above
    # are the asserted invariant)
    nb, nf = hbm_weight_tensors_baseline_vs_fused()
    results["hlo_substring_counts"] = {
        "fwd_naive": nb, "fwd_fused": nf,
        "interpret_inflated": bool(interpret)}
    if interpret:
        print(f"hbm_weight_tensors_baseline,{nb},interpret_inflated")
        print(f"hbm_weight_tensors_fused,{nf},interpret_inflated")
    else:
        print(f"hbm_weight_tensors_baseline,{nb},count")
        print(f"hbm_weight_tensors_fused,{nf},count")

    # end-to-end: the invariant on a jitted whole-model train step —
    # forward AND backward — for a dense transformer stack, a
    # deepseek-style MoE (stacked (E, K, N) expert leaves through the
    # GROUPED kernel) and a recurrentgemma-style hybrid (depthwise
    # (W, C) conv leaves through the fused conv kernel): the model
    # zoo's masked-execution routing must leave ZERO weight-shaped f32
    # defs outside pallas_call for every masked block shape, while the
    # materialized REPRO_EFF_PATH reference scores > 0 on each leaf
    results["model_step"] = {}
    for fam, (cfg, S) in MODEL_CHECK_CFGS.items():
        model = model_step_weight_defs(cfg, iters=args.iters,
                                       warmup=args.warmup, S=S)
        results["model_step"][fam] = model
        for sh, cts in model["block_shapes"].items():
            print(f"model_step[{fam}]_block_f32_defs_{sh}_fused,"
                  f"{cts['fused']},count")
            assert cts["fused"] == 0, \
                f"{fam} model step defines {cts['fused']} weight-f32 " \
                f"values for block {sh} outside pallas_call"
        for sh, cts in model["leaf_shapes"].items():
            print(f"model_step[{fam}]_leaf_f32_defs_{sh},"
                  f"{cts['eff']}:{cts['fused']},eff:fused")
            assert cts["eff"] > cts["fused"], \
                f"{fam}: materialized path lost its {sh} temporaries " \
                "— check the counter"
        if "train_step_us" in model:
            print(f"model_train_step[{fam}],"
                  f"{model['train_step_us']:.0f},fused")
            print(f"model_train_step_eff[{fam}],"
                  f"{model['train_step_eff_us']:.0f},materialized")

    assert len(results["shapes"]) >= 3, results["shapes"]
    with open(args.json, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    print(f"# wrote {args.json}")
    return results


if __name__ == "__main__":
    import sys
    main(sys.argv[1:])
