import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

# Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.
# The two lines above MUST run before ANY other import (jax locks the
# device count on first init).

import argparse     # noqa: E402
import json         # noqa: E402
import re           # noqa: E402
import time         # noqa: E402
import traceback    # noqa: E402

import jax          # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.analysis import collective_lint  # noqa: E402
from repro.analysis import comm_model       # noqa: E402
from repro.analysis import shard_lint       # noqa: E402
from repro.analysis import stream_cover     # noqa: E402
from repro.configs import get_config, ARCH_NAMES, SHAPES, LONG_CONTEXT_OK  # noqa: E402
from repro.core import masking  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.launch import mesh as meshlib  # noqa: E402
from repro.launch import sharding as shd  # noqa: E402
from repro.launch import steps as steplib  # noqa: E402

P = jax.sharding.PartitionSpec


# ---------------------------------------------------------------------------
# input_specs: ShapeDtypeStruct stand-ins (no allocation) + shardings
# ---------------------------------------------------------------------------


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def train_batch_specs(cfg, shape_cfg, mesh, C):
    """(batch_shapes, batch_shardings) with leading cohort axis C."""
    Bc = shape_cfg.global_batch // C
    S = shape_cfg.seq_len
    ns = lambda *spec: jax.sharding.NamedSharding(mesh, P(*spec))
    pod = "pod" if "pod" in mesh.axis_names else None
    shapes = {}
    sh = {}
    if cfg.family == "vlm":
        n_vis = 256
        shapes["tokens"] = sds((C, Bc, S - n_vis), jnp.int32)
        shapes["vis_embeds"] = sds((C, Bc, n_vis, cfg.d_model),
                                   jnp.bfloat16)
        sh["tokens"] = ns(pod, "data", None)
        sh["vis_embeds"] = ns(pod, "data", None, None)
    elif cfg.family == "encdec":
        shapes["tokens"] = sds((C, Bc, S), jnp.int32)
        shapes["frames"] = sds((C, Bc, cfg.enc_seq, cfg.d_model),
                               jnp.bfloat16)
        sh["tokens"] = ns(pod, "data", None)
        sh["frames"] = ns(pod, "data", None, None)
    else:
        shapes["tokens"] = sds((C, Bc, S), jnp.int32)
        sh["tokens"] = ns(pod, "data", None)
    return shapes, sh


def serve_batch_specs(cfg, shape_cfg, mesh, api):
    """decode: (cache, token, pos) shape structs + shardings."""
    B, S = shape_cfg.global_batch, shape_cfg.seq_len
    cache_shapes = jax.eval_shape(lambda: api.init_cache(B, S))
    cache_sh = shd.cache_shardings(cache_shapes, mesh, B)
    client = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    csize = 1
    for a in client:
        csize *= mesh.shape[a]
    tok_spec = P(client) if B % csize == 0 and csize > 1 else P()
    token = sds((B,), jnp.int32)
    pos = sds((), jnp.int32)
    sh = (jax.sharding.NamedSharding(mesh, tok_spec),
          shd.replicated(mesh))
    return cache_shapes, cache_sh, token, pos, sh


def prefill_batch_specs(cfg, shape_cfg, mesh):
    B, S = shape_cfg.global_batch, shape_cfg.seq_len
    ns = lambda *spec: jax.sharding.NamedSharding(mesh, P(*spec))
    client = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    shapes, sh = {}, {}
    if cfg.family == "vlm":
        n_vis = 256
        shapes["tokens"] = sds((B, S - n_vis), jnp.int32)
        shapes["vis_embeds"] = sds((B, n_vis, cfg.d_model), jnp.bfloat16)
        sh["tokens"] = ns(client, None)
        sh["vis_embeds"] = ns(client, None, None)
    elif cfg.family == "encdec":
        shapes["tokens"] = sds((B, S), jnp.int32)
        shapes["frames"] = sds((B, cfg.enc_seq, cfg.d_model),
                               jnp.bfloat16)
        sh["tokens"] = ns(client, None)
        sh["frames"] = ns(client, None, None)
    else:
        shapes["tokens"] = sds((B, S), jnp.int32)
        sh["tokens"] = ns(client, None)
    return shapes, sh


# ---------------------------------------------------------------------------
# HLO collective-byte accounting
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
                "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8}

_TYPE_RE = re.compile(r"\b(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|"
                      r"s64|u64)\[([0-9,]*)\]")
_KIND_RE = re.compile(r"\b(all-gather|all-reduce|reduce-scatter|"
                      r"all-to-all|collective-permute)(-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective-kind OPERAND bytes, parsed from compiled HLO.

    Operand types are often printed as bare %names, so operand size is
    derived from the RESULT type: all-gather result = operand *
    group_size; reduce-scatter result = operand / group_size; others are
    operand-sized. Async ops are counted once (at -start).
    """
    out = {}
    for line in hlo_text.splitlines():
        km = _KIND_RE.search(line)
        if km is None:
            continue
        if "-done(" in line:
            continue
        kind = km.group(1)
        # result type(s): everything left of the op name
        head = line[:km.start()]
        total = 0
        for tm in _TYPE_RE.finditer(head):
            dt, dims = tm.group(1), tm.group(2)
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        gm = _GROUPS_RE.search(line)
        gsize = int(gm.group(2)) if gm else 1
        if kind == "all-gather":
            total = total // max(gsize, 1)       # operand = result/group
        elif kind == "reduce-scatter":
            total = total * gsize                # operand = result*group
        out[kind] = out.get(kind, 0) + total
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               step_kind: str = "auto", packed: bool = True,
               keep_hlo: bool = False, cfg_patch: dict | None = None):
    """Returns a result dict (memory, cost, collective bytes)."""
    import dataclasses
    cfg = get_config(arch)
    shape_cfg = SHAPES[shape_name]
    chunk_kv = 512 if shape_cfg.seq_len >= 32768 else None
    microbatch = 1
    tp_only = False
    if cfg_patch:
        cfg_patch = dict(cfg_patch)
        chunk_kv = cfg_patch.pop("chunk_kv", chunk_kv)  # StepConfig
        microbatch = cfg_patch.pop("microbatch", 1)     # StepConfig
        tp_only = cfg_patch.pop("tp_only", False)       # sharding mode
    if cfg_patch:
        cfg = dataclasses.replace(cfg, **cfg_patch)
    mesh = meshlib.make_production_mesh(multi_pod=multi_pod)
    api = build_model(cfg)
    C = steplib.n_cohorts(mesh)
    spec = masking.MaskSpec()
    scfg = steplib.StepConfig(chunk_kv=chunk_kv, packed_masks=packed,
                              microbatch=microbatch)
    key = jax.random.PRNGKey(0)

    t0 = time.time()
    results = {}
    with jax.set_mesh(mesh):
        if shape_cfg.kind == "train":
            state_shapes = jax.eval_shape(
                lambda k: steplib.init_fed_state(k, api, spec, C), key)
            # ROADMAP gate: the per-shard mask streams must tile the
            # global hash stream exactly — zero overlaps, zero gaps,
            # no (leaf, shard, cohort) seed collisions across the
            # whole forced mesh
            n_dev = 1
            for a in mesh.axis_names:
                n_dev *= mesh.shape[a]
            cover = stream_cover.state_stream_report(
                state_shapes, devs=range(n_dev), cohorts=range(C),
                run_seed=scfg.seed)
            if cover["findings"]:
                raise AssertionError(
                    "mask-stream coverage violated: "
                    + "; ".join(str(f) for f in cover["findings"][:5]))
            results["stream_cover"] = {
                "ok": True, "n_leaves": cover["n_leaves"],
                "n_streams": cover["n_streams"]}
            state_sh = steplib.fed_state_shardings(state_shapes, mesh)
            batch_shapes, batch_sh = train_batch_specs(cfg, shape_cfg,
                                                       mesh, C)
            if step_kind in ("auto", "train"):
                fn = steplib.make_train_step(api, scfg)
                lowered = jax.jit(
                    fn, in_shardings=(state_sh, batch_sh),
                    out_shardings=(state_sh, shd.replicated(mesh)),
                ).lower(state_shapes, batch_shapes)
                results["train_step"] = _analyze(lowered, keep_hlo)
            if step_kind in ("auto", "round"):
                fn = steplib.make_round_step(api, scfg, mesh=mesh,
                                             state_sh=state_sh)
                # ROADMAP gate: wire purity — on the packed uplink,
                # nothing but uint32 mask words, the float-sidecar
                # pmean, and O(1) scalar metrics may cross a round
                # collective (repro.analysis.collective_lint)
                jxp = jax.make_jaxpr(fn)(state_shapes)
                purity = collective_lint.round_purity_findings(
                    jxp, state_shapes, state_sh, mesh)
                if packed and purity:
                    raise AssertionError(
                        "collective wire purity violated: "
                        + "; ".join(str(f) for f in purity[:5]))
                compiled = jax.jit(
                    fn, in_shardings=(state_sh,),
                    out_shardings=(state_sh, shd.replicated(mesh)),
                ).lower(state_shapes).compile()
                results["round_step"] = _analyze_compiled(compiled,
                                                          keep_hlo)
                model = comm_model.round_comm_model(
                    jxp, state_shapes, state_sh, mesh, scfg)
                results["round_step"]["comm_model"] = {
                    k: model[k] for k in
                    ("bpp_wire", "uplink_bits", "downlink_bits",
                     "n_sites", "ring_bytes_per_axis")}
                # ROADMAP gate: the shardings the launcher declares
                # must be the shardings the executable ingests — a
                # drift is an unmetered per-step reshard
                mism = shard_lint.input_sharding_mismatches(
                    compiled, state_sh, state_shapes, label="state/")
                if mism:
                    raise AssertionError(
                        "declared-vs-lowered sharding drift: "
                        + "; ".join(str(f) for f in mism[:5]))
                results["round_step"]["shard_lint"] = {"ok": True}
        elif shape_cfg.kind == "prefill":
            params_shapes = jax.eval_shape(api.init_params, key)
            params_sh = shd.tree_param_shardings(params_shapes, mesh,
                                                 tp_only=tp_only)
            batch_shapes, batch_sh = prefill_batch_specs(cfg, shape_cfg,
                                                         mesh)

            def prefill(params, batch):
                out = api.forward(params, batch, chunk_kv=chunk_kv)
                return out[0][:, -1]

            lowered = jax.jit(
                prefill, in_shardings=(params_sh, batch_sh),
            ).lower(params_shapes, batch_shapes)
            results["prefill_step"] = _analyze(lowered, keep_hlo)
        else:  # decode
            params_shapes = jax.eval_shape(api.init_params, key)
            params_sh = shd.tree_param_shardings(params_shapes, mesh,
                                                 tp_only=tp_only)
            cache_shapes, cache_sh, token, pos, (tok_sh, pos_sh) = \
                serve_batch_specs(cfg, shape_cfg, mesh, api)
            fn = steplib.make_serve_step(api)
            lowered = jax.jit(
                fn, in_shardings=(params_sh, cache_sh, tok_sh, pos_sh),
            ).lower(params_shapes, cache_shapes, token, pos)
            results["serve_step"] = _analyze(lowered, keep_hlo)

    for r in results.values():
        r["lower_compile_s"] = round(time.time() - t0, 1)
    return results


def _analyze(lowered, keep_hlo=False):
    return _analyze_compiled(lowered.compile(), keep_hlo)


def _analyze_compiled(compiled, keep_hlo=False):
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    out = {
        "flops": float(cost.get("flops", -1)) if cost else -1,
        "bytes_accessed": float(cost.get("bytes accessed", -1))
        if cost else -1,
        "collective_bytes": coll,
        "memory": {
            "argument_size": getattr(mem, "argument_size_in_bytes", None),
            "output_size": getattr(mem, "output_size_in_bytes", None),
            "temp_size": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size": getattr(
                mem, "generated_code_size_in_bytes", None),
        },
    }
    if keep_hlo:
        out["hlo"] = hlo
    return out


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def iter_cells(archs, shapes):
    for a in archs:
        for s in shapes:
            if s == "long_500k" and a not in LONG_CONTEXT_OK:
                continue
            yield a, s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--unpacked", action="store_true",
                    help="bf16 psum mask aggregation (baseline)")
    args = ap.parse_args()

    archs = ARCH_NAMES if args.arch == "all" else args.arch.split(",")
    shapes = (list(SHAPES) if args.shape == "all"
              else args.shape.split(","))
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    n_ok = n_fail = 0
    for arch, shape in iter_cells(archs, shapes):
        for mp in meshes:
            mesh_name = "pod2x16x16" if mp else "pod16x16"
            cell = f"{arch}|{shape}|{mesh_name}"
            if cell in results and results[cell].get("ok"):
                continue
            t0 = time.time()
            try:
                r = lower_cell(arch, shape, mp,
                               packed=not args.unpacked)
                results[cell] = {"ok": True, **r}
                n_ok += 1
                print(f"[OK]   {cell}  ({time.time() - t0:.0f}s)",
                      flush=True)
            except Exception as e:
                results[cell] = {"ok": False, "error": repr(e),
                                 "traceback": traceback.format_exc()}
                n_fail += 1
                print(f"[FAIL] {cell}: {e}", flush=True)
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
    print(f"done: {n_ok} ok, {n_fail} failed -> {args.out}")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
