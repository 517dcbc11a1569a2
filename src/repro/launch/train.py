"""Production training launcher.

    python -m repro.launch.train --arch internlm2-1.8b --smoke \
        --steps 100 --round-every 10 --ckpt-dir /tmp/ckpt

On a CPU-only host use --smoke (reduced config, 1 device); on one TPU
chip, --layers N keeps the published widths and cuts the depth to what
fits (chip_smoke.py runs internlm2-1.8b at 4 layers). Handles:
  * checkpoint/restart (atomic, async)
  * round-boundary mask exchange (the paper's protocol)
  * elastic re-entry: --cohorts may differ across restarts; theta is
    mesh-agnostic so the run continues
  * any registered algorithm with a launch plan via --algo (e.g.
    fedavg, the 32-Bpp reference); names resolve through repro.api —
    there is no per-algorithm dispatch here
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import api as fedapi
from repro.api import codecs as codecs_lib
from repro.configs import get_config
from repro.models import build_model
from repro.data import synthetic
from repro.launch import steps as steplib
from repro.launch import plans as planlib  # noqa: F401  (registers plans)
from repro.launch import mesh as meshlib
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime import elastic, fault
from repro import ckpt as ckptlib


def main(argv=None) -> dict:
    """Run the launcher; returns the last step's and last round's
    metrics with wall times (``first_step_s`` includes nothing but the
    first step: the step program is compiled ahead, in ``compile_s``)
    and the compiled step program itself (``compiled_step``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the depth to N layers at published widths "
                         "(0 = the config's own depth)")
    ap.add_argument("--algo", default="fedpm_reg",
                    choices=list(fedapi.launchable()))
    ap.add_argument("--codec", default="arithmetic",
                    choices=[c for c in codecs_lib.available()
                             if c != "float32"],
                    help="wire codec metering the mask uplink")
    ap.add_argument("--downlink-bits", type=int, default=8,
                    help="k-bit stochastic theta broadcast "
                         "(0 = raw float32 downlink)")
    ap.add_argument("--lam", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=17,
                    help="run seed for every mask stream (forward and "
                         "uplink) — two runs with the same seed sample "
                         "bit-identical masks")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--round-every", type=int, default=10)
    ap.add_argument("--cohorts", type=int, default=2)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.3)
    ap.add_argument("--score-opt", default="momentum",
                    choices=["momentum", "adam"])
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--fail-prob", type=float, default=0.0,
                    help="per-round iid cohort failure probability; "
                         "the round aggregation renormalizes over "
                         "survivors")
    ap.add_argument("--pod-size", type=int, default=0,
                    help="cohorts per failure domain (0 = independent "
                         "failures); whole pods drop together")
    ap.add_argument("--pod-outage-prob", type=float, default=0.0,
                    help="per-round correlated pod outage probability")
    ap.add_argument("--quorum-frac", type=float, default=1.0,
                    help="straggler cut: keep the fastest fraction of "
                         "surviving cohorts each round (1.0 = wait "
                         "for everyone)")
    ap.add_argument("--tree-fanout", type=int, default=0,
                    help="cohorts per edge aggregator (0 = flat "
                         "aggregation); with a tree, each round's root "
                         "traffic is one O(params) pooled fold record "
                         "per surviving edge (runtime/agg_tree.py)")
    ap.add_argument("--mesh", default="",
                    help="pod,data,model: run on that device mesh, one "
                         "cohort slice per pod (data and model 1), the "
                         "round all-gathering the packed words over "
                         "pod (e.g. 4,1,1 on a four-chip host)")
    ap.add_argument("--agg-fault-prob", type=float, default=0.0,
                    help="per-round edge-aggregator crash probability "
                         "(requires --tree-fanout); cohorts of a "
                         "crashed edge miss the barrier round")
    args = ap.parse_args(argv)
    if args.agg_fault_prob > 0 and args.tree_fanout <= 0:
        ap.error("--agg-fault-prob requires --tree-fanout > 0")

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke, layers=args.layers)
    api = build_model(cfg)
    key = jax.random.PRNGKey(args.seed)
    scfg = steplib.StepConfig(lam=args.lam, lr=args.lr,
                              optimizer=args.score_opt,
                              downlink_bits=args.downlink_bits,
                              seed=args.seed)

    mesh = None
    if args.mesh:
        shape = tuple(int(n) for n in args.mesh.split(","))
        if len(shape) != 3 or args.cohorts % shape[0]:
            ap.error(f"--mesh {args.mesh}: give pod,data,model with pod "
                     f"dividing --cohorts {args.cohorts}")
        mesh = meshlib.make_debug_pod_mesh(*shape)
    plan = fedapi.get_launch_plan(args.algo)(
        api, scfg, key=key, cohorts=args.cohorts,
        optimizer=args.score_opt, codec=args.codec, mesh=mesh)
    state, step_fn, round_fn = plan.state, plan.step_fn, plan.round_fn

    # hierarchical aggregator tree (runtime/agg_tree.py): the barrier
    # round has no retransmit window, so edge faults collapse to
    # participation masking, and the edge -> root hop is metered from
    # the static cost model — one O(params) pooled record per
    # surviving edge, independent of the cohort count
    topo, tree_edge_bits = None, 0
    if args.tree_fanout > 0:
        from repro.analysis import comm_model
        from repro.runtime import agg_tree
        if not (isinstance(state, dict) and "scores" in state):
            ap.error(f"--tree-fanout: algo '{args.algo}' carries no "
                     "mask scores to pool at an edge")
        _leaves = lambda t: (
            l for l in jax.tree_util.tree_leaves(
                t, is_leaf=lambda x: x is None) if l is not None)
        leaf_params = [int(np.prod(l.shape[1:]))
                       for l in _leaves(state["scores"])]
        float_elems = sum(int(np.prod(l.shape[1:]))
                          for l in _leaves(state.get("floats")))
        topo = agg_tree.TreeTopology(args.cohorts, args.tree_fanout,
                                     agg_fault_prob=args.agg_fault_prob,
                                     seed=args.seed)
        rec = comm_model.tree_root_record_bits(
            leaf_params, acc_bits=topo.cfg.acc_bits, n_classes=1,
            float_elems=float_elems, n_metrics=0)
        tree_edge_bits = rec["wire_bits"] + rec["sidecar_bits"]
        print(f"tree: {topo.n_edges} edge(s) at fanout "
              f"{args.tree_fanout}, root record "
              f"{tree_edge_bits}b/edge (static)")

    start = 0
    saver = None
    if args.ckpt_dir:
        saver = ckptlib.AsyncCheckpointer(args.ckpt_dir, keep=2)
        if ckptlib.latest_step(args.ckpt_dir) is not None:
            try:
                state, start = ckptlib.restore_checkpoint(args.ckpt_dir,
                                                          state)
                print(f"resumed at step {start}")
            except (KeyError, ValueError):
                # structure mismatch (elastic resize / optimizer
                # switch): carry the learned theta/float signal over,
                # rebuild the rest (runtime/elastic.py)
                state, start = elastic.restore_theta_only(
                    args.ckpt_dir, state)
                print(f"structure mismatch: theta-only partial "
                      f"restore at step {start}")

    toks = synthetic.make_lm_stream(key, 500_000, cfg.vocab)
    faulty = (args.fail_prob > 0 or args.pod_outage_prob > 0
              or args.quorum_frac < 1.0)
    sim = (fault.FaultSimulator(args.cohorts, fail_prob=args.fail_prob,
                                pod_size=args.pod_size,
                                pod_outage_prob=args.pod_outage_prob,
                                seed=args.seed)
           if faulty else None)
    policy = (fault.StragglerPolicy(quorum_frac=args.quorum_frac)
              if args.quorum_frac < 1.0 else None)
    # the ledger must survive restarts or cumulative MB under-reports;
    # it rides next to the checkpoints as a tiny json sidecar
    ledger = fedapi.CommLedger()
    ledger_path = (os.path.join(args.ckpt_dir, "comm_ledger.json")
                   if args.ckpt_dir else None)
    if start > 0 and ledger_path and os.path.exists(ledger_path):
        with open(ledger_path) as f:
            ledger = fedapi.CommLedger(**json.load(f))
        print(f"resumed ledger: {ledger.total_mb:.2f}MB over "
              f"{ledger.rounds} rounds")

    # the step program is compiled ahead of the loop, so compile time
    # and step time are reported apart
    batch = plan.make_batch(jax.random.fold_in(key, start), toks,
                            args.batch, args.seq)
    t0 = time.perf_counter()
    step_exec = step_fn.lower(state, batch).compile()
    out = {"compile_s": time.perf_counter() - t0,
           "compiled_step": step_exec, "rounds": 0}
    step_times, round_times = [], []
    t0 = time.time()
    for step in range(start, args.steps):
        if step > start:
            batch = plan.make_batch(jax.random.fold_in(key, step), toks,
                                    args.batch, args.seq)
        ts = time.perf_counter()
        state, m = step_exec(state, batch)
        out["loss"] = float(m["loss"])
        step_times.append(time.perf_counter() - ts)
        if round_fn is not None and (step + 1) % args.round_every == 0:
            # draws are keyed by (seed, round index), NOT a mutable
            # generator cursor: a resumed run replays the identical
            # fault sequence from any restart point
            round_idx = (step + 1) // args.round_every
            alive = (sim.sample_round(policy, round_idx=round_idx)
                     if sim is not None else None)
            if topo is not None:
                base = (np.asarray(alive, bool) if alive is not None
                        else np.ones(args.cohorts, bool))
                masked = topo.round_mask(base, round_idx)
                # rescue: a round never folds an empty cohort — if
                # aggregator faults orphan every surviving client,
                # the root adopts them directly this round
                alive = masked if masked.any() else base
            # survivor-renormalized aggregation: the participation
            # vector gates which cohorts' masks the round folds
            ts = time.perf_counter()
            state, rm = (round_fn(state) if alive is None
                         else round_fn(state, jnp.asarray(alive)))
            rm = {k: float(v) for k, v in rm.items()}
            round_times.append(time.perf_counter() - ts)
            out.update(rounds=out["rounds"] + 1, uplink_bpp=rm["bpp"],
                       bpp_measured=rm["bpp_measured"],
                       bits_measured=rm["bits_measured"])
            upd = {"uplink_bits_measured": rm["bits_measured"],
                   "downlink_bits": rm["downlink_bits"]}
            if topo is not None:
                upd["root_bits_measured"] = float(
                    topo.surviving_edges(round_idx) * tree_edge_bits)
            ledger.update(upd)
            msg = (f"step {step+1}: loss={out['loss']:.3f} "
                   f"uplink={rm['bpp']:.3f}Bpp "
                   f"(wire {rm['bpp_measured']:.3f}Bpp "
                   f"{args.codec}) cum={ledger.total_mb:.2f}MB")
            if alive is not None:
                msg += f" alive={alive.sum()}/{args.cohorts}"
            if topo is not None:
                msg += (f" edges={topo.surviving_edges(round_idx)}"
                        f"/{topo.n_edges} root={ledger.root_mb:.3f}MB")
            print(msg + f" ({time.time()-t0:.0f}s)", flush=True)
            if saver:
                saver.save(step + 1, state)
                os.makedirs(args.ckpt_dir, exist_ok=True)
                tmp = ledger_path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump({"uplink_bits": ledger.uplink_bits,
                               "downlink_bits": ledger.downlink_bits,
                               "root_bits": ledger.root_bits,
                               "rounds": ledger.rounds}, f)
                os.replace(tmp, ledger_path)
        elif (step + 1) % 10 == 0:
            print(f"step {step+1}: loss={out['loss']:.3f}", flush=True)
    # wall times in seconds: the first call of each program apart from
    # the mean of the later ones (the first round also compiles)
    for name, ts in (("step", step_times), ("round", round_times)):
        if ts:
            out[f"first_{name}_s"] = ts[0]
        if len(ts) > 1:
            out[f"{name}_s"] = sum(ts[1:]) / len(ts[1:])
    if saver:
        saver.close()
    if ledger.rounds:
        msg = (f"comm: {ledger.rounds} rounds, "
               f"up={ledger.uplink_mb:.2f}MB "
               f"down={ledger.downlink_mb:.2f}MB "
               f"total={ledger.total_mb:.2f}MB")
        if ledger.root_bits:
            msg += f" root={ledger.root_mb:.3f}MB"
        print(msg)
    print("done")
    return out


if __name__ == "__main__":
    main()
