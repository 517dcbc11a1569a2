#!/usr/bin/env python3
"""Readings of the control and of planted faults, for setting limits.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3

The control is the reference put in the program's place one precision
below what the configuration states: activations held in float8 (e4m3)
before every matmul where the program holds bfloat16, and the round's
sigmoid taken of bfloat16-rounded scores where the program uses
float32.  The faults (training cells) are planted in the reference put
in the program's place: half of each cohort's batch left out with the
loss the mean over the rest, and one token of every step's batch
altered where the feed produces it.  A step that returns its state
unchanged reads 1 on change_gap by that number's measure and needs no
run.  Each reading is one line of JSON on stdout.  The benchmark's own
runs never run this.
"""
import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from benchmarks.chip import harness as H  # noqa: E402


def batches_for(cell, seed, n):
    """The token batches the program's first `n` steps are fed."""
    import jax
    import numpy as np
    from benchmarks.chip import lmdata
    t = cell.traffic
    k = H.keys(seed)
    vocab = cell.family.program_arch(cell.config)["vocab"]
    toks = lmdata.stream_on_host_cpu(k["stream"], t["stream_tokens"], vocab,
                                     t["zipf_alpha"])
    make = lmdata.cohort_batch(t["cohorts"], t["batch"], t["seq"])
    return [np.asarray(make(jax.random.fold_in(k["batch"], g), toks)
                       ["tokens"]) for g in range(n)]


def readings(cell, seed):
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.chip import checks
    out = []
    if cell.traffic["loop"] == "fedtrain":
        vocab = cell.family.program_arch(cell.config)["vocab"]
        b = batches_for(cell, seed, cell.traffic["check_steps"])
        ref = checks.reference_train(cell, seed, b)
        runs = {"control": dict(act=jnp.float8_e4m3fn),
                "half_batch": dict(batch_rows=cell.traffic["batch"] // 2)}
        altered = [x.copy() for x in b]
        for x in altered:
            x[0, 0, -1] = (x[0, 0, -1] + 1) % vocab
        for name, kw in runs.items():
            got = checks.reference_train(cell, seed, b, **kw)
            out.append((name, checks.compare_train(got, ref)))
        got = checks.reference_train(cell, seed, altered)
        out.append(("token_altered", checks.compare_train(got, ref)))
    thetas, bits = checks.reference_round(cell, seed)
    ctrl, cbits = checks.reference_round(cell, seed, sig_dtype=jnp.bfloat16)
    nbits = cell.traffic["downlink_bits"]
    levels = {p: np.floor(np.asarray(v) * ((1 << nbits) - 1))
              for p, v in ctrl.items()}
    del ctrl
    out.append(("control", checks.compare_round(cell, thetas, bits, levels,
                                                0, cbits)))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = H.load_cell(args.workload)
    jax = H.preflight(cell.chips)
    H.enable_compile_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        for name, nums in readings(cell, seed):
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "run": name, "numbers": nums,
                              "kind": jax.devices()[0].device_kind}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
