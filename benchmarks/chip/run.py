#!/usr/bin/env python3
"""Chip benchmark: one run of one cell on the machine it is started on.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (`BENCHMARK.json` -> workloads) names a configuration and a
traffic mix; their files, the loop that drives the program, the plain
reference, the operation counts and the metric readers are all found by
name (see `harness.py`).  One process: set-up (imports, the program's
launch plan, the state and the tokens made from the seed, warm-up and
the first calls that the correctness check follows), a window of
`--seconds` of the cell's closed loop, then the reference check once the
program's state is freed.

With `--trace 0` the result line carries the cell's end-to-end metrics;
with `--trace 1` the window runs under the profiler and the line carries
the per-layer metrics, the device's busy and window seconds, and a
breakdown.  The last stdout line is the result JSON; the numbers that
decide `correct` are printed beside their limits as the last stderr
lines and under the result's last key, `checks`.

Without a TPU (or fewer chips than the cell asks for), with the Pallas
kernels interpreted, or with a REPRO_* path knob set, it exits non-zero
and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from benchmarks.chip import harness as H  # noqa: E402

TRACE_DIR = H.CHECKOUT / ".bench_trace"
SPANS = ("make_batch", "train_dispatch", "loss_to_host", "round_dispatch",
         "round_metrics_to_host")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class Reading:
    """What a per-layer metric reader (`metrics/<name>.py`) reads."""

    def __init__(self, cell, window, summary, peaks):
        self.cell, self.window, self.trace, self.peaks = (
            cell, window, summary, peaks)
        self.flops = cell.flops
        self.config, self.traffic = cell.config, cell.traffic


def peaks_for(kind: str) -> dict:
    table = H.load_json(H.HERE / "peaks.json")
    if kind not in table:
        raise H.BenchError(f"no peaks for device kind {kind!r} in "
                           "peaks.json")
    return table[kind]


def per_layer(cell, reading):
    out = {}
    for m in cell.per_layer():
        mod = H.load_file_module(H.HERE / "metrics" / f"{m['name']}.py",
                                 "chipbench_metric_" + m["name"]
                                 .replace(".", "_"))
        v = mod.read(reading)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def traced_window(loop, seconds):
    import jax
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(str(TRACE_DIR))
    try:
        w = loop.window(seconds)
    finally:
        jax.profiler.stop_trace()
    from benchmarks.chip import trace_reduce
    summary = trace_reduce.reduce(str(TRACE_DIR), spans=SPANS)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return w, summary


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = H.load_cell(args.workload)
        jax = H.preflight(cell.chips)
        dev = jax.devices()[0]
        peaks = peaks_for(dev.device_kind)
    except (H.BenchError, FileNotFoundError, KeyError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    H.enable_compile_cache()
    compiles = H.CompileLog()
    loop = cell.loop.Loop(cell, args.seed)
    loop.setup()
    setup_s = H.now() - T0
    in_setup = compiles.snapshot()

    if args.trace:
        w, summary = traced_window(loop, args.seconds)
    else:
        w, summary = loop.window(args.seconds), None
    in_window = compiles.counts["compiles"] - in_setup["compiles"]
    devs = jax.devices()[:cell.chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devs)
    loop.free()
    gc.collect()

    numbers = loop.check()
    missing = sorted(set(cell.limits) - set(numbers))
    if missing:
        print(f"run.py: checks/{cell.name}.json limits {missing}, which "
              "the cell's check does not read", file=sys.stderr)
        return 2
    # a number without a limit in checks/<cell>.json is printed, not
    # compared (PERF.md says why for each)
    checks = {k: {"value": v, "limit": cell.limits.get(k)}
              for k, v in numbers.items()}
    correct = (all(v == v and v <= cell.limits[k]
                   for k, v in numbers.items() if k in cell.limits)
               and w["failed"] == 0)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    if args.trace:
        reading = Reading(cell, w, summary, peaks)
        metrics = per_layer(cell, reading)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
    else:
        names = {m["name"]: m["unit"] for m in cell.end_to_end()}
        vals = dict(w["e2e"], setup_s=setup_s)
        metrics = {n: {"value": float(vals[n]), "unit": u}
                   for n, u in names.items()}
    result = {"correct": bool(correct), "attempted": int(w["attempted"]),
              "failed": int(w["failed"]), "metrics": metrics,
              "device": device}
    if args.trace:
        from benchmarks.chip import trace_reduce
        result["breakdown"] = trace_reduce.breakdown(summary)
    result["compiles"] = {"setup": in_setup, "window": in_window}
    result["checks"] = checks
    print(f"window: {w['window_s']!r} s, attempted {w['attempted']}, "
          f"rounds {w.get('rounds')}, setup {setup_s!r} s; set-up "
          f"compiles {in_setup}, compiles in the window {in_window}; "
          f"set-up phases {getattr(loop, 'setup_laps', {})}",
          file=sys.stderr)
    for k, c in checks.items():
        lim = ("not compared" if c["limit"] is None
               else f"limit {c['limit']!r}")
        print(f"check {k}: {c['value']!r} ({lim})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
