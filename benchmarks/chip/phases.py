#!/usr/bin/env python3
"""A traced run of one cell that also gives the device time of each
phase the program names.

    python3 benchmarks/chip/phases.py --workload <cell> --seed <n> \
        --seconds <s>

It runs `run.py` with `--trace 1`, unchanged (its result line and its
check come first), and reduces the same trace once more with
`scope_reduce` before `run.py` deletes it.  The train and round steps'
op_names come from their text, compiled anew after the window for the
shapes of their first calls (`scope_reduce.fresh_text`: the fedtrain
cell's traces hold none of their HLO protos, and a cached executable's
may be stale); other modules' from the trace.  The last stdout line is

    {"phases": {module: {"calls", "op_ms", <scope>: ms, ...,
                         "unscoped": ms}},
     "d2h": n, "d2h_per_round": x, "unknown_ops": n,
     "kernels": {kernel: [calls, device s]}}

with times per execution of the module; `kernels` are the calls and
device time that `metrics/*_roofline.py` read (`trace_reduce`).
"""
import json
import pathlib
import sys
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from benchmarks.chip import harness as H  # noqa: E402
from benchmarks.chip import scope_reduce as SR  # noqa: E402
from benchmarks.chip import trace_reduce as TR  # noqa: E402

KERNELS = ("masked_matmul", "masked_matmul_dx", "masked_matmul_ds",
           "sample_and_pack")
VOCAB = SR.TRAIN_SCOPES + SR.ROUND_SCOPES


class FirstCall:
    """A jitted step that keeps the shapes and shardings of its first
    call's arguments, to compile its program's text again later."""

    def __init__(self, fn):
        self.fn, self.args = fn, None

    def __call__(self, *args):
        if self.args is None:
            import jax
            self.args = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=a.sharding), args)
        return self.fn(*args)

    def op_names(self):
        return SR.text_op_names(SR.fresh_text(self.fn, *self.args))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    run = H.load_file_module(H.HERE / "run.py", "chipbench_run_phases")
    seen, steps = {}, []
    reduce, launch_plan = TR.reduce, H.launch_plan

    def recorded_plan(cell):
        plan, shapes = launch_plan(cell)
        for field in ("step_fn", "round_fn"):
            if getattr(plan, field) is not None:
                steps.append(FirstCall(getattr(plan, field)))
                setattr(plan, field, steps[-1])
        return plan, shapes

    def both(path, spans=()):
        seen["summary"] = reduce(path, spans=spans)
        try:
            names = dict(s.op_names() for s in steps if s.args)
            seen["phases"] = SR.reduce(path, VOCAB, op_names=names)
        except Exception:  # run.py's own result stands
            seen["error"] = traceback.format_exc()
        return seen["summary"]

    TR.reduce, H.launch_plan = both, recorded_plan
    try:
        rc = run.main(list(argv) + ["--trace", "1"])
    finally:
        TR.reduce, H.launch_plan = reduce, launch_plan
    if rc or "phases" not in seen:
        print(f"phases.py: {seen.get('error', 'no trace reduced')}",
              file=sys.stderr)
        return rc or 1
    ph, summary = seen["phases"], seen["summary"]
    rounds = ph.module_calls.get("jit_round_step")
    print(json.dumps({
        "phases": ph.table(VOCAB), "d2h": ph.d2h,
        "d2h_per_round": ph.d2h / rounds if rounds else None,
        "unknown_ops": ph.unknown_ops,
        "kernels": {k: [summary.kernel_count((k,)),
                        summary.kernel_s((k,))] for k in KERNELS}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
