"""Record `data/scoped.xplane.pb.gz` on a TPU, for test_bench_scopes.py.

    python3 tests/benchmark/record_scoped_trace.py <out.xplane.pb.gz>

Three train steps and one round of the program's own steps
(`launch/steps.make_train_step`, `make_round_step`, jitted and donating
their state as the launch plans do) at internlm2's smoke widths, 2
cohorts of batch 2 x seq 128, the fedpm_reg settings of the benchmark's
traffic (8-bit downlink, arithmetic codec).  Each step's loss and each
of the round's five metrics go to the host one by one (8 transfers), all
under the host span `window`.  Both programs are compiled anew, without
the persistent cache, and run once before the trace starts.
"""
import glob
import gzip
import os
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))

STEPS = 3


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import masking
    from repro.launch import steps as steplib
    from repro.models import build_model

    if jax.devices()[0].platform != "tpu":
        print("record_scoped_trace.py: no TPU", file=sys.stderr)
        return 2
    # compiled anew: a cached executable (and the trace's proto of it)
    # keeps the op_names of the version that compiled first
    jax.config.update("jax_enable_compilation_cache", False)
    api = build_model(get_config("internlm2-1.8b", smoke=True))
    scfg = steplib.StepConfig(lam=1.0, lr=0.1, downlink_bits=8)
    train = jax.jit(steplib.make_train_step(api, scfg), donate_argnums=0)
    round_ = jax.jit(steplib.make_round_step(api, scfg,
                                             codec="arithmetic"),
                     donate_argnums=0)
    key = jax.random.PRNGKey(0)
    # committed to the device, as the steps' own outputs are, so the
    # window runs the programs compiled here
    state = jax.device_put(steplib.init_fed_state(
        key, api, masking.MaskSpec(), C=2), jax.devices()[0])
    batches = [{"tokens": jax.random.randint(
        jax.random.fold_in(key, g), (2, 2, 128), 0, 256, jnp.int32)}
        for g in range(STEPS + 1)]
    state, m = train(state, batches[-1])
    float(m["loss"])
    state, rm = round_(state)
    [float(v) for v in rm.values()]
    jax.block_until_ready(state)

    tmp = str(ROOT / ".bench_trace" / "scoped")
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("window"):
        for b in batches[:STEPS]:
            state, m = train(state, b)
            float(m["loss"])
        state, rm = round_(state)
        [float(v) for v in rm.values()]
        jax.block_until_ready(state)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    with open(path, "rb") as f, gzip.open(out, "wb", 9) as g:
        g.write(f.read())
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"{out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
