"""What every cell shares: finding the cell's files by name, the checks
before a run, the program's launch plan, and the state made from the
seed.

Everything that belongs to one configuration, traffic mix or metric is
found by its name in BENCHMARK.json: `configs/<config>.json`,
`traffic/<mix>.json` (which names `loops/<loop>.py`),
`reference/<family>.py` and `flops/<family>.py` (the family is named in
the configuration), `metrics/<metric>.py`, and `checks/<cell>.json`
(the limits of the numbers that decide `correct`).
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
import importlib.util
import json
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
CACHE_DIR = CHECKOUT / ".jax_cache"
KNOBS = ("REPRO_REF_BWD", "REPRO_EFF_PATH", "REPRO_FORCE_INTERPRET")


class BenchError(RuntimeError):
    """The run cannot produce a result (exit non-zero, no result line)."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_file_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    bench: dict

    @property
    def family(self):
        return importlib.import_module(
            f"benchmarks.chip.reference.{self.config['family']}")

    @property
    def flops(self):
        return importlib.import_module(
            f"benchmarks.chip.flops.{self.config['family']}")

    @property
    def loop(self):
        return importlib.import_module(
            f"benchmarks.chip.loops.{self.traffic['loop']}")

    @functools.cached_property
    def leaf_maker(self):
        """One compiled (key) -> ({path: weight or float leaf}, {path:
        score}), shared by everything that makes the state from the
        seed: the same executable gives the same bits, where two programs
        that each fuse the generator may round its logs and inverse error
        functions differently."""
        import jax
        from benchmarks.chip.reference import common as R
        fam = self.family
        specs = fam.specs(self.config)
        return jax.jit(lambda k: R.init_leaves(specs, k, fam.float_init))

    def end_to_end(self):
        """The cell's end-to-end metric entries."""
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self):
        """The cell's per-layer metric entries: those listing the cell,
        and those without a list whose end-to-end metric it reports."""
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]


def load_cell(name: str) -> Cell:
    bench = load_json(CHECKOUT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(CHECKOUT / conf["file"]),
                traffic=load_json(HERE / "traffic" / f"{w['traffic']}.json"),
                limits=load_json(HERE / "checks" / f"{name}.json"),
                bench=bench)


def preflight(chips: int):
    """Refuse to measure anything but the default fused path on enough
    accelerator chips; returns jax."""
    set_knobs = [k for k in KNOBS if os.environ.get(k)]
    if set_knobs:
        raise BenchError(f"path knobs set: {set_knobs}")
    if not (CHECKOUT / "src" / "repro").is_dir():
        raise BenchError(f"no program at {CHECKOUT / 'src'}")
    sys.path.insert(0, str(CHECKOUT / "src"))
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is "
                         f"{devs[0].platform}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    from repro.kernels import ops
    if ops._use_interpret():
        raise BenchError("Pallas kernels would be interpreted")
    return jax


def enable_compile_cache():
    """JAX's persistent compilation cache at <checkout>/.jax_cache (the
    program's own default), or where JAX_COMPILATION_CACHE_DIR says."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileLog:
    """Counts JAX's compilations and persistent-cache hits and misses,
    so a run can show that set-up found its programs in the cache and
    that nothing compiled inside the window."""
    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax
        self.counts = {"compiles": 0, "cache_hits": 0, "cache_misses": 0}
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(
            self._duration)

    def _event(self, name, **_):
        if name == self.HIT:
            self.counts["cache_hits"] += 1
        elif name == self.MISS:
            self.counts["cache_misses"] += 1

    def _duration(self, name, secs, **_):
        if name == self.COMPILE:
            self.counts["compiles"] += 1
            self.compile_s += secs

    def snapshot(self):
        return dict(self.counts, compile_s=self.compile_s)


def keys(seed: int):
    """Independent keys for the parameters, the token stream and the
    batches, all from the seed."""
    import jax
    base = jax.random.PRNGKey(seed)
    return {n: jax.random.fold_in(base, i)
            for i, n in enumerate(("params", "stream", "batch"))}


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------


def step_config(cell: Cell):
    """The program's step configuration.  Its run seed (mixed into every
    mask stream) is the traffic's, not the benchmark's `--seed`: the
    program compiles it into the train and round steps as a constant,
    so a seed of its own per run would compile both again in every
    run's set-up."""
    from repro.launch import steps as steplib
    t = cell.traffic
    return steplib.StepConfig(lam=t["lam"], lr=t["lr"],
                              momentum=t["momentum"],
                              float_lr=t["float_lr"],
                              optimizer=t["optimizer"],
                              downlink_bits=t["downlink_bits"],
                              seed=t["run_seed"])


def program_model(cell: Cell):
    from repro.configs import get_config
    from repro.models import build_model
    base = get_config(cell.config["program_arch"])
    cfg = dataclasses.replace(base, **cell.family.program_arch(cell.config))
    return build_model(cfg)


def launch_plan(cell: Cell):
    """The program's launch plan for the cell's algorithm, and the shapes
    of its state.  The plan is built under `jax.eval_shape`, so the
    program's own initial state is never made: the benchmark makes the
    state from the seed (`state_maker`)."""
    import jax
    from repro import api as fedapi
    from repro.launch import plans  # noqa: F401  (registers the plans)
    api = program_model(cell)
    scfg = step_config(cell)
    t = cell.traffic
    held = {}

    def build(key):
        held["plan"] = fedapi.get_launch_plan(t["algo"])(
            api, scfg, key=key, cohorts=t["cohorts"],
            optimizer=t["optimizer"], codec=t["codec"])
        return held["plan"].state

    shapes = jax.eval_shape(build, jax.random.PRNGKey(0))
    return held["plan"], shapes


def _is_none(x):
    return x is None


def program_paths(tree):
    """[(path tuple, leaf)] of a program state tree, None leaves kept."""
    import jax
    flat = jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_none)[0]
    return [(tuple(k.key for k in p), leaf) for p, leaf in flat]


def state_maker(cell: Cell, shapes):
    """A (key) -> program state: frozen weights, scores, float leaves
    and zero momentum from the reference family's leaf table
    (`reference/<family>.py`), laid out in the program's state tree with
    the cohort axis in front.  Refuses a program tree that disagrees."""
    import jax
    import jax.numpy as jnp
    from benchmarks.chip.reference import common as R
    fam = cell.family
    specs = fam.specs(cell.config)
    C = cell.traffic["cohorts"]
    wdef = jax.tree_util.tree_structure(shapes["weights"], is_leaf=_is_none)
    wpaths = program_paths(shapes["weights"])
    if sorted(p for p, _ in wpaths) != R.sorted_paths(specs):
        raise BenchError("the program's parameter tree differs from the "
                         f"reference's: {[p for p, _ in wpaths]}")
    parts = {k: dict(program_paths(shapes[k]))
             for k in ("weights", "scores", "floats")}
    for p, (shape, dtype, kind) in specs.items():
        masked = kind == "masked"
        want = {"weights": (shape, dtype) if masked else None,
                "scores": ((C,) + shape, jnp.float32) if masked else None,
                "floats": None if masked else ((C,) + shape, dtype)}
        for k, v in want.items():
            got = parts[k][p]
            got = None if got is None else (tuple(got.shape),
                                            jnp.dtype(got.dtype))
            if got != (None if v is None else (tuple(v[0]),
                                               jnp.dtype(v[1]))):
                raise BenchError(f"{k} {p}: program {got}, reference {v}")

    def make(leaves):
        vals, scores = leaves
        rep = lambda a: jnp.broadcast_to(a[None], (C,) + a.shape)
        order = [p for p, _ in wpaths]
        tree = lambda f: jax.tree_util.tree_unflatten(
            wdef, [f(p) for p in order])
        masked = lambda p: specs[p][2] == "masked"
        sc = tree(lambda p: rep(scores[p]) if masked(p) else None)
        return {"weights": tree(lambda p: vals[p] if masked(p) else None),
                "scores": sc,
                "floats": tree(lambda p: None if masked(p)
                               else rep(vals[p])),
                "opt_m": jax.tree_util.tree_map(
                    lambda a: None if a is None else jnp.zeros_like(a),
                    sc, is_leaf=_is_none),
                "step": jnp.zeros((), jnp.int32)}

    gen = cell.leaf_maker
    made = jax.jit(make)
    dev = jax.devices()[0]
    # committed to the device, as the step's own outputs are: the jit
    # cache tells the two apart, and the window must not compile again
    return lambda key: jax.device_put(made(gen(key)), dev)


# ---------------------------------------------------------------------------
# Host spans
# ---------------------------------------------------------------------------


class Laps:
    """Seconds of each named phase of set-up, in order."""

    def __init__(self):
        self.laps, self._t = {}, time.perf_counter()

    def __call__(self, name):
        t = time.perf_counter()
        self.laps[name], self._t = t - self._t, t


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation."""
    v = sorted(values)
    if not v:
        return float("nan")
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def now() -> float:
    return time.perf_counter()

