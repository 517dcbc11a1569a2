"""Closed loop of the mask round alone: the program's round step back to
back on one federated state, the next issued as the last's metrics reach
the host (the barrier every cohort waits at).  Latency of one round is
from its dispatch to its metrics on the host.

Set-up: the launch plan, the state made from the seed, and one round on
it (the round's check, and its warm-up); the window continues from the
state that round produced.
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.chip import checks
from benchmarks.chip import harness as H

span = jax.profiler.TraceAnnotation


class Loop:
    def __init__(self, cell, seed):
        self.cell, self.seed = cell, seed

    def setup(self):
        lap = H.Laps()
        self.plan, shapes = H.launch_plan(self.cell)
        make_state = H.state_maker(self.cell, shapes)
        self.state = make_state(H.keys(self.seed)["params"])
        jax.block_until_ready(self.state)
        lap("plan_and_state")
        self.state, rm = self.plan.round_fn(self.state)
        self.round_metrics = {k: float(v) for k, v in rm.items()}
        self.round_levels, self.round_rows = checks.capture_round(
            self.cell, self.state["scores"])
        jax.block_until_ready(self.state)
        lap("round_checked")
        self.setup_laps = lap.laps

    def window(self, seconds):
        lat, failed = [], 0
        t0 = H.now()
        end = t0 + seconds
        with span("window"):
            while H.now() < end:
                r0 = H.now()
                with span("round_dispatch"):
                    self.state, rm = self.plan.round_fn(self.state)
                with span("round_metrics_to_host"):
                    rm = [float(v) for v in rm.values()]
                lat.append(H.now() - r0)
                failed += not all(np.isfinite(rm))
            jax.block_until_ready(self.state)
        wall = H.now() - t0
        n = len(lat)
        return {"window_s": wall, "attempted": n, "failed": failed,
                "rounds": n,
                "e2e": {"round_ms": wall / n * 1e3,
                        "round_p95_ms": H.percentile(lat, 95) * 1e3},
                "round_ms_host": [x * 1e3 for x in lat]}

    def free(self):
        del self.state, self.plan

    def check(self):
        thetas, bits = checks.reference_round(self.cell, self.seed)
        return checks.compare_round(
            self.cell, thetas, bits, self.round_levels, self.round_rows,
            self.round_metrics["bits_measured"])
