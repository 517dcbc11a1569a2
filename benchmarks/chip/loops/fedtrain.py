"""Closed loop of a federated mask-training job: local train steps of
every cohort back to back, the next issued as the last returns, and a
round after every `round_every`-th step counted by global step.  Each
round's metrics go to host floats, as the launcher does for its ledger;
nothing else waits on the device inside the window.

Set-up: the program's launch plan (`plan.step_fn`, `plan.round_fn`), the
token stream, and the state made from the seed.  The round step runs
once on that state (its check), then the state is made again and the
train step runs `check_steps` times through the window's own call and
feed (the training check); that same state goes on into the window.
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.chip import checks
from benchmarks.chip import harness as H

span = jax.profiler.TraceAnnotation
from benchmarks.chip import lmdata


class Loop:
    def __init__(self, cell, seed):
        self.cell, self.seed = cell, seed
        self.t = cell.traffic

    # -- set-up ------------------------------------------------------------

    def setup(self):
        t, cell = self.t, self.cell
        lap = H.Laps()
        self.plan, shapes = H.launch_plan(cell)
        self.make_state = H.state_maker(cell, shapes)
        k = H.keys(self.seed)
        self.key_params, self.key_batch = k["params"], k["batch"]
        vocab = cell.family.program_arch(cell.config)["vocab"]
        self.toks = lmdata.stream_on_host_cpu(
            k["stream"], t["stream_tokens"], vocab, t["zipf_alpha"])
        self.make_batch = lmdata.cohort_batch(t["cohorts"], t["batch"],
                                              t["seq"])
        lap("plan_and_tokens")
        # the round, on the state the seed made
        state = self.make_state(self.key_params)
        state, rm = self.plan.round_fn(state)
        self.round_metrics = {k: float(v) for k, v in rm.items()}
        self.round_levels, self.round_rows = checks.capture_round(
            cell, state["scores"])
        del state
        lap("round_checked")
        # the first train steps, through the window's call and feed
        self.state = self.make_state(self.key_params)
        self.batches, losses = [], []
        for g in range(t["check_steps"]):
            batch = self.next_batch(g)
            self.batches.append(np.asarray(batch["tokens"]))
            self.state, m = self.plan.step_fn(self.state, batch)
            losses.append(float(m["loss"]))
            if g == 0:
                grads = checks.leaf_norms(self.state["opt_m"])
        self.prog = {"losses": losses, "grads": grads,
                     "delta": checks.change_norms(cell, self.state,
                                                  self.key_params)}
        self.g = t["check_steps"]
        jax.block_until_ready(self.state)
        lap("steps_checked")
        self.setup_laps = lap.laps

    def next_batch(self, g):
        return self.make_batch(jax.random.fold_in(self.key_batch, g),
                               self.toks)

    # -- the window --------------------------------------------------------

    def window(self, seconds):
        t = self.t
        steps = rounds = failed = 0
        round_ms = []
        t0 = H.now()
        end = t0 + seconds
        with span("window"):
            while H.now() < end:
                with span("make_batch"):
                    batch = self.next_batch(self.g)
                with span("train_dispatch"):
                    self.state, m = self.plan.step_fn(self.state, batch)
                self.g += 1
                steps += 1
                if self.g % t["round_every"] == 0:
                    # the job reports the loss it reached at each round
                    with span("loss_to_host"):
                        loss = float(m["loss"])
                    r0 = H.now()
                    with span("round_dispatch"):
                        self.state, rm = self.plan.round_fn(self.state)
                    with span("round_metrics_to_host"):
                        rm = {k: float(v) for k, v in rm.items()}
                    round_ms.append((H.now() - r0) * 1e3)
                    rounds += 1
                    failed += not all(np.isfinite(list(rm.values())
                                                  + [loss]))
            jax.block_until_ready(self.state)
        wall = H.now() - t0
        tokens = steps * t["cohorts"] * t["batch"] * t["seq"]
        return {"window_s": wall, "attempted": steps + rounds,
                "failed": failed, "steps": steps, "rounds": rounds,
                "tokens": tokens,
                "e2e": {"train_tokens_per_s": tokens / wall},
                "round_ms_host": round_ms}

    def free(self):
        del self.state, self.plan, self.toks

    # -- the check ---------------------------------------------------------

    def check(self):
        ref = checks.reference_train(self.cell, self.seed, self.batches)
        out = checks.compare_train(self.prog, ref)
        checks.print_leaves(self.prog, ref)
        thetas, bits = checks.reference_round(self.cell, self.seed)
        out.update(checks.compare_round(
            self.cell, thetas, bits, self.round_levels, self.round_rows,
            self.round_metrics["bits_measured"]))
        return out
