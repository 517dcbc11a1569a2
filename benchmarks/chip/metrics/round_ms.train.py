"""Median host-clock time of a round inside the training window: from
the round step's dispatch (after the last train step's loss reached the
host) to its metrics as host floats (launch/steps.make_round_step)."""
import statistics


def read(r):
    ms = r.window.get("round_ms_host")
    return statistics.median(ms) if ms else None
